"""Production mesh construction (function, not module constant — importing this module
must never touch jax device state)."""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes: the model code places tensors with
    ``with_sharding_constraint``, which ``Explicit`` axes (the default since JAX
    0.7) refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256-chip v5e pod, or 2x16x16 = 512-chip two-pod mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — used by sharding tests."""
    return _auto_mesh((data, model), ("data", "model"))


def carve_worker_meshes(degrees, devices=None):
    """Carve one disjoint ("data", "model") sub-mesh per rollout worker.

    Worker ``i`` with model-parallel degree ``degrees[i]`` gets a ``(1, degrees[i])``
    mesh over the next contiguous block of the device list, so a heterogeneous fleet
    like {4, 2, 1, 1} occupies eight accelerators without overlap.  Whenever the
    devices cover the fleet, every degree-1 worker gets a trivial (1, 1) mesh over
    its own device — leaving it un-meshed would land its params/KV on the *default*
    device, a chip another worker already holds, while its own chip idles.

    A fleet the devices cannot cover (``sum(degrees) > len(devices)``) returns
    ``None`` for every worker, which runs it un-meshed on the default device:
    legal for an all-mp1 fleet (several workers sharing one chip) and for the CPU
    tier-1 environment, where the *declared* degrees still drive the control plane
    (placement, virtual token times).  On a TPU an mp>1 fleet that does not fit is
    an error — it would silently run unsharded.
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    degrees = [int(d) for d in degrees]
    if sum(degrees) > len(devices):
        if any(d > 1 for d in degrees) and devices[0].platform == "tpu":
            raise ValueError(
                f"fleet degrees {degrees} need {sum(degrees)} devices but only "
                f"{len(devices)} are visible")
        return [None] * len(degrees)
    meshes: list[Mesh | None] = []
    off = 0
    for d in degrees:
        block = np.asarray(devices[off:off + d]).reshape(1, d)
        meshes.append(Mesh(block, ("data", "model")))
        off += d
    return meshes
