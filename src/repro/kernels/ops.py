"""Jitted dispatch wrappers: Pallas kernel on TPU, pure-jnp oracle elsewhere.

The model code calls these; the backend choice is a deployment detail.  On a TPU the
kernels always run compiled; interpret mode exists only off-TPU.  Setting
``REPRO_FORCE_PALLAS=1`` runs the Pallas kernels in interpret mode on CPU (slow —
used by the kernel test sweeps, not by the engine or dry-run).
"""

from __future__ import annotations

import os
from functools import partial

import jax
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import current_mesh
from repro.kernels import ref
from repro.kernels.decode_attention import (decode_attention_pallas,
                                            paged_decode_attention_pallas)
from repro.kernels.mamba_scan import mamba_scan_pallas, mamba_scan_ref


def _use_pallas() -> bool:
    return (os.environ.get("REPRO_FORCE_PALLAS") == "1"
            or jax.default_backend() == "tpu")


def _per_device(kernel, q, k, v, *rest):
    """Run a Pallas decode kernel under the active worker mesh.

    Mosaic calls cannot be partitioned automatically, so a meshed worker runs the
    kernel inside ``shard_map``: each device takes its share of the KV heads
    (axis 1 of ``q``, axis 2 of the caches — where the sharding rules put them).
    A model axis the heads do not divide is an error: running the kernel whole on
    every device would all-gather the KV pool onto each of them."""
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return kernel(q, k, v, *rest)
    heads, mp = q.shape[1], mesh.shape.get("model", 1)
    if heads % mp:
        raise ValueError(
            f"the Pallas decode kernels shard KV heads over the model axis: "
            f"{heads} KV heads do not divide model-parallel degree {mp}")
    q_spec, kv_spec = P(None, "model"), P(None, None, "model")
    return jax.shard_map(kernel, mesh=mesh,
                         in_specs=(q_spec, kv_spec, kv_spec) + (P(),) * len(rest),
                         out_specs=q_spec, check_vma=False)(q, k, v, *rest)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     valid_len: jax.Array, *, force_pallas: bool = False) -> jax.Array:
    """Flash-decode GQA attention: q (B,KV,G,hd) vs cache (B,C,KV,hd).

    ``force_pallas`` routes through the Pallas kernel regardless of backend
    (interpret mode off-TPU) — the ``ModelConfig.use_pallas_decode`` wire.
    """
    if force_pallas or _use_pallas():
        interpret = jax.default_backend() != "tpu"
        return _per_device(partial(decode_attention_pallas, interpret=interpret),
                           q, k, v, valid_len)
    return ref.decode_attention_ref(q, k, v, valid_len)


def paged_decode_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                           page_table: jax.Array, valid_len: jax.Array, *,
                           force_pallas: bool = False) -> jax.Array:
    """Paged flash-decode: q (B,KV,G,hd) vs block pools (NB,ps,KV,hd) gathered
    through a (B,num_pages) page table.  Same dispatch contract as
    :func:`decode_attention`; the reference path gathers the lane view and is
    bit-exact with the dense layout over the valid region."""
    if force_pallas or _use_pallas():
        interpret = jax.default_backend() != "tpu"
        return _per_device(partial(paged_decode_attention_pallas, interpret=interpret),
                           q, k_pool, v_pool, page_table, valid_len)
    return ref.paged_decode_attention_ref(q, k_pool, v_pool, page_table, valid_len)


def mamba_scan(dt: jax.Array, b_in: jax.Array, c_in: jax.Array, x: jax.Array,
               a_log: jax.Array) -> jax.Array:
    """Fused SSM selective scan (see kernels/mamba_scan.py)."""
    if _use_pallas():
        interpret = jax.default_backend() != "tpu"
        return mamba_scan_pallas(dt, b_in, c_in, x, a_log, interpret=interpret)
    # pure-JAX lowering path: the chunked fused scan in models/layers.py is used by
    # the model directly; this oracle covers direct ops-level callers
    return mamba_scan_ref(dt, b_in, c_in, x, a_log)
