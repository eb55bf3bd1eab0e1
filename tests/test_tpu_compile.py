"""The Pallas decode kernels compile for a TPU v5e at real decode widths.

Interpret mode (every other kernel test) never applies the TPU's tiling rules;
the compiler does.  These tests compile — without a chip — for one chip of a
described ``v5e:2x2`` topology, at the published widths of the models the
rollout path serves, and check that the compiled program calls the kernel.

The topology is described inside a fixture, never at import: only one process
may load the TPU library, and pytest-xdist workers all import this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import (decode_attention_pallas,
                                            paged_decode_attention_pallas)

BATCH, PAGE, PAGES = 8, 16, 64          # 8 lanes x 1024 positions of decode KV


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A TPU program written to the persistent cache cannot be read back on a
    CPU host, so keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _lower_kernel(arch, kernel, sharding):
    """One chip's decode kernel call at ``arch``'s published decode widths."""
    cfg = get_config(arch)
    KV, G, hd = cfg.n_kv_heads, cfg.q_groups, cfg.hd
    dtype = jnp.dtype(cfg.dtype)
    assert dtype == jnp.bfloat16

    def spec(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    q = spec((BATCH, KV, G, hd))
    lens = spec((BATCH,), jnp.int32)
    if kernel == "paged":
        pool = spec((BATCH * PAGES + 1, PAGE, KV, hd))
        fn = jax.jit(lambda q, k, v, pt, n: paged_decode_attention_pallas(
            q, k, v, pt, n, interpret=False))
        return fn.lower(q, pool, pool, spec((BATCH, PAGES), jnp.int32), lens)
    cache = spec((BATCH, PAGES * PAGE, KV, hd))
    fn = jax.jit(lambda q, k, v, n: decode_attention_pallas(
        q, k, v, n, interpret=False))
    return fn.lower(q, cache, cache, lens)


@pytest.mark.parametrize("kernel", ["paged", "dense"])
@pytest.mark.parametrize("arch", ["qwen3-1.7b", "smollm-135m"])
def test_decode_kernel_compiles_for_v5e(arch, kernel, one_chip, no_compile_cache):
    lowered = _lower_kernel(arch, kernel, one_chip)
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_kernel_body_holds_no_checkout_path(one_chip):
    """The persistent cache keys a program by its text, the kernel's serialized
    Mosaic body included.  With source locations left out, as
    ``launch.compile_cache`` sets, that body names no file of this checkout, so
    a checkout at another path finds the same cache entries."""
    import base64
    import re

    from repro.launch.compile_cache import CHECKOUT

    was = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        text = _lower_kernel("qwen3-1.7b", "paged", one_chip).as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", was)
    bodies = [base64.b64decode(b) for b in
              re.findall(r"\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22", text)]
    assert bodies
    assert not any(str(CHECKOUT).encode() in body for body in bodies)


def test_paged_kernel_compiles_per_head_shard_on_two_chips(topo, no_compile_cache):
    """An mp-2 worker's decode: XLA cannot partition a Mosaic call, so the
    kernel must run under ``shard_map`` over the KV heads (``ops._per_device``)."""
    from functools import partial

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.distributed.sharding import axis_rules
    from repro.kernels.ops import _per_device

    cfg = get_config("qwen3-1.7b")
    KV, G, hd = cfg.n_kv_heads, cfg.q_groups, cfg.hd
    mesh = Mesh(np.asarray(topo.devices[:2]).reshape(1, 2), ("data", "model"))

    def spec(shape, pspec, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=NamedSharding(mesh, pspec))

    def decode(q, k, v, pt, n):
        with axis_rules(mesh):
            return _per_device(partial(paged_decode_attention_pallas, interpret=False),
                               q, k, v, pt, n)

    pool = spec((BATCH * PAGES + 1, PAGE, KV, hd), P(None, None, "model"))
    compiled = jax.jit(decode).lower(
        spec((BATCH, KV, G, hd), P(None, "model")), pool, pool,
        spec((BATCH, PAGES), P(), jnp.int32), spec((BATCH,), P(), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
