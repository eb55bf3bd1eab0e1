"""Logical-axis sharding rules: divisibility fallbacks, param/cache spec tables."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.distributed.sharding import (DEFAULT_RULES, axis_rules, cache_pspecs,
                                        dispatch_groups, logical_pspec, param_pspecs,
                                        shard)
from repro.models import model as M


def _mesh11():
    return jax.make_mesh((1, 1), ("data", "model"))


def test_logical_pspec_no_mesh_is_fully_replicated():
    assert logical_pspec((8, 16), ("batch", "d_ff"), mesh=None) == P(None, None)


def test_logical_pspec_divisibility_drops_axis():
    mesh = _mesh11()
    # axis size 1 -> never partition (divisible but pointless); spec stays None
    spec = logical_pspec((9, 16), ("heads", "d_ff"), mesh=mesh)
    assert spec == P(None, None)


def test_param_pspecs_cover_every_leaf():
    """Every parameter of every architecture resolves to a PartitionSpec."""
    mesh = _mesh11()
    for arch in ("smollm_135m", "jamba_v0_1_52b", "qwen2_moe_a2_7b", "xlstm_350m",
                 "whisper_medium", "llama_3_2_vision_11b", "arctic_480b"):
        cfg = get_config(arch).reduced(n_periods=1)
        shapes = jax.eval_shape(lambda c=cfg: M.init_params(c, jax.random.PRNGKey(0)))
        specs = param_pspecs(shapes, mesh)
        leaves_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        leaves_p = jax.tree.leaves(shapes)
        assert len(leaves_s) == len(leaves_p)
        for sp, leaf in zip(leaves_s, leaves_p):
            assert isinstance(sp, P)
            assert len(sp) == leaf.ndim


def test_cache_pspecs_cover_every_leaf():
    mesh = _mesh11()
    for arch in ("qwen3_1_7b", "jamba_v0_1_52b", "xlstm_350m", "whisper_medium"):
        cfg = get_config(arch).reduced(n_periods=1)
        enc = (jnp.zeros((2, cfg.encoder_seq, cfg.d_model))
               if cfg.arch_type == "audio" else None)
        cache = jax.eval_shape(
            lambda c=cfg, e=enc: M.init_cache(c, None, 2, 32, enc_out=e))
        specs = cache_pspecs(cache, mesh)
        leaves_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        assert len(leaves_s) == len(jax.tree.leaves(cache))


def test_kv_caches_shard_heads_before_positions():
    """KV heads take the model axis when they divide it (the Pallas decode
    kernels then run per head shard); positions take it only otherwise."""
    from types import SimpleNamespace
    mesh = SimpleNamespace(axis_names=("data", "model"), devices=np.empty((1, 2)))
    dims = ("batch", "kv_seq", "kv_heads", None)
    assert logical_pspec((4, 16, 8, 128), dims, mesh=mesh) == P(None, None, "model", None)
    assert logical_pspec((4, 16, 3, 64), dims, mesh=mesh) == P(None, "model", None, None)


def test_shard_is_identity_without_mesh():
    x = jnp.ones((4, 8))
    y = shard(x, ("batch", "d_ff"))
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_dispatch_groups_divisibility():
    assert dispatch_groups(1024) == 1          # no mesh active
    with axis_rules(_mesh11()):
        # mesh axes of size 1 -> one group
        assert dispatch_groups(1024) == 1


def test_rules_table_sanity():
    assert DEFAULT_RULES["batch"] == ("pod", "data")
    assert "model" in DEFAULT_RULES["experts"]
    assert "model" in DEFAULT_RULES["kv_seq"]
