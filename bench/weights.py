"""Random weights for a cell, made on the device from the seed in one jitted call.

The tree has the layout the program's model takes (``jax.eval_shape`` of its own
initializer gives the shapes and types, and no values), and the values are the
benchmark's: the plain reference reads the same arrays, so nothing it compares
against was made by the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NORM_LEAVES = ("scale", "q_norm", "k_norm")
OUT_PROJECTIONS = ("wo", "w_out")        # scaled down by sqrt(2 x layers)
STD = 0.02


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def make_params(cfg, key_seed: int):
    """Weights of ``cfg`` in its dtype, from ``key_seed`` (any int below 2**31)."""
    from repro.models import model as M

    shapes = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out_std = STD / math.sqrt(2 * cfg.n_layers)

    def build(key):
        vals = []
        for i, (path, s) in enumerate(leaves):
            name = _leaf_name(path)
            if name in NORM_LEAVES:
                vals.append(jnp.ones(s.shape, s.dtype))
                continue
            std = out_std if name in OUT_PROJECTIONS else STD
            k = jax.random.fold_in(key, i)
            vals.append((jax.random.normal(k, s.shape, jnp.float32) * std).astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, vals)

    return jax.jit(build)(jax.random.PRNGKey(key_seed))
