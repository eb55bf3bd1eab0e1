"""Logical-axis sharding rules (MaxText-style) for every architecture family.

Model code annotates tensors with *logical* axis names; a rules table maps logical names
to physical mesh axes.  ``shard`` applies ``with_sharding_constraint`` only when a mesh is
active (so the same model code runs un-meshed on CPU tests) and silently drops a mesh axis
whose size does not divide the tensor dim — this is how e.g. smollm's 9 attention heads
degrade gracefully to replicated attention on a 16-way model axis while its MLP (d_ff
1536) still shards.

Parameter shardings are derived from a leaf-name table (``PARAM_LOGICAL_AXES``): every
parameter name used by ``repro.models`` maps to the logical axes of its dims.  Stacked
(scan-over-period) params get a leading ``None``.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axis (or tuple of mesh axes)
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": ("model",),
    "d_ff": ("model",),
    "d_inner": ("model",),
    "experts": ("model",),
    "vocab": ("model",),
    "kv_seq": ("model",),     # sequence-sharded decode KV (in LAST_PICK, below)
    "fsdp": ("data",),        # ZeRO-3-style second param axis (arctic-class models
                              # cannot fit on a 16-way model axis alone)
    "act_seq": ("model",),    # sequence-parallel residual stream (Megatron-SP style)
    "dispatch": ("data",),    # MoE dispatch groups (per-data-shard capacity)
    "d_model": (),
    "seq": (),
    "state": (),
}

# logical names that pick a mesh axis only after every other dim of the tensor has
# had its pick: decode KV shards over heads where they divide the model axis (the
# Pallas decode kernels run per head shard) and over positions only otherwise
LAST_PICK: frozenset[str] = frozenset({"kv_seq"})


class _Ctx(threading.local):
    mesh: Optional[Mesh] = None
    rules: dict[str, tuple[str, ...]] = DEFAULT_RULES


_CTX = _Ctx()


@contextlib.contextmanager
def axis_rules(mesh: Optional[Mesh], rules: Optional[dict] = None):
    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh = mesh
    _CTX.rules = dict(DEFAULT_RULES, **(rules or {}))
    try:
        yield
    finally:
        _CTX.mesh, _CTX.rules = prev


def current_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def _mesh_axis_sizes(mesh: Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def logical_pspec(shape: Sequence[int], dims: Sequence[Optional[str]],
                  mesh: Optional[Mesh] = None, rules: Optional[dict] = None) -> P:
    """PartitionSpec for ``shape`` given per-dim logical names.

    A mesh axis is assigned to a dim only if (a) the rules map the logical name to it,
    (b) the axis exists in the mesh, (c) the dim size is divisible by the (product of)
    axis size(s), and (d) the axis is not already used by an earlier dim.  Dims named
    in ``LAST_PICK`` come after all the others.
    """
    mesh = mesh or _CTX.mesh
    rules = rules or _CTX.rules
    if mesh is None:
        return P(*([None] * len(shape)))
    sizes = _mesh_axis_sizes(mesh)
    used: set[str] = set()
    n = min(len(shape), len(dims))
    spec: list = [None] * n
    order = sorted(range(n), key=lambda i: dims[i] in LAST_PICK)
    for i in order:
        dim_size, logical = shape[i], dims[i]
        assigned = None
        if logical is not None:
            axes = tuple(a for a in rules.get(logical, ()) if a in sizes)
            axes = tuple(a for a in axes if a not in used)
            if axes:
                prod = 1
                for a in axes:
                    prod *= sizes[a]
                if prod > 1 and dim_size % prod == 0:
                    assigned = axes if len(axes) > 1 else axes[0]
                    used.update(axes)
                elif len(axes) == 1 and sizes[axes[0]] > 1 and dim_size % sizes[axes[0]] == 0:
                    assigned = axes[0]
                    used.add(axes[0])
                else:
                    # try each candidate axis individually (e.g. batch=("pod","data"))
                    for a in axes:
                        if sizes[a] > 1 and dim_size % sizes[a] == 0:
                            assigned = a
                            used.add(a)
                            break
        spec[i] = assigned
    return P(*spec)


def shard(x: jax.Array, dims: Sequence[Optional[str]]) -> jax.Array:
    """Constrain ``x``'s sharding by logical dims; identity when no mesh is active."""
    mesh = _CTX.mesh
    if mesh is None:
        return x
    spec = logical_pspec(x.shape, dims, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------- parameter specs

# leaf parameter name -> logical axes of its (unstacked) dims.
# Two-axis sharding: one "tensor" dim on the model axis, the d_model (or expert-hidden)
# dim on the fsdp axis — GSPMD all-gathers the fsdp axis per layer (ZeRO-3).
PARAM_LOGICAL_AXES: dict[str, tuple[Optional[str], ...]] = {
    "tok_embed": ("vocab", "fsdp"),
    "lm_head": ("fsdp", "vocab"),
    "enc_proj": ("fsdp", "d_model"),
    # attention / cross-attention
    "wq": ("fsdp", "heads", "head_dim"),
    "wk": ("fsdp", "kv_heads", "head_dim"),
    "wv": ("fsdp", "kv_heads", "head_dim"),
    "wo": ("heads", "head_dim", "fsdp"),
    "q_norm": ("head_dim",),
    "k_norm": ("head_dim",),
    "xgate": (),
    # dense MLP
    "w_gate": ("fsdp", "d_ff"),
    "w_in": ("fsdp", "d_ff"),
    "w_out": ("d_ff", "fsdp"),
    # MoE
    "router": ("d_model", "experts"),
    "we_gate": ("experts", "fsdp", None),
    "we_in": ("experts", "fsdp", None),
    "we_out": ("experts", None, "fsdp"),
    "ws_gate": ("fsdp", "d_ff"),
    "ws_in": ("fsdp", "d_ff"),
    "ws_out": ("d_ff", "fsdp"),
    "shared_gate": ("d_model",),
    "wd_gate": ("fsdp", "d_ff"),
    "wd_in": ("fsdp", "d_ff"),
    "wd_out": ("d_ff", "fsdp"),
    # Mamba
    "m_in": ("fsdp", "d_inner"),
    "m_z": ("fsdp", "d_inner"),
    "m_conv": (None, "d_inner"),
    "m_xproj": ("d_inner", None),
    "m_dtproj": (None, "d_inner"),
    "m_Alog": ("d_inner", "state"),
    "m_D": ("d_inner",),
    "m_out": ("d_inner", "fsdp"),
    # mLSTM
    "l_up": ("fsdp", "d_inner"),
    "l_z": ("fsdp", "d_inner"),
    "l_q": ("d_inner", "heads", "head_dim"),
    "l_k": ("d_inner", "heads", "head_dim"),
    "l_v": ("d_inner", "heads", "head_dim"),
    "l_ig": ("d_inner", "heads"),
    "l_fg": ("d_inner", "heads"),
    "l_og": ("d_inner", "d_inner"),
    "l_down": ("d_inner", "fsdp"),
    "l_skip": ("d_inner",),
    # sLSTM
    "s_w": ("fsdp", None, "heads", "head_dim"),
    "s_r": (None, "heads", "head_dim", None),
    "s_b": (None, "heads", "head_dim"),
    "s_out": ("fsdp", "d_model"),
    # norms
    "scale": ("d_model",),
    "bias": ("d_model",),
}


def dispatch_groups(n_tokens: int) -> int:
    """MoE dispatch-group count: one group per data shard so expert capacity is
    per-shard (keeps the dispatch buffer O(local_tokens)).  1 when un-meshed."""
    mesh = _CTX.mesh
    if mesh is None:
        return 1
    sizes = _mesh_axis_sizes(mesh)
    g = 1
    for a in _CTX.rules.get("batch", ()):
        g *= sizes.get(a, 1)
    while g > 1 and n_tokens % g:
        g //= 2
    return max(g, 1)


def _spec_for_leaf(name: str, ndim: int, mesh: Mesh, shape: Sequence[int]) -> P:
    dims = PARAM_LOGICAL_AXES.get(name)
    if dims is None:
        return P(*([None] * ndim))
    dims = tuple(dims)
    if len(dims) < ndim:                       # scan-stacked: leading period dim(s)
        dims = (None,) * (ndim - len(dims)) + dims
    elif len(dims) > ndim:
        dims = dims[-ndim:]
    return logical_pspec(shape, dims, mesh)


def param_pspecs(params, mesh: Optional[Mesh] = None):
    """PartitionSpec pytree for a params pytree (leaf-name lookup)."""
    mesh = mesh or _CTX.mesh

    def walk(path, leaf):
        name = str(path[-1].key) if hasattr(path[-1], "key") else str(path[-1])
        if mesh is None:
            return P(*([None] * leaf.ndim))
        return _spec_for_leaf(name, leaf.ndim, mesh, leaf.shape)

    return jax.tree_util.tree_map_with_path(walk, params)


def param_shardings(params, mesh: Optional[Mesh] = None):
    mesh = mesh or _CTX.mesh
    specs = param_pspecs(params, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------- decode-cache specs

# leaf cache name -> logical axes (right-aligned against the leaf's ndim; extra leading
# dims — period stacking — get None).  Collisions across families (mamba "h" vs sLSTM
# "h") are benign: the divisibility check replicates whichever dim doesn't divide.
CACHE_LOGICAL_AXES: dict[str, tuple[Optional[str], ...]] = {
    "pos": ("batch",),
    "page_table": ("batch", None),
    "k": ("batch", "kv_seq", "kv_heads", None),
    "v": ("batch", "kv_seq", "kv_heads", None),
    "xk": ("batch", None, "kv_heads", None),
    "xv": ("batch", None, "kv_heads", None),
    "h": ("batch", "d_inner", None),
    "conv": ("batch", None, "d_inner"),
    "C": ("batch", None, None, None),
    "n": ("batch", "d_inner", None),
    "c": ("batch", "d_inner", None),
    "m": ("batch", "d_inner"),
}


def cache_pspecs(cache, mesh: Optional[Mesh] = None):
    mesh = mesh or _CTX.mesh

    def walk(path, leaf):
        if mesh is None:
            return P(*([None] * leaf.ndim))
        name = str(path[-1].key) if hasattr(path[-1], "key") else str(path[-1])
        dims = CACHE_LOGICAL_AXES.get(name)
        if dims is None:
            return P(*([None] * leaf.ndim))
        dims = tuple(dims)
        if len(dims) < leaf.ndim:
            dims = (None,) * (leaf.ndim - len(dims)) + dims
        elif len(dims) > leaf.ndim:
            dims = dims[-leaf.ndim:]
        return logical_pspec(leaf.shape, dims, mesh)

    return jax.tree_util.tree_map_with_path(walk, cache)


def cache_shardings(cache, mesh: Optional[Mesh] = None):
    mesh = mesh or _CTX.mesh
    specs = cache_pspecs(cache, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))
