"""Flash-decode GQA attention Pallas kernel (TPU target, validated interpret=True).

The rollout hot spot Heddle's resource manager accelerates is decode-phase attention
against a long KV cache.  This kernel implements the TPU-native adaptation: the KV cache
streams HBM -> VMEM in tiles of ``block_c`` positions (or one page) x all KV heads, the
(KV, G, hd) query tile stays resident in VMEM, and per-head online-softmax accumulators
live in VMEM scratch across the sequential kv-block grid axis.  GQA is handled by grouping
the G query heads of one KV head into a single (G, hd) x (hd, tile) MXU matmul — no KV
replication.

Grid: (B, num_kv_blocks); the last axis is sequential on TPU, enabling accumulation.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
DEFAULT_BLOCK_C = 512


def _flash_decode_tile(step, n_steps, tile_len: int, vlen, q_ref, k_ref, v_ref,
                       o_ref, m_ref, l_ref, acc_ref):
    """Fold one KV tile into every head's online-softmax state.

    ``k_ref``/``v_ref`` hold ``(1, tile_len, KV, hd)``: all KV heads of
    ``tile_len`` positions.  The TPU block must span the array's full (KV, hd)
    minor dims — a one-head ``(.., 1, hd)`` block is rejected by the Mosaic
    tiling rule — so the block carries every head and a static loop walks them.
    Each head's (G, hd) query group meets its (tile_len, hd) keys in one matmul.
    """

    @pl.when(step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    KV, hd = q_ref.shape[1], q_ref.shape[3]
    scale = 1.0 / math.sqrt(hd)
    for h in range(KV):
        q = q_ref[0, h].astype(F32)                  # (G, hd)
        k = k_ref[0, :, h, :].astype(F32)            # (tile_len, hd)
        v = v_ref[0, :, h, :].astype(F32)            # (tile_len, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32) * scale  # (G, tile_len)
        pos = step * tile_len + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < vlen, s, -1e30)
        m_prev = m_ref[h]                             # (G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                        # (G, tile_len)
        corr = jnp.exp(m_prev - m_new)                # (G, 1)
        l_ref[h] = l_ref[h] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=F32)
        m_ref[h] = m_new

    @pl.when(step == n_steps - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _decode_attn_kernel(vlen_ref, q_ref, k_ref, v_ref, o_ref,
                        m_ref, l_ref, acc_ref, *, block_c: int, num_blocks: int):
    _flash_decode_tile(pl.program_id(1), num_blocks, block_c,
                       vlen_ref[pl.program_id(0)], q_ref, k_ref, v_ref, o_ref,
                       m_ref, l_ref, acc_ref)


def _paged_decode_attn_kernel(pt_ref, vlen_ref, q_ref, k_ref, v_ref, o_ref,
                              m_ref, l_ref, acc_ref, *, page_size: int,
                              num_pages: int):
    """Ragged paged variant: the grid's last axis walks the lane's page table.

    The physical block streamed into ``k_ref``/``v_ref`` at step ``i`` is chosen
    by the BlockSpec index_map from the scalar-prefetched page table
    (``pt_ref[b, i]``), so the gather over non-contiguous KV blocks happens in
    the HBM->VMEM pipeline — no (B, capacity, KV, hd) contiguous view is ever
    materialized.  Pages past the lane's resident length resolve to block 0
    (scratch); their scores are masked to -1e30 like any tail padding.
    """
    _flash_decode_tile(pl.program_id(1), num_pages, page_size,
                       vlen_ref[pl.program_id(0)], q_ref, k_ref, v_ref, o_ref,
                       m_ref, l_ref, acc_ref)


def _scratch(KV: int, G: int, hd: int) -> list:
    return [pltpu.VMEM((KV, G, 1), F32),              # running max m
            pltpu.VMEM((KV, G, 1), F32),              # running denom l
            pltpu.VMEM((KV, G, hd), F32)]             # output accumulator


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(q: jax.Array, k_pool: jax.Array,
                                  v_pool: jax.Array, page_table: jax.Array,
                                  valid_len: jax.Array, *,
                                  interpret: bool = True) -> jax.Array:
    """Paged flash-decode: gather KV blocks through a page table.

    q: (B, KV, G, hd); k_pool, v_pool: (NB, page_size, KV, hd) physical block
    pools; page_table: (B, num_pages) int32 (block 0 = scratch for unmapped
    entries); valid_len: scalar or (B,) int32 resident token counts.
    Returns (B, KV, G, hd).
    """
    B, KV, G, hd = q.shape
    page_size = k_pool.shape[1]
    num_pages = page_table.shape[1]
    vlen = jnp.broadcast_to(jnp.asarray(valid_len, jnp.int32), (B,))
    pt = page_table.astype(jnp.int32)

    kernel = functools.partial(_paged_decode_attn_kernel, page_size=page_size,
                               num_pages=num_pages)
    q_spec = pl.BlockSpec((1, KV, G, hd), lambda b, i, pt, vl: (b, 0, 0, 0))
    kv_spec = pl.BlockSpec((1, page_size, KV, hd),
                           lambda b, i, pt, vl: (pt[b, i], 0, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, num_pages),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=_scratch(KV, G, hd),
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(pt, vlen, q, k_pool, v_pool)
    return out


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def decode_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                            valid_len: jax.Array, *, block_c: int = DEFAULT_BLOCK_C,
                            interpret: bool = True) -> jax.Array:
    """q: (B, KV, G, hd); k, v: (B, C, KV, hd); valid_len: scalar or (B,) int32."""
    B, KV, G, hd = q.shape
    C = k.shape[1]
    block_c = min(block_c, C)
    num_blocks = -(-C // block_c)
    pad = num_blocks * block_c - C
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vlen = jnp.broadcast_to(jnp.asarray(valid_len, jnp.int32), (B,))

    kernel = functools.partial(_decode_attn_kernel, block_c=block_c,
                               num_blocks=num_blocks)
    q_spec = pl.BlockSpec((1, KV, G, hd), lambda b, c, vl: (b, 0, 0, 0))
    kv_spec = pl.BlockSpec((1, block_c, KV, hd), lambda b, c, vl: (b, c, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, num_blocks),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=_scratch(KV, G, hd),
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(vlen, q, k, v)
    return out
