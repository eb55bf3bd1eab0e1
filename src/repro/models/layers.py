"""Layer primitives shared by all 10 assigned architectures.

Every primitive has a *full-sequence* form (train / prefill) and a *step* form (decode
with cached state).  Memory-sensitive paths are blocked:

  * full attention uses a flash-style nested-scan (online softmax over KV blocks) above a
    sequence threshold, so prefill_32k never materializes an S x S score matrix;
  * mLSTM uses the chunk-recurrent linear-attention form (inter-chunk state carry);
  * Mamba uses an associative scan over the diagonal SSM recurrence;
  * MoE uses capacity-based sort dispatch (compute scales with top_k, not n_experts).

Activation sharding constraints use logical axis names via ``repro.distributed.shard``.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.distributed.sharding import shard
from repro.models.config import ModelConfig

F32 = jnp.float32


# ----------------------------------------------------------------- norms / rope

def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(F32)), axis=-1, keepdims=True)
    return (x.astype(F32) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(F32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale + bias


def block_norm(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    if cfg.norm == "layernorm":
        return layernorm(x, p["scale"], p["bias"], cfg.norm_eps)
    return rmsnorm(x, p["scale"], cfg.norm_eps)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding.  x: (..., S, H, hd); positions: (S,) or (B, S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[..., None] * freqs          # (..., S, half)
    if ang.ndim == 2:                                        # (S, half) -> broadcast batch
        ang = ang[None]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]  # (B,S,1,half)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def activate(h: jax.Array, g: Optional[jax.Array], kind: str) -> jax.Array:
    if kind == "swiglu":
        return jax.nn.silu(g) * h
    if kind == "relu2":
        return jnp.square(jax.nn.relu(h))
    if kind == "gelu":
        return jax.nn.gelu(h)
    raise ValueError(f"unknown activation {kind!r}")


# ----------------------------------------------------------------- full attention

FLASH_THRESHOLD = 2048
_QBLK, _KBLK = 512, 1024


def _plain_attention(q, k, v, mask, scale):
    # q: (B,S,KV,G,hd)  k,v: (B,T,KV,hd)  mask: broadcastable to (B,KV,G,S,T) or None
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(F32) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v)


def _flash_attention(q, k, v, q_pos, kv_pos, scale, causal, window):
    """Flash attention (models/flash.py): scan-blocked online softmax with a
    memory-correct custom VJP (backward recomputes block scores)."""
    from repro.models.flash import flash_attention
    qt = q.transpose(0, 2, 3, 1, 4)                       # (B,S,KV,G,hd)->(B,KV,G,S,hd)
    out = flash_attention(qt, k, v, q_pos, kv_pos, scale, bool(causal), int(window),
                          _QBLK, _KBLK)
    return out.transpose(0, 3, 1, 2, 4)                   # -> (B,S,KV,G,hd)


def attention_full(
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    positions: jax.Array,
    *,
    causal: bool = True,
    use_rope: bool = True,
    kv_input: Optional[jax.Array] = None,
    window: int = 0,
) -> jax.Array:
    """Full-sequence (GQA, optionally cross) attention."""
    B, S, _ = x.shape
    KV, hd = cfg.n_kv_heads, cfg.hd
    H = cfg.n_heads
    G = H // KV
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    src = x if kv_input is None else kv_input
    k = jnp.einsum("btd,dnk->btnk", src, p["wk"])
    v = jnp.einsum("btd,dnk->btnk", src, p["wv"])
    q = shard(q, ("batch", None, "heads", None))
    k = shard(k, ("batch", None, "kv_heads", None))
    v = shard(v, ("batch", None, "kv_heads", None))
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    is_cross = kv_input is not None
    if use_rope and not is_cross:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    T = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, S, KV, G, hd)
    if max(S, T) >= FLASH_THRESHOLD and not is_cross:
        kv_pos = positions if positions.ndim == 1 else positions[0]
        out = _flash_attention(qg, k, v, positions, kv_pos, scale,
                               causal, window)
    else:
        mask = None
        if causal and not is_cross:
            pos = positions if positions.ndim == 1 else positions[0]
            m = pos[:, None] >= pos[None, :]
            if window:
                m &= pos[:, None] - pos[None, :] < window
            mask = m[None, None, None]
        out = _plain_attention(qg, k, v, mask, scale)
    out = out.reshape(B, S, H, hd)
    out = shard(out, ("batch", None, "heads", None))
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def attention_decode(
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    cache_k: jax.Array,
    cache_v: jax.Array,
    pos: jax.Array,
    *,
    window: int = 0,
    use_rope: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode: write new KV at ``pos`` (ring-indexed if windowed), attend.

    cache_k/v: (B, C, KV, hd); pos: (B,) int32 — per-slot positions (continuous
    batching: every sequence in the batch may be at a different decode offset).
    Returns (out (B,1,d_model), new_cache_k, new_cache_v).
    """
    from repro.kernels import ops as kops
    B = x.shape[0]
    KV, hd, H = cfg.n_kv_heads, cfg.hd, cfg.n_heads
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dnk->bsnk", x, p["wk"])
    v = jnp.einsum("bsd,dnk->bsnk", x, p["wv"])
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    pos = jnp.broadcast_to(pos, (B,))
    if use_rope:
        q = rope(q, pos[:, None], cfg.rope_theta)
        k = rope(k, pos[:, None], cfg.rope_theta)
    C = cache_k.shape[1]
    slot = (pos % C) if window else jnp.minimum(pos, C - 1)
    bidx = jnp.arange(B)
    cache_k = cache_k.at[bidx, slot].set(k[:, 0].astype(cache_k.dtype))
    cache_v = cache_v.at[bidx, slot].set(v[:, 0].astype(cache_v.dtype))
    cache_k = shard(cache_k, ("batch", "kv_seq", "kv_heads", None))
    cache_v = shard(cache_v, ("batch", "kv_seq", "kv_heads", None))
    valid_len = jnp.minimum(pos + 1, C)
    out = kops.decode_attention(q.reshape(B, KV, H // KV, hd), cache_k, cache_v,
                                valid_len, force_pallas=cfg.use_pallas_decode)
    out = out.reshape(B, 1, H, hd)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), cache_k, cache_v


def attention_decode_paged(
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    k_pool: jax.Array,
    v_pool: jax.Array,
    page_table: jax.Array,
    pos: jax.Array,
    *,
    use_rope: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-token paged decode: scatter new KV into the lane's current block, attend
    through the page table.

    k_pool/v_pool: (NB, page_size, KV, hd) physical blocks shared by every lane;
    page_table: (B, num_pages) int32 (block 0 = scratch for unmapped entries);
    pos: (B,) int32.  A lane's write lands at block ``page_table[b, pos//ps]``,
    offset ``pos % ps`` — free/masked lanes whose rows are unmapped (or whose pos
    sits past capacity) write into scratch, which is the paged form of the dense
    pool's self-healing invariant.  Returns (out (B,1,d_model), k_pool', v_pool').
    """
    from repro.kernels import ops as kops
    B = x.shape[0]
    KV, hd, H = cfg.n_kv_heads, cfg.hd, cfg.n_heads
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dnk->bsnk", x, p["wk"])
    v = jnp.einsum("bsd,dnk->bsnk", x, p["wv"])
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    pos = jnp.broadcast_to(pos, (B,))
    if use_rope:
        q = rope(q, pos[:, None], cfg.rope_theta)
        k = rope(k, pos[:, None], cfg.rope_theta)
    ps = k_pool.shape[1]
    num_pages = page_table.shape[1]
    cap = num_pages * ps
    bidx = jnp.arange(B)
    page = jnp.clip(pos // ps, 0, num_pages - 1)
    blk = jnp.where(pos < cap, page_table[bidx, page], 0)   # overflow -> scratch
    off = pos % ps
    k_pool = k_pool.at[blk, off].set(k[:, 0].astype(k_pool.dtype))
    v_pool = v_pool.at[blk, off].set(v[:, 0].astype(v_pool.dtype))
    k_pool = shard(k_pool, (None, "kv_seq", "kv_heads", None))
    v_pool = shard(v_pool, (None, "kv_seq", "kv_heads", None))
    valid_len = jnp.minimum(pos + 1, cap)
    out = kops.paged_decode_attention(q.reshape(B, KV, H // KV, hd), k_pool,
                                      v_pool, page_table, valid_len,
                                      force_pallas=cfg.use_pallas_decode)
    out = out.reshape(B, 1, H, hd)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), k_pool, v_pool


def attention_prefill_chunk_paged(
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    k_pool: jax.Array,
    v_pool: jax.Array,
    layer: jax.Array,
    pt_row: jax.Array,
    off: jax.Array,
    length: jax.Array,
    *,
    use_rope: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fixed-shape chunk prefill straight into a lane's pages.

    x: (1, C, d) normed hidden states (rows >= ``length`` are padding);
    k_pool/v_pool: every layer's blocks, (P, num_blocks, page_size, KV, hd), of
    which this call touches layer ``layer`` only; pt_row: (num_pages,) int32,
    mapped far enough to cover ``off + length`` tokens.  The chunk's K/V rows
    scatter to their absolute (layer, block, offset) slots — padding and
    out-of-capacity rows route to scratch block 0 — then each query ``i``
    attends to positions ``t <= off + i`` through the lane's pages, gathered
    straight from the stack (never a slice of the layer's whole pool).  The
    suffix of a prefix-shared admission runs through this path attending to the
    *shared* pages in place: zero prefix KV copies.  Returns
    (out (1, C, d_model), k_pool', v_pool').
    """
    B, Cn, _ = x.shape
    KV, hd, H = cfg.n_kv_heads, cfg.hd, cfg.n_heads
    G = H // KV
    with jax.named_scope("qkv"):
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        k = jnp.einsum("bsd,dnk->bsnk", x, p["wk"])
        v = jnp.einsum("bsd,dnk->bsnk", x, p["wv"])
        if cfg.qk_norm:
            q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
            k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
        positions = (off + jnp.arange(Cn))[None]              # (1, C) absolute
        if use_rope:
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
    ps = k_pool.shape[2]
    num_pages = pt_row.shape[0]
    cap = num_pages * ps
    rows = off + jnp.arange(Cn)
    with jax.named_scope("kv_write"):
        valid = (jnp.arange(Cn) < length) & (rows < cap)
        page = jnp.clip(rows // ps, 0, num_pages - 1)
        blk = jnp.where(valid, pt_row[page], 0)               # padding -> scratch
        slot = rows % ps
        k_pool = k_pool.at[layer, blk, slot].set(k[0].astype(k_pool.dtype))
        v_pool = v_pool.at[layer, blk, slot].set(v[0].astype(v_pool.dtype))
    with jax.named_scope("attn"):
        kg = k_pool[layer, pt_row][None].reshape(1, cap, KV, hd)
        vg = v_pool[layer, pt_row][None].reshape(1, cap, KV, hd)
        mask = jnp.arange(cap)[None, :] <= rows[:, None]      # (C, cap)
        qg = q.reshape(B, Cn, KV, G, hd)
        out = _plain_attention(qg, kg, vg, mask[None, None, None],
                               1.0 / math.sqrt(hd))
        out = out.reshape(B, Cn, H, hd)
        out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return out, k_pool, v_pool


def attention_prefill_chunk(
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    cache_k: jax.Array,
    cache_v: jax.Array,
    off: jax.Array,
    length: jax.Array,
    *,
    use_rope: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fixed-shape chunk prefill for one lane: process ``C`` tokens at offset ``off``.

    x: (1, C, d) normed hidden states (rows >= ``length`` are padding); cache_k/v:
    (1, cap, KV, hd) with positions ``[0, off)`` resident.  Writes the chunk's K/V into
    the lane slice ``[off, off+C)`` (padding rows keep the old cache contents, so the
    masked-decode self-healing invariant carries over), then attends each query ``i``
    against cache slots ``t <= off + i`` — the resident prefix plus the chunk's own
    causal keys, which were just written.  ``off``/``length`` are traced scalars, so one
    compiled kernel serves every (offset, tail-length) — prefill at offset 0 and tool
    absorption at offset > 0 are the same code path.  Non-windowed linear caches only
    (ring writes would let later chunk rows overwrite slots earlier queries need).
    Returns (out (1, C, d_model), new_cache_k, new_cache_v).
    """
    B, Cn, _ = x.shape
    KV, hd, H = cfg.n_kv_heads, cfg.hd, cfg.n_heads
    G = H // KV
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dnk->bsnk", x, p["wk"])
    v = jnp.einsum("bsd,dnk->bsnk", x, p["wv"])
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    positions = (off + jnp.arange(Cn))[None]                  # (1, C) absolute
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    # write-then-attend: scatter valid rows to their ABSOLUTE slots.  A
    # dynamic_update_slice would clamp its start when off + C > cap and smear the
    # tail chunk over resident positions; per-row scatter keeps every key at
    # off + j even when the fixed-shape window hangs past the capacity edge
    # (out-of-capacity rows blend the old contents back — true overflow, which the
    # decode path also cannot represent).
    cap = cache_k.shape[1]
    rows = off + jnp.arange(Cn)
    valid = ((jnp.arange(Cn) < length) & (rows < cap))[None, :, None, None]
    slots = jnp.clip(rows, 0, cap - 1)
    cache_k = cache_k.at[:, slots].set(
        jnp.where(valid, k.astype(cache_k.dtype), cache_k[:, slots]))
    cache_v = cache_v.at[:, slots].set(
        jnp.where(valid, v.astype(cache_v.dtype), cache_v[:, slots]))
    mask = jnp.arange(cap)[None, :] <= (off + jnp.arange(Cn))[:, None]   # (C, cap)
    qg = q.reshape(B, Cn, KV, G, hd)
    out = _plain_attention(qg, cache_k, cache_v, mask[None, None, None],
                           1.0 / math.sqrt(hd))
    out = out.reshape(B, Cn, H, hd)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), cache_k, cache_v


def cross_attention_decode(p, x, cfg, cross_k, cross_v):
    """Decode-time cross-attention against fixed encoder/image KV."""
    from repro.kernels import ops as kops
    B = x.shape[0]
    KV, hd, H = cfg.n_kv_heads, cfg.hd, cfg.n_heads
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    T = cross_k.shape[1]
    out = kops.decode_attention(q.reshape(B, KV, H // KV, hd), cross_k, cross_v,
                                jnp.asarray(T, jnp.int32),
                                force_pallas=cfg.use_pallas_decode)
    out = out.reshape(B, 1, H, hd)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


# ----------------------------------------------------------------- MLPs / MoE

def mlp(p: dict, x: jax.Array, activation: str) -> jax.Array:
    h = x @ p["w_in"]
    g = x @ p["w_gate"] if activation == "swiglu" else None
    h = shard(activate(h, g, activation), ("batch", None, "d_ff"))
    return h @ p["w_out"]


def moe(p: dict, x: jax.Array, cfg: ModelConfig) -> tuple[jax.Array, jax.Array]:
    """Capacity-based top-k MoE with grouped sort dispatch.

    Tokens are split into one dispatch group per data shard (``dispatch_groups``), each
    with its own capacity — so the dispatch buffer is O(local_tokens) per device and
    GSPMD lowers the buffer movement to an all-to-all when experts shard over the model
    axis.  Compute scales with T * top_k * capacity_factor, not n_experts (overflow
    tokens drop, standard TPU practice).  Returns (output, aux_loss).
    """
    from repro.distributed.sharding import dispatch_groups
    B, S, D = x.shape
    T = B * S
    E, K = cfg.n_experts, cfg.top_k
    G = dispatch_groups(T)
    Tg = T // G
    cap = max(1, int(math.ceil(Tg * K / E * cfg.capacity_factor)))
    xf = x.reshape(T, D)

    logits = (xf @ p["router"]).astype(F32)                 # (T, E)
    gates = jax.nn.softmax(logits, axis=-1)
    top_g, top_e = lax.top_k(gates, K)                      # (T, K)
    top_g = top_g / jnp.maximum(top_g.sum(-1, keepdims=True), 1e-9)

    def dispatch_one(xg, eg, gg):
        """One group: xg (Tg, D), eg (Tg, K) expert ids, gg (Tg, K) gate weights."""
        eid = eg.reshape(-1)                                # (Tg*K,)
        tid = jnp.broadcast_to(jnp.arange(Tg)[:, None], (Tg, K)).reshape(-1)
        gat = gg.reshape(-1)
        order = jnp.argsort(eid)
        eid_s, tid_s, gat_s = eid[order], tid[order], gat[order]
        counts = jax.ops.segment_sum(jnp.ones_like(eid_s, F32), eid_s, num_segments=E)
        starts = jnp.cumsum(counts) - counts
        rank = jnp.arange(Tg * K) - starts[eid_s].astype(jnp.int32)
        keep = (rank < cap).astype(xg.dtype)
        rank_c = jnp.clip(rank, 0, cap - 1)
        buf = jnp.zeros((E, cap, D), xg.dtype)
        buf = buf.at[eid_s, rank_c].add(xg[tid_s] * keep[:, None])
        return buf, (eid_s, tid_s, gat_s, keep, rank_c)

    xg = x.reshape(G, Tg, D)
    eg = top_e.reshape(G, Tg, K)
    gg = top_g.reshape(G, Tg, K)
    buf, meta = jax.vmap(dispatch_one)(xg, eg, gg)          # buf: (G, E, cap, D)
    buf = shard(buf, ("dispatch", "experts", None, None))

    h = jnp.einsum("gecd,edf->gecf", buf, p["we_in"])
    g = jnp.einsum("gecd,edf->gecf", buf, p["we_gate"]) if cfg.activation == "swiglu" else None
    h = activate(h, g, cfg.activation if cfg.activation != "gelu" else "gelu")
    out_buf = jnp.einsum("gecf,efd->gecd", h, p["we_out"])
    out_buf = shard(out_buf, ("dispatch", "experts", None, None))

    def combine_one(ob, meta_g):
        eid_s, tid_s, gat_s, keep, rank_c = meta_g
        yflat = ob[eid_s, rank_c] * (gat_s.astype(ob.dtype) * keep)[:, None]
        return jax.ops.segment_sum(yflat, tid_s, num_segments=Tg)

    y = jax.vmap(combine_one)(out_buf, meta).reshape(B, S, D)

    # load-balance aux loss (Switch-style): E * sum_e f_e * p_e
    assigned = jax.nn.one_hot(top_e.reshape(-1), E, dtype=F32).sum(0)
    frac_tokens = assigned / jnp.maximum(assigned.sum(), 1.0)
    frac_prob = gates.mean(0)
    aux = E * jnp.sum(frac_tokens * frac_prob)

    if cfg.shared_d_ff:                                     # qwen2-moe shared experts
        sh = xf @ p["ws_in"]
        sg = xf @ p["ws_gate"]
        s_out = (jax.nn.silu(sg) * sh) @ p["ws_out"]
        gate = jax.nn.sigmoid((xf @ p["shared_gate"]).astype(F32))[:, None]
        y = y + (gate.astype(xf.dtype) * s_out).reshape(B, S, D)
    if cfg.dense_residual_ff:                               # arctic dense residual
        y = y + mlp({"w_in": p["wd_in"], "w_gate": p["wd_gate"], "w_out": p["wd_out"]},
                    x, "swiglu")
    return y, aux


# ----------------------------------------------------------------- Mamba (SSM)

def _mamba_inner(p, x_conv, cfg):
    """Shared math after the causal conv: returns (a, b, C) scan ingredients."""
    dbc = x_conv @ p["m_xproj"]                              # (..., R + 2N)
    R = p["m_dtproj"].shape[0]
    N = cfg.ssm_state_dim
    dt_r, Bm, Cm = dbc[..., :R], dbc[..., R:R + N], dbc[..., R + N:]
    dt = jax.nn.softplus(dt_r @ p["m_dtproj"]).astype(F32)   # (..., di)
    A = -jnp.exp(p["m_Alog"].astype(F32))                    # (di, N)
    a = jnp.exp(dt[..., None] * A)                           # (..., di, N)
    b = (dt * x_conv.astype(F32))[..., None] * Bm.astype(F32)[..., None, :]
    return a, b, Cm


MAMBA_CHUNK = 512


def _mamba_scan_chunked(a: jax.Array, b: jax.Array, h0: jax.Array,
                        Cm: Optional[jax.Array] = None
                        ) -> tuple[jax.Array, jax.Array]:
    """Linear recurrence h_t = a_t h_{t-1} + b_t via chunked associative scan.

    A full-sequence associative scan materializes O(log S) copies of the (B,S,di,N)
    state tensor (observed: ~100 GiB of f32 scan buffers on jamba train_4k).  Chunking
    runs an outer sequential lax.scan over S/CHUNK chunks (checkpointed, so backward
    recomputes instead of storing inner intermediates) with the associative scan inside
    — peak scan memory drops by ~S/CHUNK while keeping intra-chunk parallelism.

    With ``Cm`` (B,S,N): the output contraction y_t = <h_t, C_t> is FUSED into each
    chunk, so the full (B,S,di,N) state sequence is never written to HBM — the scan
    emits (B,S,di) instead (EXPERIMENTS.md §Perf iteration 2: N-fold output shrink).
    Returns (y_or_h, h_last (B,di,N)).
    """
    B, S, di, N = a.shape
    cs = min(MAMBA_CHUNK, S)
    nc = -(-S // cs)
    pad = nc * cs - S
    if pad:  # pad with identity elements: a=1, b=0
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)), constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if Cm is not None:
            Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    ac = a.reshape(B, nc, cs, di, N).transpose(1, 0, 2, 3, 4)
    bc = b.reshape(B, nc, cs, di, N).transpose(1, 0, 2, 3, 4)
    cc = (Cm.astype(F32).reshape(B, nc, cs, N).transpose(1, 0, 2, 3)
          if Cm is not None else None)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    @jax.checkpoint
    def chunk(h0, args):
        a_i, b_i, c_i = args                             # (B,cs,di,N), c_i may be None
        aprod, bacc = lax.associative_scan(combine, (a_i, b_i), axis=1)
        h = aprod * h0[:, None] + bacc                   # seed with the carry state
        out = h if c_i is None else jnp.einsum("bsdn,bsn->bsd", h, c_i)
        return h[:, -1], out

    h_last, outs = lax.scan(chunk, h0, (ac, bc, cc))
    if Cm is None:
        out = outs.transpose(1, 0, 2, 3, 4).reshape(B, nc * cs, di, N)[:, :S]
    else:
        out = outs.transpose(1, 0, 2, 3).reshape(B, nc * cs, di)[:, :S]
    return out, h_last


def _mamba_scan_fused(p, xc, cfg) -> jax.Array:
    """Fully fused chunked SSM scan: discretization (a = exp(dt A), b = dt x B),
    recurrence AND the C-contraction all happen inside each chunk, so the only
    HBM-resident sequence tensors are the (B,S,di) projections — the (B,S,di,N)
    discretized pair is never materialized (EXPERIMENTS.md §Perf iteration 3)."""
    B, S, di = xc.shape
    N = cfg.ssm_state_dim
    R = p["m_dtproj"].shape[0]
    dbc = xc @ p["m_xproj"]                                  # (B,S,R+2N)
    dt_r, Bm, Cm = dbc[..., :R], dbc[..., R:R + N], dbc[..., R + N:]
    dt = jax.nn.softplus(dt_r @ p["m_dtproj"]).astype(F32)   # (B,S,di)
    A = -jnp.exp(p["m_Alog"].astype(F32))                    # (di,N)

    cs = min(MAMBA_CHUNK, S)
    nc = -(-S // cs)
    pad = nc * cs - S
    if pad:
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))         # dt=0 -> a=1, b=0
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
        xc_p = jnp.pad(xc, ((0, 0), (0, pad), (0, 0)))
    else:
        xc_p = xc

    def to_chunks(t):
        return t.reshape(B, nc, cs, *t.shape[2:]).transpose(1, 0, 2, *range(3, t.ndim + 1))

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    @jax.checkpoint
    def chunk(h0, args):
        dt_i, B_i, C_i, x_i = args                           # (B,cs,di)/(B,cs,N)
        a_i = jnp.exp(dt_i[..., None] * A)                   # (B,cs,di,N) — chunk only
        b_i = (dt_i * x_i.astype(F32))[..., None] * B_i.astype(F32)[..., None, :]
        aprod, bacc = lax.associative_scan(combine, (a_i, b_i), axis=1)
        h = aprod * h0[:, None] + bacc
        y_i = jnp.einsum("bsdn,bsn->bsd", h, C_i.astype(F32))
        return h[:, -1], y_i

    h0 = jnp.zeros((B, di, N), F32)
    _, ys = lax.scan(chunk, h0, (to_chunks(dt), to_chunks(Bm), to_chunks(Cm),
                                 to_chunks(xc_p)))
    return ys.transpose(1, 0, 2, 3).reshape(B, nc * cs, di)[:, :S]


def mamba_full(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    B, S, D = x.shape
    xi = x @ p["m_in"]                                       # (B,S,di)
    z = x @ p["m_z"]
    xi = shard(xi, ("batch", None, "d_inner"))
    W = cfg.ssm_conv_width
    xp = jnp.pad(xi, ((0, 0), (W - 1, 0), (0, 0)))
    conv = sum(xp[:, i:i + S] * p["m_conv"][i] for i in range(W))
    xc = jax.nn.silu(conv)
    y = _mamba_scan_fused(p, xc, cfg)                        # fused discretize+scan+C
    y = (y + p["m_D"].astype(F32) * xc.astype(F32)).astype(x.dtype)
    y = y * jax.nn.silu(z)
    return y @ p["m_out"]


def mamba_step(p: dict, x: jax.Array, cfg: ModelConfig, state: dict
               ) -> tuple[jax.Array, dict]:
    """One-token decode.  state = {"h": (B,di,N) f32, "conv": (B,W-1,di)}."""
    B = x.shape[0]
    xi = (x[:, 0] @ p["m_in"])                               # (B,di)
    z = x[:, 0] @ p["m_z"]
    W = cfg.ssm_conv_width
    hist = jnp.concatenate([state["conv"], xi[:, None]], axis=1)   # (B,W,di)
    conv = jnp.einsum("bwd,wd->bd", hist, p["m_conv"])
    xc = jax.nn.silu(conv)
    a, b, Cm = _mamba_inner(p, xc, cfg)                      # (B,di,N)
    h = a * state["h"] + b
    y = jnp.einsum("bdn,bn->bd", h, Cm.astype(F32))
    y = (y + p["m_D"].astype(F32) * xc.astype(F32)).astype(x.dtype)
    y = y * jax.nn.silu(z)
    out = (y @ p["m_out"])[:, None]
    return out, {"h": h, "conv": hist[:, 1:]}


# ----------------------------------------------------------------- xLSTM

def _mlstm_qkv(p, xi):
    q = jnp.einsum("...d,dhk->...hk", xi, p["l_q"])
    k = jnp.einsum("...d,dhk->...hk", xi, p["l_k"])
    v = jnp.einsum("...d,dhk->...hk", xi, p["l_v"])
    i_pre = jnp.einsum("...d,dh->...h", xi, p["l_ig"]).astype(F32)
    f_pre = jnp.einsum("...d,dh->...h", xi, p["l_fg"]).astype(F32)
    return q, k, v, i_pre, f_pre


MLSTM_CHUNK = 256


def mlstm_full(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Chunk-recurrent mLSTM (matrix-memory, exponential gating, stabilized).

    Within a chunk: parallel attention-like computation with decay matrix.
    Across chunks: (C, n, m) state carry — the linear-attention chunked form.
    """
    B, S, D = x.shape
    xi = x @ p["l_up"]
    z = jax.nn.silu(x @ p["l_z"])
    xi = shard(xi, ("batch", None, "d_inner"))
    di = xi.shape[-1]
    H = cfg.n_heads
    hd = di // H
    q, k, v, i_pre, f_pre = _mlstm_qkv(p, xi)                # (B,S,H,hd), (B,S,H)
    q = q.transpose(0, 2, 1, 3)                              # (B,H,S,hd)
    k = k.transpose(0, 2, 1, 3) / math.sqrt(hd)
    v = v.transpose(0, 2, 1, 3)
    i_pre = i_pre.transpose(0, 2, 1)                         # (B,H,S)
    logf = jax.nn.log_sigmoid(f_pre.transpose(0, 2, 1))      # (B,H,S)

    cs = min(MLSTM_CHUNK, S)
    nc = -(-S // cs)
    pad = nc * cs - S
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0))) for t in (q, k, v))
        i_pre = jnp.pad(i_pre, ((0, 0), (0, 0), (0, pad)), constant_values=-1e30)
        logf = jnp.pad(logf, ((0, 0), (0, 0), (0, pad)))
    qc = q.reshape(B, H, nc, cs, hd).transpose(2, 0, 1, 3, 4)   # (nc,B,H,cs,hd)
    kc = k.reshape(B, H, nc, cs, hd).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(B, H, nc, cs, hd).transpose(2, 0, 1, 3, 4)
    ic = i_pre.reshape(B, H, nc, cs).transpose(2, 0, 1, 3)      # (nc,B,H,cs)
    fc = logf.reshape(B, H, nc, cs).transpose(2, 0, 1, 3)

    def chunk(carry, args):
        Cst, nst, mst = carry                                # (B,H,hd,hd),(B,H,hd),(B,H)
        qi, ki, vi, ii, fi = args                            # ii: log-input-gate pre, fi: log f
        kif = ki.astype(F32)
        vif = vi.astype(F32)
        qif = qi.astype(F32)
        fcum = jnp.cumsum(fi, axis=-1)                       # (B,H,cs): sum_{u<=t} log f_u
        ftot = fcum[..., -1]
        # --- outputs: per-position stabilizer m_out_t = fcum_t + max(mst, cummax(ii - fcum))
        runmax = lax.cummax(ii - fcum, axis=ii.ndim - 1)
        m_out = fcum + jnp.maximum(mst[..., None], runmax)   # (B,H,cs)
        dec_q = jnp.exp(mst[..., None] + fcum - m_out)       # inter-chunk decay per query
        inter = jnp.einsum("bhsd,bhde->bhse", qif, Cst) * dec_q[..., None]
        n_inter = jnp.einsum("bhsd,bhd->bhs", qif, nst) * dec_q
        # intra weights: D[t1,t2] = exp(ii_t2 + fcum_t1 - fcum_t2 - m_out_t1), t2 <= t1
        dmat = jnp.exp((ii - fcum)[..., None, :] + (fcum - m_out)[..., :, None])
        causal = jnp.tril(jnp.ones((cs, cs), bool))
        dmat = jnp.where(causal, dmat, 0.0)
        s = jnp.einsum("bhsd,bhtd->bhst", qif, kif)
        intra = jnp.einsum("bhst,bhtd->bhsd", s * dmat, vif)
        n_intra = jnp.sum(s * dmat, axis=-1)
        n_vec = n_inter + n_intra
        h = (inter + intra) / jnp.maximum(jnp.abs(n_vec), jnp.exp(-m_out))[..., None]
        # --- state update to chunk end: key t weight log w_t = ii_t + ftot - fcum_t
        wlog = ii + (ftot[..., None] - fcum)
        m_new = jnp.maximum(mst + ftot, jnp.max(wlog, axis=-1))
        wk = jnp.exp(wlog - m_new[..., None])
        decay = jnp.exp(mst + ftot - m_new)
        C_new = Cst * decay[..., None, None] + jnp.einsum(
            "bhtd,bhte->bhde", kif * wk[..., None], vif)
        n_new = nst * decay[..., None] + jnp.einsum("bhtd,bht->bhd", kif, wk)
        return (C_new, n_new, m_new), h

    C0 = jnp.zeros((B, H, hd, hd), F32)
    n0 = jnp.zeros((B, H, hd), F32)
    m0 = jnp.full((B, H), -1e30, F32)
    _, hs = lax.scan(chunk, (C0, n0, m0), (qc, kc, vc, ic, fc))
    h = hs.transpose(1, 2, 0, 3, 4).reshape(B, H, nc * cs, hd)[:, :, :S]
    h = h.transpose(0, 2, 1, 3).reshape(B, S, di).astype(x.dtype)
    h = h * z
    out = h + p["l_skip"] * xi
    return out @ p["l_down"]


def mlstm_step(p: dict, x: jax.Array, cfg: ModelConfig, state: dict
               ) -> tuple[jax.Array, dict]:
    """One-token mLSTM.  state = {"C": (B,H,hd,hd), "n": (B,H,hd), "m": (B,H)} (f32)."""
    B = x.shape[0]
    xi = x[:, 0] @ p["l_up"]
    z = jax.nn.silu(x[:, 0] @ p["l_z"])
    di = xi.shape[-1]
    H = cfg.n_heads
    hd = di // H
    q, k, v, i_pre, f_pre = _mlstm_qkv(p, xi)                # (B,H,hd), (B,H)
    k = k / math.sqrt(hd)
    logf = jax.nn.log_sigmoid(f_pre)
    m_new = jnp.maximum(logf + state["m"], i_pre)
    fw = jnp.exp(logf + state["m"] - m_new)[..., None]
    iw = jnp.exp(i_pre - m_new)[..., None]
    C = state["C"] * fw[..., None] + iw[..., None] * jnp.einsum(
        "bhd,bhe->bhde", k.astype(F32), v.astype(F32))
    n = state["n"] * fw + iw * k.astype(F32)
    num = jnp.einsum("bhde,bhd->bhe", C, q.astype(F32))
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", n, q.astype(F32))),
                      jnp.exp(-m_new))
    h = (num / den[..., None]).reshape(B, di).astype(x.dtype)
    h = h * z
    out = h + p["l_skip"] * xi
    return (out @ p["l_down"])[:, None], {"C": C, "n": n, "m": m_new}


def _slstm_cell(p, xt, state):
    """xt: (B, 4, H, hd) pre-activations from input; state h/c/n/m: (B,H,hd)."""
    rh = jnp.einsum("bhd,ghde->bghe", state["h"].astype(F32), p["s_r"].astype(F32))
    pre = xt.astype(F32) + rh + p["s_b"].astype(F32)
    i_pre, f_pre, z_pre, o_pre = pre[:, 0], pre[:, 1], pre[:, 2], pre[:, 3]
    logf = jax.nn.log_sigmoid(f_pre)
    m_new = jnp.maximum(logf + state["m"], i_pre)
    i_g = jnp.exp(i_pre - m_new)
    f_g = jnp.exp(logf + state["m"] - m_new)
    zt = jnp.tanh(z_pre)
    ot = jax.nn.sigmoid(o_pre)
    c = f_g * state["c"] + i_g * zt
    n = f_g * state["n"] + i_g
    h = ot * c / jnp.maximum(n, 1e-6)
    return {"h": h, "c": c, "n": n, "m": m_new}


def slstm_full(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    B, S, D = x.shape
    H = cfg.n_heads
    hd = D // H
    xt = jnp.einsum("bsd,dghe->bsghe", x, p["s_w"])          # (B,S,4,H,hd)
    state = {k: jnp.zeros((B, H, hd), F32) for k in ("h", "c", "n")}
    state["m"] = jnp.full((B, H, hd), -1e30, F32)

    def step(st, xt_t):
        st = _slstm_cell(p, xt_t, st)
        return st, st["h"]

    _, hs = lax.scan(step, state, xt.transpose(1, 0, 2, 3, 4))
    h = hs.transpose(1, 0, 2, 3).reshape(B, S, D).astype(x.dtype)
    return h @ p["s_out"]


def slstm_step(p: dict, x: jax.Array, cfg: ModelConfig, state: dict
               ) -> tuple[jax.Array, dict]:
    B = x.shape[0]
    H = cfg.n_heads
    hd = x.shape[-1] // H
    xt = jnp.einsum("bd,dghe->bghe", x[:, 0], p["s_w"])
    st = _slstm_cell(p, xt, state)
    h = st["h"].reshape(B, -1).astype(x.dtype)
    return (h @ p["s_out"])[:, None], st
