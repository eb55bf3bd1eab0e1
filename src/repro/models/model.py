"""Model assembly: init, full-sequence forward (train / prefill) and one-token decode.

Layers are stacked as (pattern position x period): parameters and caches carry a leading
``n_periods`` dim and ``jax.lax.scan`` runs over periods, with a Python loop over the
(short) pattern inside the scan body.  This keeps HLO size O(pattern) instead of
O(n_layers) for 30-40 layer models while expressing heterogeneous interleaves.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.distributed.sharding import shard
from repro.models import layers as L
from repro.models.config import ModelConfig

F32 = jnp.float32


# ------------------------------------------------------------------ init

def _norm_params(cfg: ModelConfig, dim: int, dtype) -> dict:
    p = {"scale": jnp.ones((dim,), dtype)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((dim,), dtype)
    return p


def _init_attn(key, cfg: ModelConfig, dtype, cross: bool = False) -> dict:
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    s = 0.02
    p = {
        "wq": jax.random.normal(ks[0], (d, H, hd), dtype) * s,
        "wk": jax.random.normal(ks[1], (d, KV, hd), dtype) * s,
        "wv": jax.random.normal(ks[2], (d, KV, hd), dtype) * s,
        "wo": jax.random.normal(ks[3], (H, hd, d), dtype) * (s / math.sqrt(2 * cfg.n_layers)),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    if cross:
        p["xgate"] = jnp.zeros((), dtype)
    return p


def _init_mlp(key, cfg: ModelConfig, d_ff: int, dtype) -> dict:
    d = cfg.d_model
    ks = jax.random.split(key, 3)
    s = 0.02
    p = {
        "w_in": jax.random.normal(ks[0], (d, d_ff), dtype) * s,
        "w_out": jax.random.normal(ks[1], (d_ff, d), dtype) * (s / math.sqrt(2 * cfg.n_layers)),
    }
    if cfg.activation == "swiglu":
        p["w_gate"] = jax.random.normal(ks[2], (d, d_ff), dtype) * s
    return p


def _init_moe(key, cfg: ModelConfig, dtype) -> dict:
    d, E, eff = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 8)
    s = 0.02
    p = {
        "router": jax.random.normal(ks[0], (d, E), F32) * s,
        "we_in": jax.random.normal(ks[1], (E, d, eff), dtype) * s,
        "we_out": jax.random.normal(ks[2], (E, eff, d), dtype) * (s / math.sqrt(2 * cfg.n_layers)),
    }
    if cfg.activation == "swiglu":
        p["we_gate"] = jax.random.normal(ks[3], (E, d, eff), dtype) * s
    if cfg.shared_d_ff:
        p["ws_in"] = jax.random.normal(ks[4], (d, cfg.shared_d_ff), dtype) * s
        p["ws_gate"] = jax.random.normal(ks[5], (d, cfg.shared_d_ff), dtype) * s
        p["ws_out"] = jax.random.normal(ks[6], (cfg.shared_d_ff, d), dtype) * s
        p["shared_gate"] = jax.random.normal(ks[7], (d,), dtype) * s
    if cfg.dense_residual_ff:
        kd = jax.random.split(ks[7], 3)
        p["wd_in"] = jax.random.normal(kd[0], (d, cfg.dense_residual_ff), dtype) * s
        p["wd_gate"] = jax.random.normal(kd[1], (d, cfg.dense_residual_ff), dtype) * s
        p["wd_out"] = jax.random.normal(kd[2], (cfg.dense_residual_ff, d), dtype) * s
    return p


def _init_mamba(key, cfg: ModelConfig, dtype) -> dict:
    d = cfg.d_model
    di = cfg.ssm_expand * d
    N = cfg.ssm_state_dim
    R = cfg.ssm_dt_rank or -(-d // 16)
    W = cfg.ssm_conv_width
    ks = jax.random.split(key, 6)
    s = 0.02
    return {
        "m_in": jax.random.normal(ks[0], (d, di), dtype) * s,
        "m_z": jax.random.normal(ks[1], (d, di), dtype) * s,
        "m_conv": jax.random.normal(ks[2], (W, di), dtype) * (1.0 / math.sqrt(W)),
        "m_xproj": jax.random.normal(ks[3], (di, R + 2 * N), dtype) * s,
        "m_dtproj": jax.random.normal(ks[4], (R, di), dtype) * (1.0 / math.sqrt(R)),
        "m_Alog": jnp.log(jnp.broadcast_to(jnp.arange(1, N + 1, dtype=F32), (di, N))),
        "m_D": jnp.ones((di,), F32),
        "m_out": jax.random.normal(ks[5], (di, d), dtype) * (s / math.sqrt(2 * cfg.n_layers)),
    }


def _init_mlstm(key, cfg: ModelConfig, dtype) -> dict:
    d = cfg.d_model
    di = cfg.xlstm_expand * d
    H = cfg.n_heads
    hd = di // H
    ks = jax.random.split(key, 8)
    s = 0.02
    return {
        "l_up": jax.random.normal(ks[0], (d, di), dtype) * s,
        "l_z": jax.random.normal(ks[1], (d, di), dtype) * s,
        "l_q": jax.random.normal(ks[2], (di, H, hd), dtype) * s,
        "l_k": jax.random.normal(ks[3], (di, H, hd), dtype) * s,
        "l_v": jax.random.normal(ks[4], (di, H, hd), dtype) * s,
        "l_ig": jax.random.normal(ks[5], (di, H), dtype) * s,
        "l_fg": jax.random.normal(ks[6], (di, H), dtype) * s + 1.0,  # bias toward remember
        "l_skip": jnp.ones((di,), dtype),
        "l_down": jax.random.normal(ks[7], (di, d), dtype) * (s / math.sqrt(2 * cfg.n_layers)),
    }


def _init_slstm(key, cfg: ModelConfig, dtype) -> dict:
    d = cfg.d_model
    H = cfg.n_heads
    hd = d // H
    ks = jax.random.split(key, 3)
    s = 0.02
    return {
        "s_w": jax.random.normal(ks[0], (d, 4, H, hd), dtype) * s,
        "s_r": jax.random.normal(ks[1], (4, H, hd, hd), dtype) * s,
        "s_b": jnp.zeros((4, H, hd), dtype),
        "s_out": jax.random.normal(ks[2], (d, d), dtype) * (s / math.sqrt(2 * cfg.n_layers)),
    }


def _init_layer(key, cfg: ModelConfig, kind: str, dtype) -> dict:
    mixer, _, mlp_kind = kind.partition("+")
    ks = jax.random.split(key, 4)
    p: dict[str, Any] = {"norm1": _norm_params(cfg, cfg.d_model, dtype)}
    if mixer in ("attn", "enc_attn"):
        p["mixer"] = _init_attn(ks[0], cfg, dtype)
    elif mixer == "dec":
        p["mixer"] = _init_attn(ks[0], cfg, dtype)
        p["norm_x"] = _norm_params(cfg, cfg.d_model, dtype)
        p["xattn"] = _init_attn(ks[3], cfg, dtype, cross=True)
    elif mixer == "xattn":
        p["mixer"] = _init_attn(ks[0], cfg, dtype, cross=True)
    elif mixer == "mamba":
        p["mixer"] = _init_mamba(ks[0], cfg, dtype)
    elif mixer == "mlstm":
        p["mixer"] = _init_mlstm(ks[0], cfg, dtype)
    elif mixer == "slstm":
        p["mixer"] = _init_slstm(ks[0], cfg, dtype)
    else:
        raise ValueError(f"unknown mixer {mixer!r}")
    if mlp_kind == "mlp":
        p["norm2"] = _norm_params(cfg, cfg.d_model, dtype)
        p["mlp"] = _init_mlp(ks[1], cfg, cfg.d_ff, dtype)
    elif mlp_kind in ("moe", "moe_dr"):
        p["norm2"] = _norm_params(cfg, cfg.d_model, dtype)
        p["mlp"] = _init_moe(ks[1], cfg, dtype)
    return p


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 8)
    params: dict[str, Any] = {
        "tok_embed": jax.random.normal(keys[0], (cfg.vocab, cfg.d_model), dtype) * 0.02,
        "final_norm": _norm_params(cfg, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(keys[1], (cfg.d_model, cfg.vocab), dtype) * 0.02

    def stack_layers(key, kinds, periods):
        def one_period(k):
            ks = jax.random.split(k, len(kinds))
            return {f"{i:02d}_{kind}": _init_layer(ks[i], cfg, kind, dtype)
                    for i, kind in enumerate(kinds)}
        pkeys = jax.random.split(key, periods)
        trees = [one_period(k) for k in pkeys]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)

    params["blocks"] = stack_layers(keys[2], cfg.block_pattern, cfg.n_periods)
    if cfg.arch_type == "audio":
        params["enc_blocks"] = stack_layers(keys[3], ("enc_attn+mlp",), cfg.encoder_layers)
        params["enc_norm"] = _norm_params(cfg, cfg.d_model, dtype)
    if cfg.arch_type == "vlm":
        params["enc_proj"] = jax.random.normal(keys[4], (cfg.d_model, cfg.d_model), dtype) * 0.02
    return params


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ------------------------------------------------------------------ helpers

def _sinusoidal(seq: int, d: int, dtype) -> jax.Array:
    pos = jnp.arange(seq, dtype=F32)[:, None]
    dim = jnp.arange(d // 2, dtype=F32)[None]
    ang = pos / jnp.power(10_000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


def _use_rope(cfg: ModelConfig) -> bool:
    return cfg.arch_type != "audio"


def _logits(cfg: ModelConfig, params, x) -> jax.Array:
    x = L.block_norm(cfg, params["final_norm"], x)
    head = params["tok_embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head
    return shard(logits, ("batch", None, "vocab"))


def _encoder(cfg: ModelConfig, params, embeds) -> jax.Array:
    """Whisper-style encoder over precomputed (stub) frame embeddings."""
    B, T, D = embeds.shape
    x = embeds + _sinusoidal(T, D, embeds.dtype)[None]
    positions = jnp.arange(T)

    def body(x, p):
        lp = p["00_enc_attn+mlp"]
        h = L.block_norm(cfg, lp["norm1"], x)
        x = x + L.attention_full(lp["mixer"], h, cfg, positions, causal=False,
                                 use_rope=False)
        h = L.block_norm(cfg, lp["norm2"], x)
        x = x + L.mlp(lp["mlp"], h, cfg.activation)
        return x, None

    x, _ = lax.scan(body, x, params["enc_blocks"])
    return L.block_norm(cfg, params["enc_norm"], x)


def _cross_source(cfg: ModelConfig, params, batch) -> Optional[jax.Array]:
    if cfg.arch_type == "audio":
        return _encoder(cfg, params, batch["encoder_embeds"])
    if cfg.arch_type == "vlm":
        return batch["image_embeds"] @ params["enc_proj"]
    return None


# ------------------------------------------------------------------ full forward

def _layer_full(cfg, kind, p, x, ctx, capacity=None):
    """One layer, full sequence.  Returns (x, cache_slice_or_None, aux)."""
    mixer, _, mlp_kind = kind.partition("+")
    aux = jnp.zeros((), F32)
    cache = None
    h = L.block_norm(cfg, p["norm1"], x)
    # Megatron-SP boundary: gather the sequence-sharded residual HERE, on the bf16
    # post-norm tensor — otherwise GSPMD places the all-gather on an f32 upcast inside
    # the mixer and doubles the wire bytes (EXPERIMENTS.md §Perf, vision train: 38 GiB
    # of f32[16,4096,4096] gathers per scan body).
    h = shard(h, ("batch", None, None))
    if mixer in ("attn", "dec", "enc_attn"):
        out = L.attention_full(p["mixer"], h, cfg, ctx["positions"],
                               causal=mixer != "enc_attn",
                               use_rope=_use_rope(cfg), window=cfg.sliding_window)
        x = x + out
        if capacity is not None:
            cache = _kv_from_full(cfg, p["mixer"], h, ctx, capacity)
        if mixer == "dec":
            hx = L.block_norm(cfg, p["norm_x"], x)
            xout = L.attention_full(p["xattn"], hx, cfg, ctx["positions"],
                                    causal=False, use_rope=False,
                                    kv_input=ctx["enc_out"])
            x = x + xout
            if capacity is not None:
                cache.update(_cross_kv(cfg, p["xattn"], ctx["enc_out"]))
    elif mixer == "xattn":
        out = L.attention_full(p["mixer"], h, cfg, ctx["positions"], causal=False,
                               use_rope=False, kv_input=ctx["enc_out"])
        x = x + jnp.tanh(p["mixer"]["xgate"]) * out
        if capacity is not None:
            cache = _cross_kv(cfg, p["mixer"], ctx["enc_out"])
    elif mixer == "mamba":
        x = x + L.mamba_full(p["mixer"], h, cfg)
        if capacity is not None:
            cache = _mamba_state_from_full(cfg, p["mixer"], h)
    elif mixer == "mlstm":
        x = x + L.mlstm_full(p["mixer"], h, cfg)
        if capacity is not None:
            cache = _mlstm_state_from_full(cfg, p["mixer"], h)
    elif mixer == "slstm":
        x = x + L.slstm_full(p["mixer"], h, cfg)
        if capacity is not None:
            cache = _slstm_state_from_full(cfg, p["mixer"], h)
    else:
        raise ValueError(mixer)
    if mlp_kind:
        h = L.block_norm(cfg, p["norm2"], x)
        h = shard(h, ("batch", None, None))      # bf16 SP gather (see above)
        if mlp_kind == "mlp":
            x = x + L.mlp(p["mlp"], h, cfg.activation)
        else:
            out, aux = L.moe(p["mlp"], h, cfg)
            x = x + out
    return x, cache, aux


def forward_full(cfg: ModelConfig, params, batch, capacity: Optional[int] = None,
                 remat: bool = False, return_hidden: bool = False):
    """Full-sequence forward.  batch["tokens"]: (B, S).

    Returns (logits, aux_loss) or, with ``capacity``, (logits, aux_loss, cache) where
    cache decodes from position S onward.  ``remat=True`` checkpoints each period
    (training memory: only the per-period residual stream is stored, and it is
    sequence-sharded on the model axis, Megatron-SP style).  ``return_hidden=True``
    returns the final-normed hidden states instead of logits — used by the fused
    chunked cross-entropy (rl/grpo.py) so full logits are never materialized.
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    seq_ax = "act_seq" if cfg.sequence_parallel else None
    x = params["tok_embed"][tokens]
    x = shard(x, ("batch", seq_ax, None))
    if cfg.arch_type == "audio":
        x = x + _sinusoidal(S, cfg.d_model, x.dtype)[None]
    ctx = {"positions": jnp.arange(S), "enc_out": _cross_source(cfg, params, batch)}

    def body(carry, p_period):
        x, aux = carry
        caches = {}
        for i, kind in enumerate(cfg.block_pattern):
            keyname = f"{i:02d}_{kind}"
            x, cache, a = _layer_full(cfg, kind, p_period[keyname], x, ctx, capacity)
            aux = aux + a
            if capacity is not None:
                caches[keyname] = cache
        x = shard(x, ("batch", seq_ax, None))        # (sequence-parallel) residual store
        return (x, aux), (caches if capacity is not None else None)

    if remat:
        body = jax.checkpoint(body)
    (x, aux), stacked_caches = lax.scan(body, (x, jnp.zeros((), F32)), params["blocks"])
    if return_hidden:
        return L.block_norm(cfg, params["final_norm"], x), aux
    logits = _logits(cfg, params, x)
    if capacity is None:
        return logits, aux
    cache = {"pos": jnp.full((B,), S, jnp.int32), "blocks": stacked_caches}
    return logits, aux, cache


# ---- cache construction from a full forward (prefill) -------------------------

def _kv_from_full(cfg, p, h, ctx, capacity):
    B, S, _ = h.shape
    k = jnp.einsum("btd,dnk->btnk", h, p["wk"])
    v = jnp.einsum("btd,dnk->btnk", h, p["wv"])
    if cfg.qk_norm:
        k = L.rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if _use_rope(cfg):
        k = L.rope(k, ctx["positions"], cfg.rope_theta)
    KV, hd = cfg.n_kv_heads, cfg.hd
    dtype = k.dtype
    ck = jnp.zeros((B, capacity, KV, hd), dtype)
    cv = jnp.zeros((B, capacity, KV, hd), dtype)
    if capacity >= S:
        ck = lax.dynamic_update_slice(ck, k, (0, 0, 0, 0))
        cv = lax.dynamic_update_slice(cv, v, (0, 0, 0, 0))
    else:  # sliding window: keep last `capacity` tokens at ring slots pos % capacity
        keep = jnp.arange(S - capacity, S)
        slots = keep % capacity
        ck = ck.at[:, slots].set(k[:, keep])
        cv = cv.at[:, slots].set(v[:, keep])
    return {"k": ck, "v": cv}


def _cross_kv(cfg, p, enc_out):
    xk = jnp.einsum("btd,dnk->btnk", enc_out, p["wk"])
    xv = jnp.einsum("btd,dnk->btnk", enc_out, p["wv"])
    return {"xk": xk, "xv": xv}


def _mamba_state_from_full(cfg, p, h):
    B, S, _ = h.shape
    xi = h @ p["m_in"]
    W = cfg.ssm_conv_width
    xp = jnp.pad(xi, ((0, 0), (W - 1, 0), (0, 0)))
    conv = sum(xp[:, i:i + S] * p["m_conv"][i] for i in range(W))
    xc = jax.nn.silu(conv)
    a, b, _ = L._mamba_inner(p, xc, cfg)
    h0 = jnp.zeros(a.shape[:1] + a.shape[2:], jnp.float32)
    _, h_last = L._mamba_scan_chunked(a, b, h0)
    return {"h": h_last, "conv": xp[:, S:S + W - 1] if W > 1 else
            jnp.zeros((B, 0, xi.shape[-1]), xi.dtype)}


def _mlstm_state_from_full(cfg, p, h):
    # Rerun the chunked scan, keep final carry.  (Shares math with mlstm_full; the
    # small recompute keeps the public API simple.)
    B, S, _ = h.shape
    di = p["l_up"].shape[1]
    H = cfg.n_heads
    hd = di // H
    xi = h @ p["l_up"]
    q, k, v, i_pre, f_pre = L._mlstm_qkv(p, xi)
    state = {"C": jnp.zeros((B, H, hd, hd), F32), "n": jnp.zeros((B, H, hd), F32),
             "m": jnp.full((B, H), -1e30, F32)}

    def step(st, args):
        kt, vt, it, ft = args
        kt = kt / math.sqrt(hd)
        logf = jax.nn.log_sigmoid(ft)
        m_new = jnp.maximum(logf + st["m"], it)
        fw = jnp.exp(logf + st["m"] - m_new)[..., None]
        iw = jnp.exp(it - m_new)[..., None]
        C = st["C"] * fw[..., None] + iw[..., None] * jnp.einsum(
            "bhd,bhe->bhde", kt.astype(F32), vt.astype(F32))
        n = st["n"] * fw + iw * kt.astype(F32)
        return {"C": C, "n": n, "m": m_new}, None

    xs = (k.transpose(1, 0, 2, 3), v.transpose(1, 0, 2, 3),
          i_pre.transpose(1, 0, 2), f_pre.transpose(1, 0, 2))
    state, _ = lax.scan(step, state, xs)
    return state


def _slstm_state_from_full(cfg, p, h):
    B, S, D = h.shape
    H = cfg.n_heads
    hd = D // H
    xt = jnp.einsum("bsd,dghe->bsghe", h, p["s_w"])
    state = {k: jnp.zeros((B, H, hd), F32) for k in ("h", "c", "n")}
    state["m"] = jnp.full((B, H, hd), -1e30, F32)

    def step(st, xt_t):
        return L._slstm_cell(p, xt_t, st), None

    state, _ = lax.scan(step, state, xt.transpose(1, 0, 2, 3, 4))
    return state


# ------------------------------------------------------------------ decode

def _merge_state(active, new, old):
    """Keep ``old`` state on inactive lanes (slot-pool masked decode).

    Only recurrent mixers need this: their state update is destructive.  Attention KV
    caches are *self-healing* under a frozen ``pos`` — a masked step writes at the same
    slot the resuming token will overwrite — so they skip the merge (see docs/engine.md).
    """
    if active is None:
        return new

    def sel(n, o):
        m = active.reshape(active.shape + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o.astype(n.dtype))

    return jax.tree.map(sel, new, old)


def _layer_step(cfg, kind, p, x, cache, pos, active=None, page_table=None):
    mixer, _, mlp_kind = kind.partition("+")
    new_cache = cache
    h = L.block_norm(cfg, p["norm1"], x)
    if mixer == "attn" and page_table is not None:
        out, ck, cv = L.attention_decode_paged(p["mixer"], h, cfg, cache["k"],
                                               cache["v"], page_table, pos,
                                               use_rope=_use_rope(cfg))
        x = x + out
        new_cache = dict(cache, k=ck, v=cv)
    elif mixer in ("attn", "dec"):
        out, ck, cv = L.attention_decode(p["mixer"], h, cfg, cache["k"], cache["v"],
                                         pos, window=cfg.sliding_window,
                                         use_rope=_use_rope(cfg))
        x = x + out
        new_cache = dict(cache, k=ck, v=cv)
        if mixer == "dec":
            hx = L.block_norm(cfg, p["norm_x"], x)
            x = x + L.cross_attention_decode(p["xattn"], hx, cfg,
                                             cache["xk"], cache["xv"])
    elif mixer == "xattn":
        out = L.cross_attention_decode(p["mixer"], h, cfg, cache["xk"], cache["xv"])
        x = x + jnp.tanh(p["mixer"]["xgate"]) * out
    elif mixer == "mamba":
        out, new_cache = L.mamba_step(p["mixer"], h, cfg, cache)
        new_cache = _merge_state(active, new_cache, cache)
        x = x + out
    elif mixer == "mlstm":
        out, new_cache = L.mlstm_step(p["mixer"], h, cfg, cache)
        new_cache = _merge_state(active, new_cache, cache)
        x = x + out
    elif mixer == "slstm":
        out, new_cache = L.slstm_step(p["mixer"], h, cfg, cache)
        new_cache = _merge_state(active, new_cache, cache)
        x = x + out
    else:
        raise ValueError(mixer)
    if mlp_kind:
        h = L.block_norm(cfg, p["norm2"], x)
        if mlp_kind == "mlp":
            x = x + L.mlp(p["mlp"], h, cfg.activation)
        else:
            out, _ = L.moe(p["mlp"], h, cfg)
            x = x + out
    return x, new_cache


def decode_step(cfg: ModelConfig, params, cache, tokens, active=None):
    """One decode step.  tokens: (B, 1) int32; cache["pos"]: (B,) per-slot positions
    (continuous batching).  Returns (logits (B, V), cache').

    ``active``: optional (B,) bool slot mask.  Inactive lanes do not advance ``pos``
    and keep their recurrent state; their attention KV write lands at the frozen
    ``pos`` slot and is overwritten when the lane resumes, so a preempted / finished /
    free lane can ride along in the batch at zero bookkeeping cost (slot-pool
    continuous batching — the lane's emitted logits are garbage and must be masked
    by the caller).
    """
    pos = cache["pos"]
    page_table = cache.get("page_table")       # paged pool: blocks are shared pages
    x = params["tok_embed"][tokens]
    x = shard(x, ("batch", None, None))
    if cfg.arch_type == "audio":
        d = cfg.d_model
        x = x + _sinusoidal_at(pos, d, x.dtype)

    def body(x, xs):
        p_period, c_period = xs
        new_c = {}
        for i, kind in enumerate(cfg.block_pattern):
            keyname = f"{i:02d}_{kind}"
            x, new_c[keyname] = _layer_step(cfg, kind, p_period[keyname], x,
                                            c_period[keyname], pos, active,
                                            page_table)
        return x, new_c

    x, new_blocks = lax.scan(body, x, (params["blocks"], cache["blocks"]))
    logits = _logits(cfg, params, x)
    new_pos = pos + 1 if active is None else pos + active.astype(jnp.int32)
    new_cache = {"pos": new_pos, "blocks": new_blocks}
    if page_table is not None:
        new_cache["page_table"] = page_table
    return logits[:, 0], new_cache


# ------------------------------------------------------------------ chunked prefill
#
# ``prefill_chunk`` processes a fixed-shape (1, C) token chunk at an arbitrary
# position offset against an existing batch-1 lane cache: attention layers write the
# chunk's K/V into the lane slice and attend to resident + own-causal keys
# (layers.attention_prefill_chunk); recurrent layers run their exact one-token step
# cells over the chunk inside a single fused scan, masking padding rows so the state
# carry is position-exact.  A prompt of any length runs as ceil(S/C) reuses of ONE
# compiled kernel (off/length are traced), and suffix prefill at offset > 0 — tool
# absorption, prefix-reuse admission — is the same code path.  Logits are not
# computed: the engine's decode loop re-feeds the last context token, exactly as it
# does after a full prefill.

def supports_chunked_prefill(cfg: ModelConfig) -> bool:
    """Chunked prefill serves linear (non-ring) caches without cross-attention or MoE
    (MoE capacity dispatch would let padding rows displace real tokens)."""
    for kind in cfg.block_pattern:
        mixer, _, mlp_kind = kind.partition("+")
        if mixer not in ("attn", "mamba", "mlstm", "slstm"):
            return False
        if mlp_kind not in ("", "mlp"):
            return False
    return cfg.sliding_window == 0 and cfg.arch_type not in ("audio", "vlm")


def supports_prefix_reuse(cfg: ModelConfig) -> bool:
    """Prefix KV implanting needs position-sliceable caches: attention-only stacks
    (recurrent mixers only retain their *final* state, not per-position snapshots)."""
    return supports_chunked_prefill(cfg) and all(
        k.partition("+")[0] == "attn" for k in cfg.block_pattern)


def _recurrent_chunk(step_fn, p, h, cfg, state, length):
    """Run a one-token recurrent step cell over a (1, C) chunk inside one scan.

    Padding rows (index >= ``length``) keep the previous state (recurrent updates are
    destructive, unlike the self-healing KV writes).  Returns (out (1, C, d), state')."""
    Cn = h.shape[1]

    def body(st, inp):
        h_t, idx = inp                               # h_t: (1, d)
        out, new = step_fn(p, h_t[:, None], cfg, st)
        valid = idx < length
        new = jax.tree.map(lambda n, o: jnp.where(valid, n, o.astype(n.dtype)),
                           new, st)
        return new, out[:, 0]

    state, outs = lax.scan(body, state, (h.transpose(1, 0, 2), jnp.arange(Cn)))
    return outs.transpose(1, 0, 2), state


def _layer_chunk(cfg, kind, p, x, cache, off, length):
    mixer, _, mlp_kind = kind.partition("+")
    new_cache = cache
    h = L.block_norm(cfg, p["norm1"], x)
    if mixer == "attn":
        out, ck, cv = L.attention_prefill_chunk(p["mixer"], h, cfg, cache["k"],
                                                cache["v"], off, length,
                                                use_rope=_use_rope(cfg))
        x = x + out
        new_cache = dict(cache, k=ck, v=cv)
    elif mixer == "mamba":
        out, new_cache = _recurrent_chunk(L.mamba_step, p["mixer"], h, cfg, cache,
                                          length)
        x = x + out
    elif mixer == "mlstm":
        out, new_cache = _recurrent_chunk(L.mlstm_step, p["mixer"], h, cfg, cache,
                                          length)
        x = x + out
    elif mixer == "slstm":
        out, new_cache = _recurrent_chunk(L.slstm_step, p["mixer"], h, cfg, cache,
                                          length)
        x = x + out
    else:
        raise ValueError(f"prefill_chunk: unsupported mixer {mixer!r} "
                         "(see supports_chunked_prefill)")
    if mlp_kind == "mlp":
        h = L.block_norm(cfg, p["norm2"], x)
        x = x + L.mlp(p["mlp"], h, cfg.activation)
    elif mlp_kind:
        raise ValueError("prefill_chunk: MoE layers are not chunk-safe "
                         "(padding rows would consume expert capacity)")
    return x, new_cache


def prefill_chunk(cfg: ModelConfig, params, cache: dict, tokens: jax.Array,
                  length) -> dict:
    """Teacher-force a fixed-shape (1, C) chunk into a batch-1 lane cache.

    ``tokens``: (1, C) int32, rows >= ``length`` are padding; ``length``: traced
    scalar count of valid tokens.  The chunk lands at positions
    ``pos .. pos + length`` where ``pos = cache["pos"][0]``.  Returns the updated
    lane with ``pos`` advanced by ``length``.
    """
    assert tokens.shape[0] == 1, "prefill_chunk operates on one lane (batch 1)"
    with jax.named_scope("prefill_chunk"):
        off = cache["pos"][0]
        length = jnp.asarray(length, jnp.int32)
        x = params["tok_embed"][tokens]
        x = shard(x, ("batch", None, None))

        def body(x, xs):
            p_period, c_period = xs
            new_c = {}
            for i, kind in enumerate(cfg.block_pattern):
                keyname = f"{i:02d}_{kind}"
                x, new_c[keyname] = _layer_chunk(cfg, kind, p_period[keyname], x,
                                                 c_period[keyname], off, length)
            return x, new_c

        _, new_blocks = lax.scan(body, x, (params["blocks"], cache["blocks"]))
        return {"pos": cache["pos"] + length, "blocks": new_blocks}


def copy_prefix(pool: dict, src_slot, lane: dict, n) -> dict:
    """Implant the first ``n`` cache positions of pool lane ``src_slot`` into a
    batch-1 ``lane`` (radix-cache prefix reuse: GRPO siblings / multi-turn
    re-entries pay O(suffix) prefill instead of O(full prompt)).

    Attention-only caches: every blocks leaf is (P, B, cap, KV, hd) with the
    position axis at 2.  ``src_slot``/``n`` are traced, so one compiled kernel
    serves every (source lane, match length).  Sets ``lane["pos"] = n``.
    """
    src_slot = jnp.asarray(src_slot, jnp.int32)
    n = jnp.asarray(n, jnp.int32)

    def blend(dst, src):
        src_lane = lax.dynamic_slice_in_dim(src, src_slot, 1, axis=1)
        keep = jnp.arange(dst.shape[2])[None, None, :, None, None] < n
        return jnp.where(keep, src_lane.astype(dst.dtype), dst)

    blocks = jax.tree.map(blend, lane["blocks"], pool["blocks"])
    pos = jnp.full_like(lane["pos"], n)
    return {"pos": pos, "blocks": blocks}


def _sinusoidal_at(pos, d, dtype):
    pos = jnp.atleast_1d(pos).astype(F32)                    # (B,) per-slot positions
    dim = jnp.arange(d // 2, dtype=F32)
    ang = pos[:, None] / jnp.power(10_000.0, 2 * dim / d)[None]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)[:, None].astype(dtype)


def init_cache(cfg: ModelConfig, params, batch_size: int, capacity: int,
               enc_out: Optional[jax.Array] = None, start_pos: int = 0) -> dict:
    """Empty decode cache (used by the dry-run's serve_step input_specs and the engine).

    ``capacity`` is the KV slot count (window size when cfg.sliding_window is set).
    """
    dtype = jnp.dtype(cfg.dtype)
    KV, hd = cfg.n_kv_heads, cfg.hd
    B, P = batch_size, cfg.n_periods

    def per_kind(kind):
        mixer = kind.partition("+")[0]
        if mixer == "attn":
            return {"k": jnp.zeros((P, B, capacity, KV, hd), dtype),
                    "v": jnp.zeros((P, B, capacity, KV, hd), dtype)}
        if mixer == "dec":
            c = {"k": jnp.zeros((P, B, capacity, KV, hd), dtype),
                 "v": jnp.zeros((P, B, capacity, KV, hd), dtype)}
            c.update(_stack_cross(kind))
            return c
        if mixer == "xattn":
            return _stack_cross(kind)
        if mixer == "mamba":
            di = cfg.ssm_expand * cfg.d_model
            return {"h": jnp.zeros((P, B, di, cfg.ssm_state_dim), F32),
                    "conv": jnp.zeros((P, B, cfg.ssm_conv_width - 1, di), dtype)}
        if mixer == "mlstm":
            di = cfg.xlstm_expand * cfg.d_model
            hdi = di // cfg.n_heads
            return {"C": jnp.zeros((P, B, cfg.n_heads, hdi, hdi), F32),
                    "n": jnp.zeros((P, B, cfg.n_heads, hdi), F32),
                    "m": jnp.full((P, B, cfg.n_heads), -1e30, F32)}
        if mixer == "slstm":
            hdm = cfg.d_model // cfg.n_heads
            st = {k: jnp.zeros((P, B, cfg.n_heads, hdm), F32) for k in ("h", "c", "n")}
            st["m"] = jnp.full((P, B, cfg.n_heads, hdm), -1e30, F32)
            return st
        raise ValueError(kind)

    def _stack_cross(kind):
        assert enc_out is not None, "cross-attention cache needs encoder output"
        # same cross KV per period position: recompute per period via stacked params
        idx = [i for i, k in enumerate(cfg.block_pattern) if k == kind]
        del idx
        return {"xk": jnp.zeros((P, B, enc_out.shape[1], KV, hd), dtype),
                "xv": jnp.zeros((P, B, enc_out.shape[1], KV, hd), dtype)}

    blocks = {f"{i:02d}_{kind}": per_kind(kind)
              for i, kind in enumerate(cfg.block_pattern)}
    return {"pos": jnp.full((batch_size,), start_pos, jnp.int32), "blocks": blocks}


# ------------------------------------------------------------------ slot-pool ops
#
# A slot-pool cache is an ordinary decode cache whose batch dimension is a pool of
# ``max_slots`` lanes.  Sequences are admitted by writing a batch-1 cache into a free
# lane (``write_slot``), decode runs over the whole pool with an active-slot mask
# (``decode_step(..., active=mask)``), preemption is a mask flip, and migration moves
# one lane (``gather_slots`` -> host -> ``write_slot`` on the destination pool).
# Blocks leaves are laid out (n_periods, B, ...): the lane axis is axis 1; ``pos`` is
# (B,).

def write_slot(pool: dict, lane: dict, slot) -> dict:
    """Write a batch-1 cache ``lane`` into lane ``slot`` of a slot-pool cache.

    Uses ``lax.dynamic_update_slice`` so, under jit with the pool donated, XLA updates
    the lane in place — admission cost is O(lane), not O(pool).
    """
    slot = jnp.asarray(slot, jnp.int32)
    zero = jnp.zeros((), jnp.int32)

    def upd(dst, src):
        start = (zero, slot) + (zero,) * (dst.ndim - 2)
        return lax.dynamic_update_slice(dst, src.astype(dst.dtype), start)

    blocks = jax.tree.map(upd, pool["blocks"], lane["blocks"])
    pos = lax.dynamic_update_slice(
        pool["pos"], lane["pos"].astype(pool["pos"].dtype), (slot,))
    return {"pos": pos, "blocks": blocks}


def gather_slots(pool: dict, idx) -> dict:
    """Extract lanes ``idx`` from a slot-pool cache as a standalone batch-len(idx)
    cache (KV migration packages one lane; parity tests compare lanes)."""
    idx = jnp.asarray(idx, jnp.int32)
    return {"pos": pool["pos"][idx],
            "blocks": jax.tree.map(lambda x: x[:, idx], pool["blocks"])}


def concat_pools(a: dict, b: dict) -> dict:
    """Concatenate two slot-pool caches along the lane axis (pool growth)."""
    return {"pos": jnp.concatenate([a["pos"], b["pos"]]),
            "blocks": jax.tree.map(lambda x, y: jnp.concatenate([x, y], axis=1),
                                   a["blocks"], b["blocks"])}


# ------------------------------------------------------------------ paged-KV pool ops
#
# A paged pool replaces the per-lane (P, B, capacity, KV, hd) attention leaves with
# physical block pools (P, num_blocks, page_size, KV, hd) shared by every lane, plus
# a (B, num_pages) ``page_table`` mapping logical page index -> block id per lane
# (block 0 is reserved scratch: unmapped entries — and any masked lane's self-healing
# write — resolve there).  Recurrent state, cross-KV and ``pos`` keep their dense
# per-lane layout: only position-indexed attention KV pages.  Host-side block
# bookkeeping (alloc/free/refcount sharing) lives in ``engine.paging.PagePool``.

def supports_paged_kv(cfg: ModelConfig) -> bool:
    """Paged KV serves linear (non-ring) decoder-only stacks: sliding-window ring
    writes would wrap across page boundaries, and cross-attention KV is not
    position-paged.  MoE and recurrent mixers are fine — recurrent state simply
    stays dense per-lane."""
    for kind in cfg.block_pattern:
        if kind.partition("+")[0] not in ("attn", "mamba", "mlstm", "slstm"):
            return False
    return cfg.sliding_window == 0 and cfg.arch_type not in ("audio", "vlm")


def _paged_kind(kind: str) -> bool:
    return kind.partition("+")[0] == "attn"


def init_paged_pool(cfg: ModelConfig, params, max_lanes: int, num_blocks: int,
                    page_size: int, num_pages: int) -> dict:
    """Empty paged pool: block pools for attention KV, dense lanes for the rest."""
    base = init_cache(cfg, params, max_lanes, capacity=0)   # attn leaves are empty
    dtype = jnp.dtype(cfg.dtype)
    KV, hd, P = cfg.n_kv_heads, cfg.hd, cfg.n_periods
    blocks = {}
    for key, c in base["blocks"].items():
        if _paged_kind(key[3:]):
            blocks[key] = {"k": jnp.zeros((P, num_blocks, page_size, KV, hd), dtype),
                           "v": jnp.zeros((P, num_blocks, page_size, KV, hd), dtype)}
        else:
            blocks[key] = c
    return {"pos": base["pos"],
            "page_table": jnp.zeros((max_lanes, num_pages), jnp.int32),
            "blocks": blocks}


def _layer_chunk_paged(cfg, kind, p, x, cache, layer, pt_row, slot, off, length):
    """One layer of the paged chunk.  ``cache``: this kind's leaves for every
    period, (P, ...); only ``[layer, ...]`` is read or written."""
    mixer, _, mlp_kind = kind.partition("+")
    new_cache = cache
    with jax.named_scope("norm"):
        h = L.block_norm(cfg, p["norm1"], x)
    if mixer == "attn":
        out, ck, cv = L.attention_prefill_chunk_paged(
            p["mixer"], h, cfg, cache["k"], cache["v"], layer, pt_row, off, length,
            use_rope=_use_rope(cfg))
        x = x + out
        new_cache = dict(cache, k=ck, v=cv)
    elif mixer in ("mamba", "mlstm", "slstm"):
        step_fn = {"mamba": L.mamba_step, "mlstm": L.mlstm_step,
                   "slstm": L.slstm_step}[mixer]
        zero = jnp.zeros((), jnp.int32)

        def start(c):
            return (layer, slot) + (zero,) * (c.ndim - 2)

        state = jax.tree.map(
            lambda c: lax.dynamic_slice(c, start(c), (1, 1) + c.shape[2:])[0], cache)
        out, state = _recurrent_chunk(step_fn, p["mixer"], h, cfg, state, length)
        x = x + out
        new_cache = jax.tree.map(
            lambda c, s: lax.dynamic_update_slice(c, s[None].astype(c.dtype),
                                                  start(c)),
            cache, state)
    else:
        raise ValueError(f"prefill_chunk_paged: unsupported mixer {mixer!r} "
                         "(see supports_paged_kv)")
    if mlp_kind == "mlp":
        with jax.named_scope("norm"):
            h = L.block_norm(cfg, p["norm2"], x)
        with jax.named_scope("mlp"):
            x = x + L.mlp(p["mlp"], h, cfg.activation)
    elif mlp_kind:
        raise ValueError("prefill_chunk_paged: MoE layers are not chunk-safe "
                         "(padding rows would consume expert capacity)")
    return x, new_cache


def prefill_chunk_paged(cfg: ModelConfig, params, pool: dict, slot,
                        tokens: jax.Array, length) -> dict:
    """Teacher-force a fixed-shape (1, C) chunk straight into lane ``slot``'s pages.

    The paged analogue of :func:`prefill_chunk`, minus the gather/implant round
    trip: attention K/V scatters to the lane's mapped blocks at absolute
    positions, queries attend through the gathered page view (resident prefix —
    possibly *shared* pages — plus the chunk's own causal keys), and recurrent
    state updates its dense lane row in place.  ``slot``/``length`` are traced,
    so one compiled kernel serves every (lane, offset, tail-length).

    The layer scan carries the whole pool and indexes it by layer: slicing each
    layer's pool in as the scan's ``xs`` and writing it back as its ``ys``
    would move every block of every layer per chunk, where a chunk writes C
    rows a layer and reads only the lane's pages.
    """
    assert tokens.shape[0] == 1, "prefill_chunk_paged operates on one lane"
    with jax.named_scope("prefill_chunk"):
        slot = jnp.asarray(slot, jnp.int32)
        length = jnp.asarray(length, jnp.int32)
        off = pool["pos"][slot]
        pt_row = pool["page_table"][slot]
        x = params["tok_embed"][tokens]
        x = shard(x, ("batch", None, None))

        def body(carry, xs):
            x, blocks = carry
            p_period, layer = xs
            new_c = {}
            for i, kind in enumerate(cfg.block_pattern):
                keyname = f"{i:02d}_{kind}"
                x, new_c[keyname] = _layer_chunk_paged(cfg, kind, p_period[keyname], x,
                                                       blocks[keyname], layer, pt_row,
                                                       slot, off, length)
            return (x, new_c), None

        (_, new_blocks), _ = lax.scan(
            body, (x, pool["blocks"]),
            (params["blocks"], jnp.arange(cfg.n_periods, dtype=jnp.int32)))
        return {"pos": pool["pos"].at[slot].add(length),
                "page_table": pool["page_table"], "blocks": new_blocks}


def paged_set_lane(pool: dict, slot, row, pos0) -> dict:
    """Map lane ``slot``: write its page-table row and reset its position.
    ``row``: (num_pages,) int32, unmapped tail zeroed (scratch)."""
    slot = jnp.asarray(slot, jnp.int32)
    return {"pos": pool["pos"].at[slot].set(jnp.asarray(pos0, jnp.int32)),
            "page_table": pool["page_table"].at[slot].set(
                jnp.asarray(row, jnp.int32)),
            "blocks": pool["blocks"]}


def paged_copy_block(pool: dict, dst, src) -> dict:
    """Device-to-device copy of one physical block across every paged leaf
    (the boundary partial page of a prefix share is privately copied so the
    sibling's suffix writes never touch the shared block)."""
    dst = jnp.asarray(dst, jnp.int32)
    src = jnp.asarray(src, jnp.int32)
    blocks = {}
    for key, c in pool["blocks"].items():
        if _paged_kind(key[3:]):
            blocks[key] = {
                name: leaf.at[:, dst].set(
                    lax.dynamic_slice_in_dim(leaf, src, 1, axis=1)[:, 0])
                for name, leaf in c.items()}
        else:
            blocks[key] = c
    return {**pool, "blocks": blocks}


def paged_write_lane(pool: dict, lane: dict, slot, row, n) -> dict:
    """Implant a dense batch-1 ``lane`` into the paged pool: scatter its first
    ``n`` KV positions into the blocks mapped by ``row``, write its dense
    per-lane leaves into lane ``slot`` (non-chunkable admission and the
    cross-degree migration/restore fallback)."""
    slot = jnp.asarray(slot, jnp.int32)
    n = jnp.asarray(n, jnp.int32)
    row = jnp.asarray(row, jnp.int32)
    num_pages = row.shape[0]
    zero = jnp.zeros((), jnp.int32)
    blocks = {}
    for key, c in pool["blocks"].items():
        src = lane["blocks"][key]
        if _paged_kind(key[3:]):
            ps = c["k"].shape[2]
            cap = src["k"].shape[2]
            j = jnp.arange(cap)
            page = jnp.clip(j // ps, 0, num_pages - 1)
            blk = jnp.where(j < n, row[page], 0)        # padding -> scratch
            off = j % ps
            blocks[key] = {
                name: c[name].at[:, blk, off].set(
                    src[name][:, 0].astype(c[name].dtype))
                for name in c}
        else:
            def upd(dst, s):
                start = (zero, slot) + (zero,) * (dst.ndim - 2)
                return lax.dynamic_update_slice(dst, s.astype(dst.dtype), start)
            blocks[key] = jax.tree.map(upd, c, src)
    pos = pool["pos"].at[slot].set(lane["pos"][0].astype(pool["pos"].dtype))
    return {"pos": pos, "page_table": pool["page_table"].at[slot].set(row),
            "blocks": blocks}


def paged_gather_pages(pool: dict, blocks_idx) -> dict:
    """Pull physical blocks ``blocks_idx`` out of every paged leaf as compact
    (P, n, page_size, KV, hd) stacks — the D2D migration payload (only the
    lane's *resident* pages move, never the full preallocated lane)."""
    idx = jnp.asarray(blocks_idx, jnp.int32)
    return {key: {name: leaf[:, idx] for name, leaf in c.items()}
            for key, c in pool["blocks"].items() if _paged_kind(key[3:])}


def paged_gather_state(pool: dict, slot: int) -> dict:
    """Batch-1 view of lane ``slot``'s dense (non-paged) leaves + ``pos``."""
    blocks = {key: jax.tree.map(lambda x: x[:, slot:slot + 1], c)
              for key, c in pool["blocks"].items() if not _paged_kind(key[3:])}
    return {"pos": pool["pos"][slot:slot + 1], "blocks": blocks}


def paged_scatter_pages(pool: dict, pages: dict, blocks_idx) -> dict:
    """Write page stacks (from :func:`paged_gather_pages`) into physical blocks
    ``blocks_idx`` — the D2D migration ingress."""
    idx = jnp.asarray(blocks_idx, jnp.int32)
    blocks = dict(pool["blocks"])
    for key, pg in pages.items():
        c = blocks[key]
        blocks[key] = {name: c[name].at[:, idx].set(
            jnp.asarray(pg[name]).astype(c[name].dtype)) for name in c}
    return {**pool, "blocks": blocks}


def paged_write_state(pool: dict, state: dict, slot, row) -> dict:
    """Write a batch-1 dense-leaf ``state`` (from :func:`paged_gather_state`)
    into lane ``slot`` and map its page-table row."""
    slot = jnp.asarray(slot, jnp.int32)
    zero = jnp.zeros((), jnp.int32)
    blocks = dict(pool["blocks"])
    for key, c in state["blocks"].items():
        def upd(dst, src):
            start = (zero, slot) + (zero,) * (dst.ndim - 2)
            return lax.dynamic_update_slice(dst, jnp.asarray(src).astype(dst.dtype),
                                            start)
        blocks[key] = jax.tree.map(upd, pool["blocks"][key], c)
    pos = pool["pos"].at[slot].set(jnp.asarray(state["pos"])[0])
    return {"pos": pos,
            "page_table": pool["page_table"].at[slot].set(jnp.asarray(row, jnp.int32)),
            "blocks": blocks}


def pages_to_lane(pages: dict, state: dict, capacity: int) -> dict:
    """Reassemble a dense batch-1 lane from gathered pages + lane state (the
    cross-degree / checkpoint-restore fallback: page stacks flatten back to a
    contiguous (P, 1, capacity, KV, hd) lane, zero-padded past the resident
    span)."""
    blocks = {key: jax.tree.map(jnp.asarray, c) for key, c in state["blocks"].items()}
    for key, pg in pages.items():
        out = {}
        for name, x in pg.items():
            x = jnp.asarray(x)
            P, n, ps = x.shape[:3]
            flat = x.reshape((P, n * ps) + x.shape[3:])
            pad = capacity - n * ps
            if pad > 0:
                flat = jnp.pad(flat, ((0, 0), (0, pad)) + ((0, 0),) * (flat.ndim - 2))
            else:
                flat = flat[:, :capacity]
            out[name] = flat[:, None]                   # add the lane axis
        blocks[key] = out
    return {"pos": jnp.asarray(state["pos"]), "blocks": blocks}


def grow_paged_blocks(pool: dict, extra: int) -> dict:
    """Append ``extra`` zeroed physical blocks to every paged leaf (block-pool
    growth: page tables are unaffected — block ids are stable)."""
    blocks = {}
    for key, c in pool["blocks"].items():
        if _paged_kind(key[3:]):
            blocks[key] = {
                name: jnp.concatenate(
                    [leaf, jnp.zeros((leaf.shape[0], extra) + leaf.shape[2:],
                                     leaf.dtype)], axis=1)
                for name, leaf in c.items()}
        else:
            blocks[key] = c
    return {**pool, "blocks": blocks}


def grow_paged_lanes(cfg: ModelConfig, pool: dict, extra: int) -> dict:
    """Append ``extra`` empty lanes: dense per-lane leaves and page-table rows
    grow; the physical block pools are untouched (lane count and block count
    scale independently — the whole point of paging)."""
    fresh = init_cache(cfg, None, extra, capacity=0)
    blocks = {}
    for key, c in pool["blocks"].items():
        if _paged_kind(key[3:]):
            blocks[key] = c
        else:
            blocks[key] = jax.tree.map(
                lambda x, y: jnp.concatenate([x, y.astype(x.dtype)], axis=1),
                c, fresh["blocks"][key])
    num_pages = pool["page_table"].shape[1]
    return {"pos": jnp.concatenate([pool["pos"], fresh["pos"]]),
            "page_table": jnp.concatenate(
                [pool["page_table"], jnp.zeros((extra, num_pages), jnp.int32)]),
            "blocks": blocks}
