"""Plain reference of a Qwen3 decoder (Qwen/Qwen3 ``modeling_qwen3``), in jax.numpy.

It imports nothing of the program.  It reads the published configuration keys and
the benchmark's weights in the layout the program takes: ``tok_embed`` (V, d),
``final_norm.scale``, ``lm_head`` (d, V) where the head is not tied, and per layer,
stacked on a leading axis, ``norm1.scale``, ``mixer.{wq (d,H,hd), wk, wv
(d,KV,hd), wo (H,hd,d), q_norm, k_norm}``, ``norm2.scale`` and ``mlp.{w_in (up),
w_gate, w_out}``.

The block follows the published description: RMSNorm before attention and MLP,
per-head RMSNorm of queries and keys, rotary embedding on halves (rotate_half),
grouped-query attention (query head h reads KV head h // (H / KV)), causal
softmax scaled by 1/sqrt(hd), SwiGLU MLP, a final RMSNorm and the head.

Four precisions, so that the same code gives the reference and its controls:
``f32`` (every matmul at ``highest``), ``bf16`` (matmul inputs and the residual
stream in bf16 with f32 accumulation, as a sound bf16 server computes), ``int8``
and ``fp8`` (weights rounded to int8, or to float8 e4m3, with one scale per
output channel; f32 math).
The model runs one sequence at a time and one layer per call, so that it fits
beside the weights.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
BF16 = jnp.bfloat16
BLOCK = "00_attn+mlp"
MODES = ("f32", "bf16", "int8", "fp8")
FP8_MAX = 448.0                          # largest float8 e4m3 number


def _int8(w, in_axes):
    """Round ``w`` to int8 with one scale per output column (max over ``in_axes``)."""
    w = w.astype(F32)
    s = jnp.max(jnp.abs(w), axis=in_axes, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(w / s), -127, 127) * s


def _fp8(w, in_axes):
    """Round ``w`` to float8 e4m3 with one scale per output column."""
    w = w.astype(F32)
    s = jnp.max(jnp.abs(w), axis=in_axes, keepdims=True) / FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _w(w, mode, in_axes):
    if mode == "int8":
        return _int8(w, in_axes)
    if mode == "fp8":
        return _fp8(w, in_axes)
    return w.astype(BF16 if mode == "bf16" else F32)


def _act(x, mode):
    return x.astype(BF16) if mode == "bf16" else x.astype(F32)


def _mm(spec_str, x, w, mode):
    out = jnp.einsum(spec_str, _act(x, mode), w, preferred_element_type=F32,
                     precision=lax.Precision.HIGHEST)
    return out.astype(BF16).astype(F32) if mode == "bf16" else out


def _rms(x, scale, eps, mode):
    x = x.astype(F32)
    y = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    y = y * scale.astype(F32)
    return y.astype(BF16).astype(F32) if mode == "bf16" else y


def _rope(x, theta):
    """x: (S, heads, hd) at positions 0..S-1."""
    S, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) * 2.0 / hd))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@partial(jax.jit, static_argnames=("hp", "mode"))
def _layer(h, blocks, i, hp, mode):
    """One decoder layer over one sequence h: (S, d) f32.  Returns the layer's
    output and the keys (after their norm and the rotary embedding) and values
    it attends, as a KV cache holds them: (S, KV, hd) each."""
    eps, theta, H, KV, hd = hp
    p = jax.tree.map(lambda x: lax.dynamic_index_in_dim(x, i, keepdims=False),
                     blocks[BLOCK])
    a = p["mixer"]
    x = _rms(h, p["norm1"]["scale"], eps, mode)
    q = _mm("sd,dhk->shk", x, _w(a["wq"], mode, 0), mode)
    k = _mm("sd,dhk->shk", x, _w(a["wk"], mode, 0), mode)
    v = _mm("sd,dhk->shk", x, _w(a["wv"], mode, 0), mode)
    q = _rope(_rms(q, a["q_norm"], eps, mode), theta)
    k = _rope(_rms(k, a["k_norm"], eps, mode), theta)
    S = h.shape[0]
    G = H // KV
    qg = q.reshape(S, KV, G, hd)
    s = jnp.einsum("sngk,tnk->ngst", qg, k, precision=lax.Precision.HIGHEST) / jnp.sqrt(
        jnp.asarray(hd, F32))
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("ngst,tnk->sngk", pr, v, precision=lax.Precision.HIGHEST)
    o = o.reshape(S, H, hd)
    if mode == "bf16":
        o = o.astype(BF16).astype(F32)
    h = h + _mm("shk,hkd->sd", o, _w(a["wo"], mode, (0, 1)), mode)
    m = p["mlp"]
    x = _rms(h, p["norm2"]["scale"], eps, mode)
    up = _mm("sd,df->sf", x, _w(m["w_in"], mode, 0), mode)
    gate = _mm("sd,df->sf", x, _w(m["w_gate"], mode, 0), mode)
    act = jax.nn.silu(gate) * up
    if mode == "bf16":
        act = act.astype(BF16).astype(F32)
    h = h + _mm("sf,fd->sd", act, _w(m["w_out"], mode, 0), mode)
    if mode == "bf16":
        return tuple(x.astype(BF16).astype(F32) for x in (h, k, v))
    return h, k, v


def _hp(spec):
    return (float(spec["rms_norm_eps"]), float(spec["rope_theta"]),
            int(spec["num_attention_heads"]), int(spec["num_key_value_heads"]),
            int(spec["head_dim"]))


def layer_kv(spec: dict, params, tokens, mode: str = "f32"):
    """Per layer, in order: the (keys, values) of ``tokens``, (S, KV, hd) f32
    each; then, last, the hidden states after the last layer."""
    if mode not in MODES:
        raise ValueError(f"mode {mode!r} not in {MODES}")
    rows = jnp.take(params["tok_embed"], jnp.asarray(tokens, jnp.int32), axis=0)
    h = {"int8": _int8, "fp8": _fp8}.get(mode, lambda r, _: r)(rows, 1).astype(F32)
    for i in range(int(spec["num_hidden_layers"])):
        h, k, v = _layer(h, params["blocks"], jnp.asarray(i, jnp.int32), _hp(spec),
                         mode)
        yield k, v
    yield h


def final_hidden(spec: dict, params, tokens, mode: str = "f32"):
    """Hidden states after the last layer, before the final norm: (S, d) f32."""
    for out in layer_kv(spec, params, tokens, mode):
        pass
    return out


@partial(jax.jit, static_argnames=("eps", "mode"))
def _logits(h, scale, head, eps, mode):
    """``head`` is ``lm_head`` (d, V), or the tied ``tok_embed`` (V, d)."""
    x = _rms(h, scale, eps, mode)
    if head.shape[0] == h.shape[-1]:
        return _mm("sd,dv->sv", x, _w(head, mode, 0), mode)
    return _mm("sd,vd->sv", x, _w(head, mode, 1), mode)


def logits(spec: dict, params, h, mode: str):
    """(P, V) f32 logits of final hidden states h: (P, d); the head is read in
    the mode's precision inside the call, so no copy of it outlives the call."""
    head = params["lm_head"] if "lm_head" in params else params["tok_embed"]
    return _logits(h, params["final_norm"]["scale"], head,
                   float(spec["rms_norm_eps"]), mode)
