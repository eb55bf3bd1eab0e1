"""Operations and bytes the model needs, counted from shapes.

Counts follow the published configuration keys, not the program: what a token
needs, whatever the kernels do.  Attention counts only the keys a query may see:
a query at cache position ``p`` attends ``p + 1`` keys.  The matmul counts are
those of ``benchmarks/roofline.py`` (``_attn_flops_token``, ``_mlp_flops_token``).
"""

from __future__ import annotations


def dims(spec: dict) -> dict:
    return {"d": spec["hidden_size"], "H": spec["num_attention_heads"],
            "KV": spec["num_key_value_heads"], "hd": spec["head_dim"],
            "ff": spec["intermediate_size"], "V": spec["vocab_size"],
            "L": spec["num_hidden_layers"]}


def matmul_flops_token(spec: dict, head: bool) -> float:
    """Projections, MLP and (where logits are computed) the head, per token."""
    k = dims(spec)
    d, H, KV, hd, ff, L = k["d"], k["H"], k["KV"], k["hd"], k["ff"], k["L"]
    per_layer = 2 * d * (H + 2 * KV) * hd + 2 * H * hd * d + 3 * 2 * d * ff
    return L * per_layer + (2 * d * k["V"] if head else 0)


def attn_flops_keys(spec: dict, keys: float) -> float:
    """QK^T and PV over ``keys`` attended keys, summed over layers."""
    k = dims(spec)
    return 4 * k["H"] * k["hd"] * keys * k["L"]


def _keys_decode(lanes: int, steps: int, ctx_sum: int) -> float:
    """Keys attended by ``lanes`` lanes over ``steps`` decode steps, when the lanes'
    caches start ``ctx_sum`` positions long in all: step j attends ctx + j + 1."""
    return steps * ctx_sum + lanes * steps * (steps + 1) / 2


def decode_flops(spec: dict, lanes: int, steps: int, ctx_sum: int) -> float:
    """One decode call: every live lane, every step, with its logits."""
    return (lanes * steps * matmul_flops_token(spec, head=True)
            + attn_flops_keys(spec, _keys_decode(lanes, steps, ctx_sum)))


def fill_flops(spec: dict, start: int, end: int) -> float:
    """Prefill or absorption of cache positions [start, end): no logits."""
    n = max(end - start, 0)
    keys = (end * (end + 1) - start * (start + 1)) / 2      # sum of (p + 1)
    return n * matmul_flops_token(spec, head=False) + attn_flops_keys(spec, keys)


def paged_attn_need(spec: dict, lanes: int, steps: int, ctx_sum: int,
                    itemsize: int = 2) -> tuple[float, float]:
    """(flops, bytes) the paged decode attention needs in one decode call, over all
    layers: each live lane's valid K and V, its query and its output.  Masked
    lanes and pages past a lane's length are not needed."""
    k = dims(spec)
    keys = _keys_decode(lanes, steps, ctx_sum)
    kv_bytes = 2 * keys * k["KV"] * k["hd"] * itemsize
    qo_bytes = lanes * steps * 2 * k["H"] * k["hd"] * itemsize
    return attn_flops_keys(spec, keys), (kv_bytes + qo_bytes) * k["L"]
