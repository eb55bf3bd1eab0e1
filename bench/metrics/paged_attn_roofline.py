"""Kernels (kernels/decode_attention.py, paged): the least time the live lanes'
paged decode attention needs (valid K and V of every layer, query and output, at
the chip's peak bandwidth, or its operations at peak, whichever is longer), over
the kernel's device time in the traced batch, in %."""

from bench.flops import paged_attn_need
from bench.trace import kernel_ns

KERNEL = r"^%paged_decode_attention_pallas"


def read(m):
    if m.trace is None:
        return None
    a, b = m.trace_calls
    flops = bytes_ = 0.0
    for lanes, n, ctx, _ in m.rec.decode_calls[a:b]:
        f, by = paged_attn_need(m.spec, lanes, n, ctx)
        flops, bytes_ = flops + f, bytes_ + by
    ns = sum(kernel_ns(ops, KERNEL, m.trace["t0"], m.trace["t1"])
             for ops in m.trace["ops"]) / len(m.trace["ops"])
    if ns <= 0 or bytes_ == 0:
        return None
    least = max(bytes_ / m.peaks["hbm_bytes_per_s"], flops / m.peaks["bf16_flops"])
    return 100.0 * least / (ns * 1e-9)
