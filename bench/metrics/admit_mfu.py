"""Model step (models/model.py ``prefill_chunk_paged``): the operations the model
needs for the prompt positions admission computed (those the radix cache did not
implant; no logits; attention over each query's valid keys), over window wall x
chips x the chip's peak, in %."""

from bench.flops import fill_flops


def read(m):
    flops = sum(fill_flops(m.spec, a, b) for a, b in m.rec.fills)
    if flops == 0 or m.window_s <= 0:
        return None
    return 100.0 * flops / (m.window_s * m.chips * m.peaks["bf16_flops"])
