"""Model step (models/model.py): the operations the model needs for every token
it processed in the window (decode with logits, prefill and absorption without),
live lanes only and attention over each query's valid keys, over window wall x
chips x the chip's peak, in %."""

from bench.flops import decode_flops, fill_flops


def read(m):
    flops = sum(decode_flops(m.spec, lanes, n, ctx)
                for lanes, n, ctx, _ in m.rec.decode_calls)
    flops += sum(fill_flops(m.spec, a, b) for a, b in m.rec.fills)
    if flops == 0 or m.window_s <= 0:
        return None
    return 100.0 * flops / (m.window_s * m.chips * m.peaks["bf16_flops"])
