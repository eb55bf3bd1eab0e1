"""Device trace of a traced batch, and its reduction to numbers.

The profiler's ``.xplane.pb`` is read with ``jax.profiler.ProfileData``.  Device
operations are the events of the ``XLA Ops`` line of each ``/device:TPU:<n>``
plane; host spans are the ``TraceAnnotation`` events on the ``/host:CPU`` plane.
Both come from the one file, on one clock.  The reduction works on plain
``(name, start_ns, duration_ns)`` lists, so it can be checked on a recorded one.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW = "bench_window"
OPS_LINE = "XLA Ops"


def load(trace_dir: str):
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(files[-1])


def _stat_text(ev) -> str:
    try:
        return " ".join(str(v) for _, v in ev.stats)
    except Exception:                     # a stat the reader cannot decode
        return ""


def leaves(ops) -> list:
    """The ops that hold no other op: a loop's event spans its body's ops, which
    are events of their own on the same line."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[1] + nxt[2] > o[1] + o[2] or nxt[1] >= o[1] + o[2]]


def _op_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``%fusion.12``."""
    return name.split(" = ", 1)[0]


def device_ops(profile, n_chips: int) -> list[list[tuple[str, int, int, str]]]:
    """Per chip used: (name, start_ns, duration_ns, stats text) of every leaf op;
    the name is the HLO instruction's, without its text."""
    planes = {p.name: p for p in profile.planes}
    out = []
    for i in range(n_chips):
        plane = planes.get(f"/device:TPU:{i}")
        if plane is None:
            raise RuntimeError(f"trace has no /device:TPU:{i} plane "
                               f"(planes: {sorted(planes)})")
        evs = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                evs = [(_op_name(e.name), int(e.start_ns), int(e.duration_ns),
                        _stat_text(e)) for e in line.events]
        out.append(leaves(evs))
    return out


def host_spans(profile, names) -> list[tuple[str, int, int]]:
    """(name, start_ns, duration_ns) of the benchmark's annotations."""
    names = set(names) | {WINDOW}
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    out.append((e.name, int(e.start_ns), int(e.duration_ns)))
    return out


def merge(intervals) -> list[tuple[int, int]]:
    """Union of (start, end) intervals, sorted."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, t0: int, t1: int) -> list[tuple[int, int]]:
    return [(max(s, t0), min(e, t1)) for s, e in intervals if e > t0 and s < t1]


def busy_ns(ops, t0: int, t1: int) -> int:
    """Nanoseconds of [t0, t1) in which some op ran."""
    return sum(e - s for s, e in clip(merge((s, s + d) for _, s, d, *_ in ops), t0, t1))


def gaps(ops, t0: int, t1: int) -> list[tuple[int, int]]:
    """Idle intervals of [t0, t1): no op running."""
    busy = clip(merge((s, s + d) for _, s, d, *_ in ops), t0, t1)
    out, at = [], t0
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < t1:
        out.append((at, t1))
    return out


def label(gap: tuple[int, int], spans) -> str:
    """The innermost host span holding the gap's midpoint; ``control`` where the
    host was outside every data-plane call (the orchestrator's own work)."""
    mid = (gap[0] + gap[1]) / 2
    best = None
    for name, s, d in spans:
        if name != WINDOW and s <= mid < s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "control"


def top_ops(ops, k: int = 10) -> list[list]:
    """The ``k`` op names that took the most device time: [name, seconds]."""
    tot: dict[str, int] = {}
    for name, _, d, *_ in ops:
        tot[name] = tot.get(name, 0) + d
    return [[n, t * 1e-9] for n, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def top_gaps(idle, spans, k: int = 10) -> list[list]:
    """The ``k`` longest idle gaps, each named by the host span it fell in."""
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:k]
    return [[label(g, spans), (g[1] - g[0]) * 1e-9] for g in longest]


def kernel_ns(ops, pattern: str, t0: int, t1: int) -> int:
    """Device nanoseconds of the ops whose name or stats match ``pattern``."""
    rx = re.compile(pattern)
    return sum(d for name, s, d, text in ops
               if t0 <= s < t1 and (rx.search(name) or rx.search(text)))


def reduce(profile, n_chips: int, span_names) -> dict:
    """Window, busy and idle time, top ops and gaps: the traced numbers."""
    spans = host_spans(profile, span_names)
    win = [s for s in spans if s[0] == WINDOW]
    if not win:
        raise RuntimeError("the trace holds no window annotation")
    _, t0, dur = win[0]
    t1 = t0 + dur
    chips = device_ops(profile, n_chips)
    busy = [busy_ns(ops, t0, t1) for ops in chips]
    return {"t0": t0, "t1": t1, "window_s": dur * 1e-9,
            "busy_s": sum(busy) / len(busy) * 1e-9,
            "ops": chips, "spans": spans,
            "device_ops": top_ops([o for ops in chips for o in ops
                                   if t0 <= o[1] < t1]),
            "idle_gaps": top_gaps(gaps(chips[0], t0, t1), spans)}
