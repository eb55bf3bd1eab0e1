"""Where one traced admission batch of a cell spends the chip's time, by the
program's own names.

    python3 -m bench.tools.scopes <workload> <seed>

Runs the cell's set-up and warm-up as ``bench.run`` does, profiles the
admission of one batch, and reduces the trace with what ``bench.trace`` leaves
out.  Each device op is put in its program: the event of the ``XLA Modules``
line that holds it.  Each op of the chunk program is put in its named scope:
``jax.profiler.ProfileData`` gives a device op no op name, so the scope is read
from the op name that the chunk program's compiled text keeps for the
instruction of the same name; an instruction XLA inserted has none.  Each idle
gap is named by the innermost of the worker's own spans that holds it.  Prints
one JSON object.  Needs the chip.
"""

from __future__ import annotations

import bisect
import json
import os
import re
import shutil
import sys
import tempfile

from bench import trace as T

CHUNK_PROGRAM = "jit__paged_chunk"
COMPUTE = ("norm", "qkv", "attn", "mlp")
SCOPES = COMPUTE + ("kv_write",)
SPANS = ("prefill", "radix_match", "map_pages", "chunk_dispatch", "seq_key",
         "radix_insert")
_INSTR = re.compile(r"^\s*(?:ROOT )?(%[\w.-]+) = .*$", re.M)
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_of(op_name: str) -> str:
    """The innermost scope of ``SCOPES`` on an op name's path; ``prefill_chunk``
    for the rest of the chunk program's scope; ``unnamed`` for no op name."""
    parts = re.split(r"[/;]", op_name)
    for p in reversed(parts):
        if p in SCOPES:
            return p
    if "prefill_chunk" in parts:
        return "prefill_chunk"
    return "unnamed" if not op_name else "other"


def op_scopes(hlo_text: str) -> dict[str, str]:
    """Instruction name -> ``scope_of`` its op name, over a compiled program."""
    out = {}
    for m in _INSTR.finditer(hlo_text):
        name = _OP_NAME.search(m.group(0))
        out[m.group(1)] = scope_of(name.group(1) if name else "")
    return out


def programs_of(ops, modules) -> list[str]:
    """For each op, the program whose module event holds its start ('' if none)."""
    mods = sorted((s, s + d, name.split("(")[0]) for name, s, d in modules)
    starts = [m[0] for m in mods]
    out = []
    for _, s, *_ in ops:
        i = bisect.bisect_right(starts, s) - 1
        out.append(mods[i][2] if i >= 0 and s < mods[i][1] else "")
    return out


def reduce(ops, modules, spans, t0: int, t1: int, scopes: dict[str, str],
           chunks: int) -> dict:
    """The traced batch's device time by program and by the chunk program's
    scopes, and its idle time by the worker's innermost span."""
    busy_ns, idle = T.busy_ns(ops, t0, t1), T.gaps(ops, t0, t1)
    ops = [o for o in ops if t0 <= o[1] < t1]
    progs = programs_of(ops, modules)
    by_prog: dict[str, int] = {}
    by_scope: dict[str, int] = {}
    for o, prog in zip(ops, progs):
        by_prog[prog] = by_prog.get(prog, 0) + o[2]
        if prog == CHUNK_PROGRAM:
            sc = scopes.get(o[0], "unnamed")
            by_scope[sc] = by_scope.get(sc, 0) + o[2]
    chunk_ns = by_prog.get(CHUNK_PROGRAM, 0)
    compute_ns = sum(by_scope.get(s, 0) for s in COMPUTE)
    idle_by: dict[str, int] = {}
    for g in idle:
        name = T.label(g, spans)
        idle_by[name] = idle_by.get(name, 0) + g[1] - g[0]
    return {
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "chunks": chunks,
        "chunk_device_ms": chunk_ns * 1e-6 / chunks if chunks else None,
        "chunk_compute_share": 100.0 * compute_ns / chunk_ns if chunk_ns else None,
        "program_s": {k: v * 1e-9 for k, v in sorted(by_prog.items(),
                                                      key=lambda x: -x[1])},
        "chunk_scope_s": {k: v * 1e-9 for k, v in sorted(by_scope.items(),
                                                          key=lambda x: -x[1])},
        "top_ops": [[n, s, scopes.get(n, "")] for n, s in T.top_ops(
            [o for o, p in zip(ops, progs) if p == CHUNK_PROGRAM])],
        "idle_by_span_s": {k: v * 1e-9 for k, v in sorted(idle_by.items(),
                                                           key=lambda x: -x[1])},
        "top_gaps": T.top_gaps(idle, spans),
    }


def _modules(profile) -> list[tuple[str, int, int]]:
    for plane in profile.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Modules":
                    return [(e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events]
    return []


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    workload, seed = argv[0], int(argv[1])
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import run as R

    sys.path.insert(0, str(R.ROOT / "src"))
    import jax.numpy as jnp
    from bench import admit as A
    from bench.spans import Recorder
    from repro.engine import worker as W

    cell = R.load_cell(workload)
    R.require_chips(cell.chips)
    R.configure_jax(R.ROOT)
    sys_ = A.build(cell, seed)
    rec = Recorder(annotate=True)
    rec._instrument_engine(sys_.worker)
    A.warm_up(sys_, seed, rec)
    w = sys_.worker
    text = W._paged_chunk.lower(
        w.cfg, w.params, w.pool, jnp.asarray(0, jnp.int32),
        jnp.zeros((1, w.chunk_size), jnp.int32), jnp.asarray(1, jnp.int32),
        mesh=w.mesh).compile().as_text()
    trace_dir = tempfile.mkdtemp(prefix="bench_scopes_")
    try:
        b = A.run_batch(sys_, rec, seed, 0, keep=False, trace_dir=trace_dir)
        profile = T.load(trace_dir)
        spans = T.host_spans(profile, SPANS)
        win = [s for s in spans if s[0] == T.WINDOW][0]
        out = reduce(T.device_ops(profile, 1)[0], _modules(profile), spans,
                     win[1], win[1] + win[2], op_scopes(text),
                     b["counters"][0]["prefill_dispatches"])
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(dict(out, workload=workload, seed=seed,
                          batch_wall_s=b["wall_s"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
