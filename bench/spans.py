"""Host spans and counters, recorded from the benchmark's side of the program.

The recorder wraps methods on the instances of one batch's runtime (the backend's
data-plane calls and each worker's ``prefill``, ``extend`` and ``decode``); it
never edits the program.  Every span is a (name, start_ns, end_ns) on the host's
``perf_counter_ns`` clock.  In a traced run each span is also a
``jax.profiler.TraceAnnotation``, so the device trace can name the host work
around each idle gap, and admission and absorption end in a sync, so that their
span holds their device time.
"""

from __future__ import annotations

import time
from contextlib import nullcontext

import jax

BACKEND_CALLS = ("admit", "advance", "tool_submit", "tool_absorb", "migrate_out",
                 "migrate_in", "release")
SYNCED = ("admit", "tool_absorb")


class Recorder:
    """Spans, turns and work counts of the batches of one run."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.spans: list[tuple[str, int, int]] = []
        self.turn_latency_s: list[float] = []
        # per decode call: (live lanes, steps, sum of live lanes' starting context,
        # lane slots of the worker)
        self.decode_calls: list[tuple[int, int, int, int]] = []
        # per prefill/extend call: (first computed position, end position)
        self.fills: list[tuple[int, int]] = []
        self._ready: dict[int, int] = {}
        self._turn: dict[int, list[int]] = {}
        self.gens: dict[int, list[list[int]]] = {}
        self.admitted_by: dict[int, int] = {}

    # ------------------------------------------------------------ spans
    def _span(self, name: str, fn, sync=None):
        annotate = self.annotate

        def wrapped(*args, **kwargs):
            ctx = jax.profiler.TraceAnnotation(name) if annotate else nullcontext()
            with ctx:
                t0 = time.perf_counter_ns()
                out = fn(*args, **kwargs)
                if sync is not None:
                    jax.block_until_ready(sync())
                t1 = time.perf_counter_ns()
            self.spans.append((name, t0, t1))
            return out
        return wrapped

    def instrument(self, runtime, batch_start_ns: int) -> None:
        """Wrap one batch's runtime; turn 0 of every trajectory is ready at
        ``batch_start_ns``."""
        backend = runtime.backend
        engines = [v.engine for v in backend.views]

        def pools():
            return [e.pool for e in engines]

        self.admitted_by = {}
        for tr in runtime.trajs:
            self._ready[tr.traj_id] = batch_start_ns
            self._turn[tr.traj_id] = []
            self.gens[tr.traj_id] = []
        admit, submit, absorb = backend.admit, backend.tool_submit, backend.tool_absorb

        def on_admit(trajectories, now=0.0):
            for tr in trajectories:
                self.admitted_by.setdefault(tr.traj_id, tr.worker_id)
            return admit(trajectories, now)

        def on_submit(traj):
            t = time.perf_counter_ns()
            tid = traj.traj_id
            self.turn_latency_s.append((t - self._ready[tid]) * 1e-9)
            self.gens[tid].append(self._turn[tid])
            self._turn[tid] = []
            return submit(traj)

        def on_absorb(traj):
            self._ready[traj.traj_id] = time.perf_counter_ns()
            return absorb(traj)

        hooks = {"admit": on_admit, "tool_submit": on_submit, "tool_absorb": on_absorb}
        for name in BACKEND_CALLS:
            fn = hooks.get(name, getattr(backend, name))
            sync = pools if (self.annotate and name in SYNCED) else None
            setattr(backend, name, self._span(name, fn, sync))
        for e in engines:
            self._instrument_engine(e)

    def _instrument_engine(self, engine) -> None:
        decode, prefill, extend = engine.decode, engine.prefill, engine.extend

        def on_decode(seq_ids, n_tokens, stop_token=None):
            live = [s for s in seq_ids if not engine.store[s].finished]
            ctx = sum(len(engine.store[s].tokens) for s in live)
            out = decode(seq_ids, n_tokens, stop_token)
            self.decode_calls.append((len(live), n_tokens, ctx, engine.max_slots))
            for sid, toks in out.items():
                if sid in self._turn:
                    self._turn[sid].extend(toks)
            return out

        def on_prefill(seq_id, tokens):
            reused = engine.reused_tokens
            prefill(seq_id, tokens)
            self.fills.append((engine.reused_tokens - reused, len(tokens)))

        def on_extend(seq_id, tool_tokens):
            start = len(engine.store[seq_id].tokens)
            extend(seq_id, tool_tokens)
            self.fills.append((start, start + len(tool_tokens)))

        engine.decode = self._span("decode", on_decode)
        engine.prefill = self._span("prefill", on_prefill)
        engine.extend = self._span("extend", on_extend)
