"""The chip benchmark: one cell of ``BENCHMARK.json``, one run.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell pairs a model configuration (``bench/configs/<name>.json``) with a traffic
mix (``bench/traffic/<name>.json``); its limits are ``bench/limits/<cell>.json``.
A mix whose file says ``"serve": "admit"`` times admission alone
(``bench/admit.py``).  Any other mix is a rollout: the run serves it through the
program's normal rollout path, ``make_runtime`` then ``RolloutRuntime.run``
(Orchestrator -> EngineBackend -> RolloutWorker: paged chunked prefill, the
paged Pallas decode loop, the sampler), in a closed loop: batch ``i`` is drawn
from ``(seed, i)`` and handed over whole, as a trainer hands one over; the next
follows when it has finished, until ``--seconds`` have passed, and the last one
started runs to its end.

Set-up makes the weights on the device from the seed, fits the scheduler's
predictor on the mix's history, and runs every program the window will run once,
so that nothing compiles in the window.  After the window the served tokens of a
sample of trajectories are compared with the plain float32 reference
(``bench/correct.py``), and the data plane's guarantees are counted.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, each read by ``bench/metrics/<name>.py``,
and the device trace's breakdown.  The last line of standard output is one JSON
object.  A run exits non-zero, and prints no result, where JAX finds no TPU or
fewer chips than the cell asks for.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SAMPLE = 4                          # trajectories compared with the reference
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    ticks = os.sysconf("SC_CLK_TCK")
    with open("/proc/self/stat") as f:
        start = int(f.read().rsplit(")", 1)[1].split()[19]) / ticks
    with open("/proc/uptime") as f:
        return float(f.read().split()[0]) - start


# ---------------------------------------------------------------- the cell

@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    spec: dict                       # the configuration file
    traffic: dict                    # the traffic file
    end_to_end: list[dict]
    per_layer: list[dict]
    limits: dict                     # bench/limits/<workload>.json
    family: str = "qwen3"


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    spec = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((BENCH / "limits" / f"{workload}.json").read_text())
    return Cell(workload, int(w["chips"]), w["config"], spec, traffic,
                bench["end_to_end"], bench["per_layer"], limits,
                spec.get("family", "qwen3"))


def require_chips(n: int) -> dict:
    """The device as JAX reports it; exits where there is no TPU or too few."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devices[0].platform!r}); the "
              "benchmark measures the chip and has no fallback", file=sys.stderr)
        sys.exit(3)
    if len(devices) < n:
        print(f"bench: the cell needs {n} chips, JAX found {len(devices)}",
              file=sys.stderr)
        sys.exit(3)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": n}


def configure_jax(root: Path) -> None:
    """Persistent compile cache at a fixed path inside the checkout, for every
    program however short its compile; no source paths in lowered programs."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_traceback_in_locations_limit", 0)


class CompileCount:
    """Backend compiles (or reads from the persistent cache) and traces to a
    jaxpr seen so far."""

    def __init__(self):
        import jax

        self.n = 0
        self.seconds = 0.0
        self.names: list[str] = []
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, fun_name="?", **_):
        if event == COMPILE_EVENT:
            self.n += 1
            self.seconds += duration
            self.names.append(str(fun_name))
        elif event == TRACE_EVENT:
            self.traces += 1


class GcPauses:
    """Python's garbage collections while it is on: (generation, seconds)."""

    def __init__(self):
        self.pauses: list[tuple[int, float]] = []
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((info["generation"], time.perf_counter() - self._t))

    def stop(self) -> str:
        gc.callbacks.remove(self._on)
        longest = max((d for _, d in self.pauses), default=0.0)
        full = sum(1 for g, _ in self.pauses if g == 2)
        return (f"{len(self.pauses)} garbage collections ({full} full), "
                f"longest {longest:.4f} s")


# ---------------------------------------------------------------- the system

@dataclass
class System:
    cell: Cell
    cfg: object
    params: object
    predictor: object
    capacity: int
    batches: list[dict] = field(default_factory=list)


def runtime_seed(seed: int, index: int) -> int:
    from bench.traffic.generator import sub_seed
    return sub_seed(seed, index, 2) % (2 ** 31 - 1024)


def build(cell: Cell, seed: int) -> System:
    from bench.traffic import generator as G
    from bench.weights import make_params
    from repro.core.predictor import ProgressivePredictor

    fam = importlib.import_module(f"bench.configs.{cell.family}")
    cfg = fam.program_config(cell.config_name, cell.spec)
    params = make_params(cfg, G.sub_seed(seed, 0) % (2 ** 31 - 1))
    predictor = ProgressivePredictor().fit_trajectories(G.history(cell.traffic))
    return System(cell, cfg, params, predictor, G.capacity_bound(cell.traffic))


def make_batch_runtime(sys_: System, trajs, prompts, rs: int):
    from repro.engine.runtime import RuntimeConfig, make_runtime

    tr = sys_.cell.traffic
    rcfg = RuntimeConfig(scheduler=tr["scheduler"], migration=bool(tr["migration"]),
                         max_active=tr["max_active"], quantum=tr["quantum"],
                         tool_latency_scale=tr["tool_latency_scale"], seed=rs)
    runtime = make_runtime(sys_.cfg, sys_.params, trajs, sys_.predictor,
                           n_workers=tr["workers"], config=rcfg,
                           capacity=sys_.capacity, temperature=tr["temperature"])
    runtime.prompts.update(prompts)          # serve the benchmark's prompt ids
    return runtime


def pools(runtime):
    return [ws.engine.pool for ws in runtime.workers]


def warm_up(sys_: System, seed: int) -> None:
    """Run every program the window runs: a short batch through the normal path,
    then each decode length, migration size and lane slice the mix can reach."""
    import jax
    import jax.numpy as jnp
    from bench.traffic import generator as G
    from repro.engine import worker as W
    from repro.models import model as M

    tr = sys_.cell.traffic
    q = tr["quantum"]
    trajs, prompts = G.batch(tr, seed, -1)
    for t in trajs:                  # two short steps each: every event kind, quickly
        p = t.payload
        n = min(p.num_steps, 2)
        p.gen_tokens[:] = [min(g, q + 1) for g in p.gen_tokens[:n]]
        for lst in (p.tool_latency, p.tool_failed, p.tool_output_tokens):
            del lst[n:]
        p.tool_failed[-1] = False
    runtime = make_batch_runtime(sys_, trajs, prompts, runtime_seed(seed, -1))
    runtime.run()
    for n in range(1, len(trajs) + 1):
        sys_.predictor.predict_batch(trajs[:n])
    eng = [ws.engine for ws in runtime.workers]
    e = eng[0]
    sid = 1 << 30
    e.prefill(sid, list(range(5, 5 + min(2 * q, sys_.capacity // 2))))
    for n in range(1, q + 1):
        e.decode([sid], n)
    for w in eng:
        w.pool = W._copy_block(w.pool, jnp.asarray(0, jnp.int32),
                               jnp.asarray(0, jnp.int32))
        for slot in range(w.max_slots):
            M.paged_gather_state(w.pool, slot)
    slot = e.store[sid].slot
    state = M.paged_gather_state(e.pool, slot)
    row = e._row_of(e.lane_pages.get(slot, []))
    for k in range(1, e.num_pages + 1):
        idx = jnp.asarray([0] * k, jnp.int32)       # scratch block: harmless
        pages = W._gather_pages(e.pool, idx)
        e.pool = W._paged_ingest(e.pool, pages, idx, state,
                                 jnp.asarray(slot, jnp.int32), row)
    e.release(sid)
    jax.block_until_ready([w.pool for w in eng])
    for w in eng:
        w.reset_cache()


def run_batch(sys_: System, rec, seed: int, index: int) -> dict:
    import jax
    from bench.traffic import generator as G

    trajs, prompts = G.batch(sys_.cell.traffic, seed, index)
    rs = runtime_seed(seed, index)
    t0 = time.perf_counter_ns()
    runtime = make_batch_runtime(sys_, trajs, prompts, rs)
    rec.instrument(runtime, t0)
    runtime.run()
    jax.block_until_ready(pools(runtime))
    t1 = time.perf_counter_ns()
    counters = [ws.engine.dispatch_stats() for ws in runtime.workers]
    drained = []
    for ws in runtime.workers:
        ws.engine.reset_cache()
        drained.append(ws.engine.dispatch_stats())
    out = {"index": index, "t0": t0, "t1": t1, "wall_s": (t1 - t0) * 1e-9,
           "tokens": runtime.backend.total_tokens, "trajectories": trajs,
           "prompts": prompts, "runtime_seed": rs, "counters": counters,
           "drained": drained,
           "gens": {t.traj_id: rec.gens.get(t.traj_id, []) for t in trajs},
           "admitted_by": {t.traj_id: rec.admitted_by.get(t.traj_id) for t in trajs}}
    del runtime
    gc.collect()
    return out


@contextmanager
def device_trace(directory: str):
    """Profile what runs inside: the profiler starts and stops outside the
    ``WINDOW`` annotation that bounds the traced interval."""
    import jax
    from bench.trace import WINDOW

    jax.profiler.start_trace(directory)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield
    finally:
        jax.profiler.stop_trace()


# ---------------------------------------------------------------- correctness

def natural_sequence(b: dict, t) -> dict:
    """Prompt, then each turn's generated tokens and its tool output, with the
    indices of the served tokens."""
    from bench.traffic import generator as G

    plan = t.payload
    toks = list(b["prompts"][t.traj_id])
    served = []
    gens = b["gens"][t.traj_id]
    for k, gen in enumerate(gens):
        served.extend(range(len(toks), len(toks) + len(gen)))
        toks.extend(gen)
        if k + 1 < plan.num_steps:
            toks.extend(G.tool_tokens(b["runtime_seed"], t.traj_id, k,
                                      plan.tool_output_tokens[k]))
    return {"tokens": toks, "served": served}


def pick_sample(batches: list[dict], seed: int, k: int = SAMPLE) -> list[tuple]:
    """Of the trajectories that finished, the one with the most served tokens,
    and ``k - 1`` others drawn from the seed."""
    import numpy as np
    from bench.traffic.generator import sub_seed

    pool = [(b, t) for b in batches for t in b["trajectories"] if t.finished]
    if not pool:
        return []
    n_served = [sum(len(g) for g in b["gens"][t.traj_id]) for b, t in pool]
    longest = int(np.argmax(n_served))
    rest = [i for i in range(len(pool)) if i != longest]
    rng = np.random.default_rng(sub_seed(seed, 3))
    chosen = [longest] + list(rng.choice(rest, size=min(k - 1, len(rest)),
                                         replace=False))
    return [pool[i] for i in chosen]


def compare(sys_: System, seed: int, alt_modes=()) -> dict:
    """The gap numbers of the served tokens of the sample (``correct.gap_numbers``),
    and those of the tokens each alternative precision of the reference would
    put first at the same positions."""
    from bench import correct as C

    ref = importlib.import_module(f"bench.configs.{sys_.cell.family}_reference")
    tr = sys_.cell.traffic
    margin = sys_.cell.limits["nucleus_margin"]
    parts = []
    for b, t in pick_sample(sys_.batches, seed):
        r = natural_sequence(b, t)
        r["key"] = C.seq_key(b["runtime_seed"], b["admitted_by"][t.traj_id], t.traj_id)
        parts.append(C.sequence_gap(ref, sys_.cell.spec, sys_.params, r, sys_.capacity,
                                    tr["temperature"], tr["top_p"], margin, alt_modes))
    return {"n": sum(p["n"] for p in parts),
            **{m: C.gap_numbers(parts, m) for m in ("served",) + tuple(alt_modes)}}


def checks(sys_: System, gaps: dict) -> dict:
    """Each gap number the configuration states a limit for, then the data
    plane's guarantees (limit 0)."""
    from bench import correct as C

    out = {k: {"value": gaps[k], "limit": lim}
           for k, lim in sys_.cell.limits["limits"].items()}
    for k, v in C.data_plane(sys_.batches).items():
        out[k] = {"value": v, "limit": 0}
    return out


def passed(ch: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in ch.values())


# ---------------------------------------------------------------- metrics

def host_stalls(rec, batches) -> str:
    """Where a slow batch lost its time: the longest span of each kind, and the
    longest stretch of a batch's wall outside every span."""
    from bench.trace import merge

    longest: dict[str, float] = {}
    for name, s, e in rec.spans:
        longest[name] = max(longest.get(name, 0.0), (e - s) * 1e-9)
    outside = 0
    for b in batches:
        at = b["t0"]
        for s, e in merge((s, e) for _, s, e in rec.spans
                          if b["t0"] <= s and e <= b["t1"]):
            outside, at = max(outside, s - at), max(at, e)
        outside = max(outside, b["t1"] - at)
    return (f"longest span of each kind (s) {longest}, longest time outside "
            f"every span {outside * 1e-9} s")


@dataclass
class Measures:
    """What the per-layer readers read: spans, counters, work counts, trace."""

    spec: dict
    peaks: dict
    chips: int
    batches: list[dict]
    rec: object
    trace: dict | None
    trace_calls: tuple[int, int]
    memory_peak_bytes: int

    @property
    def window_s(self) -> float:
        return sum(b["wall_s"] for b in self.batches)

    def counter(self, name: str) -> int:
        return sum(c[name] for b in self.batches for c in b["counters"])


def end_to_end(cell: Cell, batches, rec, setup_s: float) -> dict:
    import numpy as np

    wall = sum(b["wall_s"] for b in batches)
    vals = {"setup_s": setup_s,
            "rollout_tokens_per_s": sum(b["tokens"] for b in batches) / wall,
            "turn_latency_p95_s": float(np.percentile(rec.turn_latency_s, 95))}
    return {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def per_layer(cell: Cell, meas) -> dict:
    """Each per-layer metric, read by ``bench/metrics/<name>.py``; a name's part
    after its first ``.`` tells cells apart that read one quantity."""
    out = {}
    for m in cell.per_layer:
        reader = importlib.import_module(f"bench.metrics.{m['name'].split('.')[0]}")
        v = reader.read(meas)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


# ---------------------------------------------------------------- the run

def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, device: dict,
             log=print) -> dict:
    """Set up, warm up, measure, check.  Returns the result object.  A traced run
    profiles the window's first batch whole: every batch is the same plans, and
    the profiler's start and stop fall outside every batch's wall."""
    import jax
    from bench.spans import Recorder

    compiles = CompileCount()
    sys_ = build(cell, seed)
    warm_up(sys_, seed)
    rec = Recorder(annotate=trace)
    gc.collect()
    setup_s = process_age_s()
    compiles0, traces0 = compiles.n, compiles.traces
    gcp = GcPauses()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    trace_calls = (0, 0)
    while sum(b["wall_s"] for b in sys_.batches) < seconds:
        index = len(sys_.batches)
        with device_trace(trace_dir) if trace and index == 0 else nullcontext():
            sys_.batches.append(run_batch(sys_, rec, seed, index))
        if index == 0:
            trace_calls = (0, len(rec.decode_calls))
    window_compiles = compiles.n - compiles0
    gc_line = gcp.stop()
    stats = jax.devices()[0].memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    turns = len(rec.turn_latency_s)
    steps = sum(n for _, n, _, _ in rec.decode_calls)
    log(f"window: {len(sys_.batches)} batches, {turns} turns, "
        f"{len(rec.decode_calls)} decode calls of {steps} steps, "
        f"{sum(b['tokens'] for b in sys_.batches)} tokens in "
        f"{sum(b['wall_s'] for b in sys_.batches):.4f} s of batch wall; "
        f"{window_compiles} compiles inside the window "
        f"{compiles.names[compiles0:compiles.n]}; set-up {setup_s:.4f} s, "
        f"{compiles0} compiles ({compiles.seconds:.4f} s)")
    log(f"batch walls (s): {[b['wall_s'] for b in sys_.batches]}; in the window: "
        f"{compiles.traces - traces0} traces, {gc_line}; {host_stalls(rec, sys_.batches)}")
    result = {"device": dict(device, memory_peak_bytes=mem_peak)}
    traced = None
    if trace_dir is not None:
        from bench import trace as T
        from bench.spans import BACKEND_CALLS

        traced = T.reduce(T.load(trace_dir), cell.chips,
                          BACKEND_CALLS + ("decode", "prefill", "extend"))
        shutil.rmtree(trace_dir, ignore_errors=True)
        result["device"].update(busy_s=traced["busy_s"], window_s=traced["window_s"])
        result["breakdown"] = {"device_ops": traced["device_ops"],
                               "idle_gaps": traced["idle_gaps"]}
    t_ref = time.perf_counter()
    gap = compare(sys_, seed)
    log(f"reference: {gap['n']} served tokens of {SAMPLE} trajectories compared "
        f"in {time.perf_counter() - t_ref:.4f} s: {gap['served']}")
    ch = checks(sys_, gap["served"])
    attempted = sum(len(b["trajectories"]) for b in sys_.batches)
    if trace:
        meas = Measures(cell.spec, peaks_for(device["kind"]), cell.chips,
                        sys_.batches, rec, traced, trace_calls, mem_peak)
        metrics = per_layer(cell, meas)
    else:
        metrics = end_to_end(cell, sys_.batches, rec, setup_s)
    result.update(correct=passed(ch), attempted=attempted,
                  failed=ch["unfinished"]["value"], metrics=metrics)
    result["checks"] = ch
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")    # no logs under /tmp
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench: the program is not in this checkout ({src})", file=sys.stderr)
        return 3
    sys.path.insert(0, str(src))
    cell = load_cell(args.workload)
    device = require_chips(cell.chips)
    configure_jax(ROOT)
    peaks_for(device["kind"])

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    run = run_cell
    if cell.traffic.get("serve", "rollout") == "admit":
        from bench.admit import run_cell as run
    result = run(cell, args.seed, args.seconds, bool(args.trace), device, log)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
