"""GRPO admission: a mix whose traffic file says ``"serve": "admit"``.

The window drives ``RolloutWorker.prefill``, the data plane's admission, in the
order ``EngineBackend.admit`` gives it (by group, then sibling), for every
trajectory of a batch.  The first sibling of a group prefills its prompt in
fixed-shape chunks straight into fresh pages of the paged KV pool; each later
sibling finds the prompt in the radix cache, shares the first's full pages and
copies its boundary page.  A batch ends when the pool is ready on the device;
batches follow one another until their walls sum to ``--seconds``.  Between
batches, outside the window's time, two lanes of the batch are read back from
the pool, every lane's position is checked, and every lane is released and the
cache reset, as at a weight sync.

Admission's answer is the key and value of every prompt position in every
layer.  After the window each lane read back is compared, layer by layer, with
the plain reference's keys and values (``kv_numbers``).
"""

from __future__ import annotations

import gc
import importlib
import shutil
import tempfile
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import run as R

PAGE = 16                    # the runtime's page size (RuntimeConfig.page_size)
COUNTERS = ("reused_tokens", "prefilled_tokens", "absorbed_tokens",
            "prefill_dispatches")


@dataclass
class AdmitSystem:
    cell: R.Cell
    cfg: object
    params: object
    worker: object
    capacity: int
    batches: list[dict] = field(default_factory=list)


def build(cell: R.Cell, seed: int) -> AdmitSystem:
    from bench.traffic import generator as G
    from bench.weights import make_params
    from repro.engine.worker import RolloutWorker

    fam = importlib.import_module(f"bench.configs.{cell.family}")
    cfg = fam.program_config(cell.config_name, cell.spec)
    params = make_params(cfg, G.sub_seed(seed, 0) % (2 ** 31 - 1))
    tr = cell.traffic
    cap = -(-tr["max_prompt"] // PAGE) * PAGE
    worker = RolloutWorker(cfg, params, capacity=cap,
                           max_slots=tr["n_prompts"] * tr["group_size"],
                           page_size=PAGE)
    return AdmitSystem(cell, cfg, params, worker, cap)


@partial(jax.jit, static_argnames=("block",))
def lane_kv(pool, slot, block: str):
    """Keys and values of lane ``slot`` at every position its page row maps:
    (layers, capacity, KV, hd) each."""
    row = pool["page_table"][slot]
    out = []
    for x in (pool["blocks"][block]["k"], pool["blocks"][block]["v"]):
        x = x[:, row]
        out.append(x.reshape(x.shape[0], -1, *x.shape[3:]))
    return tuple(out)


def keep_ids(items, seed: int, index: int) -> list[int]:
    """Two lanes to read back: a later sibling of the longest prompt (its pages
    are the first sibling's, its boundary page a copy) and one drawn from the
    seed among the rest."""
    from bench.traffic.generator import sub_seed

    ids = [tid for tid, _ in items]
    n = max(len(t) for _, t in items)
    longest = [tid for tid, t in items if len(t) == n]
    first = longest[min(1, len(longest) - 1)]
    rest = [t for t in ids if t != first]
    rng = np.random.default_rng(sub_seed(seed, index, 4))
    return [first, int(rng.choice(rest))] if rest else [first]


def run_batch(sys_: AdmitSystem, rec, seed: int, index: int, keep: bool = True,
              trace_dir: str | None = None) -> dict:
    """Admit batch ``index``; with ``trace_dir``, profile its admission alone
    (the profiler starts and stops outside the batch's wall)."""
    from bench.traffic import generator as G

    w = sys_.worker
    items = G.admission_batch(sys_.cell.traffic, seed, index, sys_.cfg.vocab)
    before = w.dispatch_stats()
    ctx = jax.profiler.TraceAnnotation("admit") if rec.annotate else nullcontext()
    with R.device_trace(trace_dir) if trace_dir else nullcontext(), ctx:
        t0 = time.perf_counter_ns()
        for tid, toks in items:
            w.prefill(tid, toks)
        jax.block_until_ready(w.pool)
        t1 = time.perf_counter_ns()
    rec.spans.append(("admit", t0, t1))
    after = w.dispatch_stats()
    pos = np.asarray(w.pool["pos"])
    missing = [tid for tid, _ in items if tid not in w.store]
    pos_wrong = sum(1 for tid, toks in items
                    if tid in w.store and int(pos[w.store[tid].slot]) != len(toks))
    block = next(iter(w.pool["blocks"]))
    kept = []
    for tid in (keep_ids(items, seed, index) if keep else []):
        if tid in w.store:
            k, v = lane_kv(w.pool, jnp.asarray(w.store[tid].slot, jnp.int32), block)
            kept.append({"tokens": dict(items)[tid], "k": np.asarray(k),
                         "v": np.asarray(v)})
    for tid, _ in items:
        w.release(tid)
    w.reset_cache()
    return {"index": index, "t0": t0, "t1": t1, "wall_s": (t1 - t0) * 1e-9,
            "lanes": len(items), "tokens": sum(len(t) for _, t in items),
            "missing": len(missing), "pos_wrong": pos_wrong, "kept": kept,
            "counters": [{k: after[k] - before[k] for k in COUNTERS}],
            "drained": [w.dispatch_stats()]}


def warm_up(sys_: AdmitSystem, seed: int, rec) -> None:
    """One batch of the same sizes through the same calls: every program the
    window and the read-back run."""
    run_batch(sys_, rec, seed, -1)
    rec.spans.clear()
    rec.fills.clear()


# ---------------------------------------------------------------- correctness

def _rel(x, ref) -> float:
    return float(jnp.linalg.norm(x - ref) / jnp.maximum(jnp.linalg.norm(ref), 1e-30))


def _stored(x, dtype):
    return x.astype(dtype).astype(jnp.float32)


def lane_errors(ref, spec, params, lane: dict, capacity: int, modes=("program",)):
    """Per mode, per layer, per K and V: the relative error ``|x - ref| / |ref|``
    over the lane's prompt positions, where ``ref`` is the float32 reference as
    the pool stores it (rounded to the pool's type): the rounding every sound
    server pays is not counted.  Mode ``program`` is the lane as read back; any
    other names the reference's precision, stored the same way, in the
    program's place."""
    dtype = lane["k"].dtype
    S = len(lane["tokens"])
    toks = np.zeros((capacity,), np.int32)      # causal: rows past S change nothing
    toks[:S] = lane["tokens"]
    gens = {"f32": ref.layer_kv(spec, params, toks, "f32")}
    for m in modes:
        if m != "program":
            gens[m] = ref.layer_kv(spec, params, toks, m)
    out = {m: [] for m in modes}
    for i in range(int(spec["num_hidden_layers"])):
        layer = {m: next(g) for m, g in gens.items()}
        want = [_stored(x[:S], dtype) for x in layer["f32"]]
        for m in modes:
            if m == "program":
                got = [jnp.asarray(lane[n][i, :S], jnp.float32) for n in ("k", "v")]
            else:
                got = [_stored(x[:S], dtype) for x in layer[m]]
            out[m].append([_rel(g, r) for g, r in zip(got, want)])
    return out


def kv_numbers(errors: list[list[list[float]]]) -> dict:
    """The numbers ``correct`` compares, over lanes x layers x (K, V) relative
    errors: the widest, and the mean."""
    flat = [e for lane in errors for layer in lane for e in layer]
    return {"kv_err": max(flat, default=0.0),
            "kv_err_mean": float(np.mean(flat)) if flat else 0.0}


def compare(sys_: AdmitSystem, modes=("program",)) -> dict:
    ref = importlib.import_module(f"bench.configs.{sys_.cell.family}_reference")
    lanes = [lane for b in sys_.batches for lane in b["kept"]]
    per = [lane_errors(ref, sys_.cell.spec, sys_.params, lane, sys_.capacity, modes)
           for lane in lanes]
    return {"n": len(lanes), **{m: kv_numbers([p[m] for p in per]) for m in modes}}


def guarantees(batches: list[dict]) -> dict:
    from bench.correct import leaked_blocks

    return {"lanes_missing": sum(b["missing"] for b in batches),
            "pos_wrong": sum(b["pos_wrong"] for b in batches),
            "leaked_blocks": leaked_blocks(batches)}


def checks(cell: R.Cell, numbers: dict, batches: list[dict]) -> dict:
    out = {k: {"value": numbers[k], "limit": lim}
           for k, lim in cell.limits["limits"].items()}
    for k, v in guarantees(batches).items():
        out[k] = {"value": v, "limit": 0}
    return out


# ---------------------------------------------------------------- the run

def run_cell(cell: R.Cell, seed: int, seconds: float, trace: bool, device: dict,
             log=print) -> dict:
    from bench.spans import Recorder

    compiles = R.CompileCount()
    sys_ = build(cell, seed)
    rec = Recorder(annotate=trace)
    rec._instrument_engine(sys_.worker)
    warm_up(sys_, seed, rec)
    gc.collect()
    setup_s = R.process_age_s()
    compiles0 = compiles.n
    gcp = R.GcPauses()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    while sum(b["wall_s"] for b in sys_.batches) < seconds:
        index = len(sys_.batches)
        sys_.batches.append(run_batch(sys_, rec, seed, index,
                                      trace_dir=trace_dir if index == 0 else None))
    window_s = sum(b["wall_s"] for b in sys_.batches)
    stats = jax.devices()[0].memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    log(f"window: {len(sys_.batches)} batches, "
        f"{sum(b['lanes'] for b in sys_.batches)} lanes, "
        f"{sum(b['tokens'] for b in sys_.batches)} prompt tokens in {window_s:.4f} s "
        f"of batch wall; {compiles.n - compiles0} compiles inside the window "
        f"{compiles.names[compiles0:compiles.n]}; set-up {setup_s:.4f} s, "
        f"{compiles0} compiles ({compiles.seconds:.4f} s); {gcp.stop()}")
    log(f"batch walls (s): {[b['wall_s'] for b in sys_.batches]}")
    result = {"device": dict(device, memory_peak_bytes=mem_peak)}
    traced = None
    if trace_dir is not None:
        from bench import trace as T

        traced = T.reduce(T.load(trace_dir), cell.chips, ("admit", "prefill"))
        shutil.rmtree(trace_dir, ignore_errors=True)
        result["device"].update(busy_s=traced["busy_s"], window_s=traced["window_s"])
        result["breakdown"] = {"device_ops": traced["device_ops"],
                               "idle_gaps": traced["idle_gaps"]}
    sys_.worker = None                 # the program's pool is freed before the reference
    gc.collect()
    t_ref = time.perf_counter()
    numbers = compare(sys_)
    log(f"reference: {numbers['n']} lanes compared in "
        f"{time.perf_counter() - t_ref:.4f} s: {numbers['program']}")
    ch = checks(cell, numbers["program"], sys_.batches)
    if trace:
        meas = R.Measures(cell.spec, R.peaks_for(device["kind"]), cell.chips,
                          sys_.batches, rec, traced, (0, 0), mem_peak)
        metrics = R.per_layer(cell, meas)
    else:
        vals = {"setup_s": setup_s,
                "admit_tokens_per_s": sum(b["tokens"] for b in sys_.batches) / window_s}
        metrics = {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result.update(correct=R.passed(ch),
                  attempted=sum(b["lanes"] for b in sys_.batches),
                  failed=ch["lanes_missing"]["value"] + ch["pos_wrong"]["value"],
                  metrics=metrics)
    result["checks"] = ch
    return result
