#!/usr/bin/env python3
"""Smoke run of the rollout path on a TPU: qwen3-1.7b at its published widths, bf16.

    python3 chip_smoke.py               # one chip (the default phases)
    python3 chip_smoke.py --four-chips  # four chips: the cross-chip phase only

Default phases, one process, no child processes:

1. The backend is a TPU: print the device kind and count, the model's widths
   and dtype.
2. The paged and the dense Pallas decode kernels, compiled at the model's decode
   widths, match the jnp oracles of ``kernels/ref.py`` within ``BF16_ATOL``.
3. The serve path (``repro.launch.serve``: ``make_parser`` -> ``load_model`` ->
   ``build_runtime`` -> ``runtime.run``, i.e. Orchestrator -> EngineBackend ->
   RolloutWorker) serves 8 GRPO-grouped, multi-step tool-loop requests on 2
   workers.  Every trajectory must reach FINISHED.
4. The compiled decode step contains the Pallas kernel (``tpu_custom_call``).

``--four-chips`` runs only the cross-chip phase: a 2,1,1 fleet and a 1,1,1,1
fleet serve the same batch on disjoint device sets covering the host; after a
prefill of a fixed prompt, the MP-2 worker's decode-step logits (its attention
the paged Pallas kernel, run per KV-head shard) match an MP-1 worker's within
``LOGIT_RTOL``; and one tool-interval migration between chips decodes exactly
the tokens a run without migration decodes.

Any failure exits non-zero.  The readings printed on the way (wall and compile
seconds, tokens, peak device memory) are smoke readings, not benchmark numbers.
The compile readings name the longest programs and count persistent-cache
lookups, hits and writes.  The last line of stdout is one JSON object naming
the device:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

F32 = jnp.float32
# kernel vs oracle on unit-normal bf16 inputs: the bound the interpret-mode
# kernel tests hold bf16 to (tests/test_paging.py, tests/test_kernels.py)
BF16_ATOL = 2.5e-2
# MP-2 vs MP-1 logits: max |difference| over the largest |logit|.  Sharded
# matmuls reduce in a different order; 2**-4 is sixteen bf16 ulps of the scale.
LOGIT_RTOL = 2.0 ** -4
SERVE_ARGS = ["--published", "--requests", "8", "--group-size", "4",
              "--workers", "2", "--steps", "3", "--max-tokens", "256",
              "--capacity", "1024"]
# the four-chip phase decodes one token per quantum: every worker mesh then
# compiles one decode program instead of one per quantum length
FOUR_CHIP_ARGS = SERVE_ARGS[:-4] + ["--max-tokens", "64", "--capacity", "256",
                                    "--quantum", "1"]
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENT_PREFIX = "/jax/compilation_cache/"


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


class CompileClock:
    """Seconds of each XLA program's compile, and persistent-cache traffic.

    JAX times compile-or-read-from-cache as one backend-compile event, so a
    program read back from the cache is counted too, at its retrieval time.  JAX
    counts a miss only when it writes the entry, which it does for programs that
    took ``jax_persistent_cache_min_compile_time_secs`` (1 s) or more."""

    def __init__(self):
        self.programs: list[tuple[float, str]] = []
        self.cache = {"compile_requests_use_cache": 0, "cache_hits": 0,
                      "cache_misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, fun_name="?", **_):
        if event == BACKEND_COMPILE_EVENT:
            self.programs.append((duration, fun_name))

    def _on_event(self, event, **_):
        name = event.rsplit("/", 1)[-1]
        if event.startswith(CACHE_EVENT_PREFIX) and name in self.cache:
            self.cache[name] += 1

    def report(self, n_longest: int = 6) -> str:
        total = sum(s for s, _ in self.programs)
        longest = ", ".join(f"{name} {s:.2f} s" for s, name in
                            sorted(self.programs, reverse=True)[:n_longest])
        return (f"XLA compiles {total:.2f} s ({len(self.programs)} programs; "
                f"persistent cache: {self.cache['compile_requests_use_cache']} "
                f"looked up, {self.cache['cache_hits']} hits, "
                f"{self.cache['cache_misses']} written); longest: {longest}")


def device_report(n_chips: int) -> dict:
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        fail(f"JAX found no TPU (platform {d.platform!r}); this smoke run "
             "drives the chip and has no CPU fallback")
    if len(devices) < n_chips:
        fail(f"needs {n_chips} TPU devices, found {len(devices)}")
    print(f"device: {d.device_kind} x{len(devices)} ({d.platform})")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def serve_args(argv):
    from repro.launch.serve import make_parser
    return make_parser().parse_args(argv)


def worker_devices(engine) -> list[int]:
    return sorted({d.id for x in jax.tree.leaves(engine.pool) for d in x.devices()})


def check_kernels(cfg) -> None:
    """Both Pallas decode kernels at decode widths vs the f32 oracles."""
    from repro.kernels.decode_attention import (decode_attention_pallas,
                                                paged_decode_attention_pallas)
    from repro.kernels.ref import decode_attention_ref, paged_decode_attention_ref

    B, KV, G, hd, ps, n_pages = 8, cfg.n_kv_heads, cfg.q_groups, cfg.hd, 16, 64
    n_blocks = B * n_pages + 1
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    bf16 = jnp.dtype(cfg.dtype)
    q = jax.random.normal(ks[0], (B, KV, G, hd), bf16)
    k_pool = jax.random.normal(ks[1], (n_blocks, ps, KV, hd), bf16)
    v_pool = jax.random.normal(ks[2], (n_blocks, ps, KV, hd), bf16)
    table = (1 + jax.random.permutation(ks[3], n_blocks - 1)).reshape(B, n_pages)
    valid = jnp.asarray([1, 17, 250, 512, 700, 1023, 1024, 333], jnp.int32)
    k = jax.random.normal(ks[4], (B, n_pages * ps, KV, hd), bf16)
    v = jax.random.normal(ks[5], (B, n_pages * ps, KV, hd), bf16)
    cases = {
        "paged": (paged_decode_attention_pallas(q, k_pool, v_pool, table, valid,
                                                interpret=False),
                  lambda: paged_decode_attention_ref(
                      q.astype(F32), k_pool.astype(F32), v_pool.astype(F32),
                      table, valid)),
        "dense": (decode_attention_pallas(q, k, v, valid, interpret=False),
                  lambda: decode_attention_ref(q.astype(F32), k.astype(F32),
                                               v.astype(F32), valid)),
    }
    for name, (out, oracle) in cases.items():
        with jax.default_matmul_precision("highest"):
            want = oracle()
        err = float(jnp.max(jnp.abs(out.astype(F32) - want)))
        print(f"kernel {name} decode attention B={B} KV={KV} G={G} hd={hd} "
              f"({n_pages} pages of {ps}): max |pallas - ref| = {err:.3e} "
              f"(tolerance {BF16_ATOL})")
        if not err <= BF16_ATOL:
            fail(f"{name} kernel differs from kernels/ref.py by {err}")


def run_serve(argv, cfg, params):
    """Build and run the runtime the way ``serve.main`` does; all must finish."""
    from repro.launch import serve

    args = serve_args(argv)
    t0 = time.perf_counter()
    runtime = serve.build_runtime(args, cfg, params)
    res = runtime.run()
    jax.block_until_ready([ws.engine.pool for ws in runtime.workers])
    wall = time.perf_counter() - t0
    left = [t.traj_id for t in res.trajectories if not t.finished]
    if len(res.trajectories) != args.requests or left:
        fail(f"{len(left)} of {len(res.trajectories)} trajectories did not "
             f"finish: {left}")
    for ws in runtime.workers:
        served = sum(1 for t in res.trajectories if t.worker_id == ws.wid)
        print(f"  worker {ws.wid} (mp {ws.engine.mp}) on devices "
              f"{worker_devices(ws.engine)}: finished {served} trajectories")
    steps = sum(t.num_steps for t in res.trajectories)
    print(f"  served {len(res.trajectories)}/{args.requests} trajectories to "
          f"FINISHED ({steps} agentic steps, {res.preemptions} preemptions, "
          f"{res.migrations} tool-interval migrations), {res.total_tokens} "
          f"generated tokens, wall {wall:.2f} s to block_until_ready")
    return runtime


def check_decode_hlo(runtime) -> None:
    """The decode program the workers ran contains the Pallas kernel."""
    from repro.engine import worker as W

    w = runtime.workers[0].engine
    B = w.max_slots
    compiled = W._decode_loop.lower(
        w.cfg, w.params, w.pool, jnp.zeros((B,), jnp.int32),
        jnp.zeros((B,), bool), jnp.zeros((B, 2), jnp.uint32),
        runtime.cfg.quantum, None, w.sampler, mesh=w.mesh).compile()
    n = compiled.as_text().count("tpu_custom_call")
    print(f"compiled decode step ({runtime.cfg.quantum} tokens x {B} lanes): "
          f"{n} tpu_custom_call site(s)")
    if n == 0:
        fail("the compiled decode step does not call the Pallas kernel")


def one_chip() -> None:
    from repro.launch.serve import load_model

    cfg, params = load_model(serve_args(SERVE_ARGS))
    jax.block_until_ready(params)
    print(f"model {cfg.name}: published widths, {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.n_heads} heads / {cfg.n_kv_heads} KV heads x "
          f"{cfg.hd}, d_ff {cfg.d_ff}, vocab {cfg.vocab}, {cfg.dtype}")
    check_kernels(cfg)
    print(f"serve: {' '.join(SERVE_ARGS)}")
    runtime = run_serve(SERVE_ARGS, cfg, params)
    check_decode_hlo(runtime)


def decode_logits(engine, prompt: list[int]) -> tuple[np.ndarray, int]:
    """Logits of one decode step after the worker prefills ``prompt``, under its
    own params, paged pool and mesh, and the step's ``tpu_custom_call`` count.

    The step is the worker's: ``decode_step`` on the last context token of the
    lane, whose attention is the paged Pallas kernel — per KV-head shard, inside
    ``shard_map``, on a worker with more than one chip."""
    from repro.distributed.sharding import axis_rules
    from repro.models import model as M

    seq_id = 1 << 20                          # no trajectory of the serve run
    engine.prefill(seq_id, prompt)
    slot, B = engine.store[seq_id].slot, engine.max_slots
    tokens = jnp.zeros((B, 1), jnp.int32).at[slot, 0].set(prompt[-1])
    active = jnp.zeros((B,), bool).at[slot].set(True)

    def step(params, pool, tokens, active):
        with axis_rules(engine.mesh):
            return M.decode_step(engine.cfg, params, pool, tokens, active=active)[0]

    args = (engine.params, engine.pool, tokens, active)
    compiled = jax.jit(step).lower(*args).compile()
    logits = compiled(*args)
    return (np.asarray(logits[slot].astype(F32)),
            compiled.as_text().count("tpu_custom_call"))


def check_migration(cfg, params, capacity: int) -> None:
    """A lane decodes on chip 2, migrates to chip 3 at its tool boundary, absorbs
    the tool output there and decodes on: the tokens equal a chip-1 run that
    never migrated (greedy sampling, same worker key)."""
    from repro.engine.sampler import SamplerConfig
    from repro.engine.worker import RolloutWorker
    from repro.launch.mesh import carve_worker_meshes

    meshes = carve_worker_meshes([1, 1, 1, 1])
    greedy = SamplerConfig(temperature=0.0)

    def worker(chip, wid):
        return RolloutWorker(cfg, params, capacity=capacity, max_slots=2,
                             worker_id=wid, sampler=greedy, mesh=meshes[chip])

    ref, src, dst = worker(1, 0), worker(2, 0), worker(3, 1)
    prompt, tool = list(range(5, 45)), list(range(300, 320))
    for w in (ref, src):
        w.prefill(0, prompt)
    straight = ref.decode([0], 16)[0]
    ref.extend(0, tool)
    straight += ref.decode([0], 16)[0]
    first = src.decode([0], 16)[0]
    dst.migrate_in(src.migrate_out(0))
    dst.extend(0, tool)
    resumed = dst.decode([0], 16)[0]
    print(f"migration: lane decoded 16 tokens on devices {worker_devices(src)}, "
          f"moved to devices {worker_devices(dst)} at the tool boundary, "
          f"absorbed {len(tool)} tool tokens, decoded 16 more; matches the "
          f"unmigrated run on devices {worker_devices(ref)}: "
          f"{first + resumed == straight}")
    if first + resumed != straight:
        fail(f"migrated tokens {first + resumed} != unmigrated {straight}")


def four_chips() -> None:
    from repro.launch.serve import load_model

    cfg, params = load_model(serve_args(FOUR_CHIP_ARGS))
    jax.block_until_ready(params)
    all_ids = sorted(d.id for d in jax.devices())
    for degrees in ("2,1,1", "1,1,1,1"):
        argv = FOUR_CHIP_ARGS + ["--degrees", degrees]
        print(f"fleet {degrees}: {' '.join(argv)}")
        runtime = run_serve(argv, cfg, params)
        sets = [worker_devices(ws.engine) for ws in runtime.workers]
        union = sorted(set().union(*map(set, sets)))
        disjoint = sum(map(len, sets)) == len(union)
        print(f"  worker device sets {sets}: disjoint {disjoint}, cover "
              f"{union == all_ids}")
        if not disjoint or union != all_ids:
            fail(f"fleet {degrees} device sets {sets} are not a partition of "
                 f"{all_ids}")
        if degrees == "2,1,1":
            prompt = list(range(7, 47))           # 40 tokens: 2.5 pages of 16
            mp2, n2 = decode_logits(runtime.workers[0].engine, prompt)
            mp1, n1 = decode_logits(runtime.workers[1].engine, prompt)
            rel = float(np.max(np.abs(mp2 - mp1)) / np.max(np.abs(mp1)))
            print(f"decode-step logits after a {len(prompt)}-token prefill, MP-2 "
                  f"({n2} tpu_custom_call) vs MP-1 ({n1}): max |diff| / max "
                  f"|logit| = {rel:.3e} (tolerance {LOGIT_RTOL:.3e}), argmax "
                  f"{int(mp2.argmax())} vs {int(mp1.argmax())}")
            if n2 == 0:
                fail("the MP-2 decode step does not call the Pallas kernel")
            if not rel <= LOGIT_RTOL:
                fail(f"MP-2 logits differ from MP-1 by {rel} of their scale")
        del runtime
    check_migration(cfg, params, capacity=256)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the cross-chip phase (needs four TPU chips)")
    args = ap.parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)   # a killed run keeps its lines
    from repro.launch.compile_cache import enable_compile_cache
    device = device_report(4 if args.four_chips else 1)
    print(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips()
    else:
        one_chip()
    stats = jax.devices()[0].memory_stats() or {}
    print(f"total wall {time.perf_counter() - t0:.2f} s; {clock.report()}")
    print(f"peak_bytes_in_use on device 0: "
          f"{stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
