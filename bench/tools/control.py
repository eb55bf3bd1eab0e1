"""Readings that set a cell's limits, on the chip at the cell's size.

A rollout mix: for each seed, one process serves one batch of the cell's mix
through the timed path, then reads, at the same positions of the same
trajectories, the gap numbers a run compares (``logit_gap``, the widest;
``logit_gap_mean``):

* ``served``: of the tokens the program served (what a run checks);
* ``bf16``: of the tokens a sound bf16 server would put first (the plain
  reference computed in bf16, on the natural sequence);
* ``int8``: the control, of the tokens the reference with int8 weights would put
  first: the step below the configuration's bf16.

Each of the three is also judged as a run judges the served tokens, by
``run.checks`` and ``run.passed`` against the cell's own limits (``*_correct``);
the control has to come out false.

An admission mix (``bench/admit.py``): for each seed, ``--batches`` batches are
admitted through the timed path, as a run admits them, and the lanes read back
are compared with the float32 reference (``kv_err``, ``kv_err_mean``) as
``program``; ``bf16``, ``int8`` and ``fp8`` put the reference in that precision
in the program's place, at the same lanes.  Each is judged by ``admit.checks``.

    python3 -m bench.tools.control --workload <name> --seeds 12 [--first 0] [--batches 4]

Prints one JSON line per seed, then a summary line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from bench import run as R


MODES = ("served", "bf16", "int8")
ADMIT_MODES = ("program", "bf16", "int8", "fp8")


def readings(cell, seeds, device, log=print) -> list[dict]:
    from bench import correct as C
    from bench.spans import Recorder
    from bench.traffic.generator import sub_seed
    from bench.weights import make_params

    sys_ = R.build(cell, seeds[0])
    R.warm_up(sys_, seeds[0])
    out = []
    for seed in seeds:
        t0 = time.perf_counter()
        sys_.params = None                   # one set of weights on the chip at a time
        sys_.params = make_params(sys_.cfg, sub_seed(seed, 0) % (2 ** 31 - 1))
        sys_.batches = [R.run_batch(sys_, Recorder(), seed, 0)]
        served = time.perf_counter() - t0
        gaps = R.compare(sys_, seed, alt_modes=MODES[1:])
        row = {"seed": seed, **gaps,
               **{f"{m}_correct": R.passed(R.checks(sys_, gaps[m])) for m in MODES},
               "data_plane": C.data_plane(sys_.batches),
               "serve_s": served, "total_s": time.perf_counter() - t0}
        log(json.dumps(row))
        out.append(row)
    return out


def admit_readings(cell, seeds, batches: int = 4, log=print,
                   control_seeds: int | None = None) -> list[dict]:
    """Per seed: the program's numbers; on the first ``control_seeds`` seeds
    (all, where None) also those of each precision of the reference."""
    from bench import admit as A
    from bench.spans import Recorder
    from bench.traffic.generator import sub_seed
    from bench.weights import make_params

    sys_ = A.build(cell, seeds[0])
    A.warm_up(sys_, seeds[0], Recorder())
    out = []
    for seed in seeds:
        t0 = time.perf_counter()
        sys_.params = sys_.worker.params = None    # one set of weights at a time
        sys_.params = sys_.worker.params = make_params(
            sys_.cfg, sub_seed(seed, 0) % (2 ** 31 - 1))
        sys_.batches = [A.run_batch(sys_, Recorder(), seed, i) for i in range(batches)]
        served = time.perf_counter() - t0
        control = control_seeds is None or len(out) < control_seeds
        modes = ADMIT_MODES if control else ADMIT_MODES[:1]
        nums = A.compare(sys_, modes)
        row = {"seed": seed, **nums,
               **{f"{m}_correct": R.passed(A.checks(cell, nums[m], sys_.batches))
                  for m in modes},
               "guarantees": A.guarantees(sys_.batches),
               "serve_s": served, "total_s": time.perf_counter() - t0}
        log(json.dumps(row))
        out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first", type=int, default=0)
    ap.add_argument("--batches", type=int, default=4)
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="admission: read the reference's precisions on this many")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(R.ROOT / "src"))
    cell = R.load_cell(args.workload)
    device = R.require_chips(cell.chips)
    R.configure_jax(R.ROOT)
    seeds = [2 ** 31 + 1000 * (args.first + i) + 17 for i in range(args.seeds)]
    log = lambda m: print(m, flush=True)              # noqa: E731
    if cell.traffic.get("serve", "rollout") == "admit":
        modes = ADMIT_MODES
        rows = admit_readings(cell, seeds, args.batches, log, args.control_seeds)
    else:
        modes = MODES
        rows = readings(cell, seeds, device, log)
    summary = {f"{m}.{k}": {"max": max(r[m][k] for r in rows if m in r),
                            "min": min(r[m][k] for r in rows if m in r)}
               for m in modes for k in rows[0][m]}
    summary.update({f"{m}_correct": sum(r[f"{m}_correct"] for r in rows if m in r)
                    for m in modes})
    print(json.dumps({"workload": cell.name, "device": device, "seeds": len(rows),
                      "summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
