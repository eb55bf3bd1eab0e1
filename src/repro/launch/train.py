"""Training launcher: agentic GRPO with Heddle-orchestrated rollout.

Local (real execution, reduced model on this host):
    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --iters 20

Production dry-run (lower + compile the full config for the pod mesh):
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --dry-run [--multi-pod]
"""

from __future__ import annotations

import argparse
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--group-size", type=int, default=8)
    ap.add_argument("--tasks-per-iter", type=int, default=4)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--lr", type=float, default=8e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=10)
    ap.add_argument("--dry-run", action="store_true",
                    help="lower+compile the FULL config for the production mesh instead "
                         "of training the reduced one locally")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args(argv)

    if args.dry_run:
        # delegate to the dry-run module (it must own process start: device count is
        # locked at first jax init)
        from repro.launch import dryrun
        dr_args = ["--arch", args.arch, "--shape", "train_4k"]
        if args.multi_pod:
            dr_args.append("--multi-pod")
        return dryrun.main(dr_args)

    from repro.checkpoint import checkpoint as ckpt
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.rl import data as D
    from repro.rl.loop import HeddleTrainer, TrainerConfig

    enable_compile_cache()
    cfg = get_config(args.arch).reduced(n_periods=2)
    trainer = HeddleTrainer(cfg, TrainerConfig(
        group_size=args.group_size, n_workers=args.workers, lr=args.lr,
        seed=args.seed))
    print(f"training {cfg.name} (reduced, {cfg.n_layers}L) — {args.iters} iterations, "
          f"{args.workers} workers, GRPO group {args.group_size}")
    t0 = time.time()
    for it in range(args.iters):
        tasks = D.sample_tasks(args.tasks_per_iter, seed=args.seed * 10_000 + it)
        records = trainer.rollout(tasks)
        metrics = trainer.update(records)
        print(f"iter {it+1:4d}  reward {metrics['mean_reward']:.3f}  "
              f"loss {metrics['loss']:+.4f}  kl {metrics['approx_kl']:+.4f}  "
              f"({time.time()-t0:5.1f}s)", flush=True)
        if args.checkpoint_dir and (it + 1) % args.checkpoint_every == 0:
            path = f"{args.checkpoint_dir}/step{it+1}"
            ckpt.save(path, trainer.params, step=it + 1)
            print(f"  checkpoint -> {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
