"""Second witness of the worker's context fault, on the CPU: the program's own
full forward (``models.model.forward_full``) sides with the plain reference.

A ``RolloutWorker`` prefills a prompt, decodes a turn greedily, absorbs a tool
output and decodes a second turn.  Each served token is compared with the greedy
choice of ``forward_full`` over (a) the natural sequence the trajectory reports
(prompt, turn 1, tool output, turn 2) and (b) the sequence the worker's cache
holds: the prompt's last token twice, and turn 1 without its last token.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 -m bench.tools.witness [--seeds 5]
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def witness(seed: int, turn: int = 6) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.engine.sampler import SamplerConfig
    from repro.engine.worker import RolloutWorker
    from repro.models import model as M

    cfg = get_config("qwen3-1.7b").reduced(n_periods=2)
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    params = jax.tree.map(lambda x: x * 10 if x.ndim >= 2 else x, params)
    rng = np.random.default_rng(seed)
    prompt = [int(x) for x in rng.integers(5, cfg.vocab, 20)]
    tool = [int(x) for x in rng.integers(5, cfg.vocab, 5)]
    w = RolloutWorker(cfg, params, capacity=64, sampler=SamplerConfig(temperature=0.0))
    w.prefill(1, prompt)
    t1 = w.decode([1], turn)[1]
    w.extend(1, tool)
    t2 = w.decode([1], turn)[1]

    def greedy_next(seq):
        lg = M.forward_full(cfg, params, {"tokens": jnp.asarray(seq)[None]})[0]
        return int(jnp.argmax(lg[0, -1]))

    natural = prompt + t1 + tool + t2
    held = prompt + [prompt[-1]] + t1[:-1] + tool + [tool[-1]] + t2
    served = list(range(len(prompt), len(prompt) + turn)) + list(
        range(len(prompt) + turn + len(tool), len(natural)))
    # the held sequence is one token longer before every served token: the
    # prompt's last token twice, turn 1's last token missing, the tool's twice
    nat = sum(greedy_next(natural[:i]) == natural[i] for i in served)
    dup = sum(greedy_next(held[:i + 1]) == natural[i] for i in served)
    return {"seed": seed, "served": len(served), "agree_natural": nat,
            "agree_worker_cache": dup}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--seeds", type=int, default=5)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    for s in range(args.seeds):
        print(json.dumps(witness(s)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
