"""The general traffic generator: one rollout batch from a traffic file.

A traffic file (``bench/traffic/<name>.json``) holds only parameters; this module
turns them into the program's inputs.  It is a copy of the program's own
generator (``engine/workload.generate`` and ``replay_finished``, and
``engine/runtime.miniaturize``, ``synth_prompts`` and ``required_capacity``),
kept here so that a change to the program's generator does not move the
yardstick.  The tool profiles of ``engine/tools.py`` are copied as numbers.

Every batch of one traffic mix is the same work.  The plans (generated tokens
per step, tool outputs, tool latencies and failures) and their order come from
the traffic's own ``plan_seed``; the run's ``--seed`` and the batch index draw
the token ids of the prompts (and, through the runtime's seed, of the tool
outputs and the sampling keys).  The scheduler's decisions depend on the plans
alone, so two seeds make the same decode calls with other tokens, and runs of
one cell differ by the chip and the host, not by how much work they are given.
"""

from __future__ import annotations

import hashlib
import json
from pathlib import Path

import numpy as np

from repro.core.trajectory import StepRecord, Trajectory
from repro.engine.workload import TrajectoryPlan as Plan

TRAFFIC_DIR = Path(__file__).resolve().parent

# engine/tools.py TOOL_PROFILES: (mean latency s, cv, fail rate, output tokens mean)
TOOL_PROFILES = {
    "coding": (0.46, 0.8, 0.35, 160),
    "search": (1.42, 0.5, 0.10, 256),
    "math": (0.051, 0.4, 0.20, 48),
}
# engine/workload.py _TASK_SHAPE: (steps multiplier, step-token multiplier, spread)
TASK_SHAPE = {
    "coding": (2.5, 1.2, 1.0),
    "search": (3.0, 0.4, 0.6),
    "math": (1.8, 0.8, 0.8),
}
TOKEN_IDS = (5, 105)          # prompt and tool-output ids, as synth_prompts draws them


def load(name: str) -> dict:
    """The parameters of traffic mix ``name``."""
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def sub_seed(*parts) -> int:
    """A 63-bit seed for numpy from any whole numbers (``--seed`` may exceed 32 bits)."""
    digest = hashlib.sha256(repr(tuple(int(p) for p in parts)).encode()).digest()
    return int.from_bytes(digest[:8], "little") >> 1


def _sample_latency(profile, rng) -> float:
    mean, cv = profile[0], profile[1]
    sigma = np.sqrt(np.log(1 + cv ** 2))
    mu = np.log(mean) - sigma ** 2 / 2
    return float(rng.lognormal(mu, sigma))


def _sample_output_tokens(profile, rng, failed: bool) -> int:
    base = profile[3] * (2.0 if failed else 1.0)
    return int(max(1, rng.normal(base, base * 0.3)))


def generate(task: str, n_prompts: int, group_size: int, seed: int, *,
             base_steps: float = 3.0, mean_step_tokens: float = 420.0,
             difficulty_sigma: float = 0.55, max_output_tokens: int = 40_000
             ) -> list[Trajectory]:
    """n_prompts x group_size trajectories with paper-scale plans (workload.generate)."""
    rng = np.random.default_rng(seed)
    steps_mult, tok_mult, spread = TASK_SHAPE[task]
    tool = TOOL_PROFILES[task]
    out: list[Trajectory] = []
    for pid in range(n_prompts):
        difficulty = rng.lognormal(0.0, difficulty_sigma)
        prompt_tokens = int(np.clip(rng.normal(120 + 60 * difficulty, 40), 16, 2048))
        group = [Trajectory(prompt_id=pid, sample_id=s, prompt_tokens=prompt_tokens,
                            context_tokens=prompt_tokens, traj_id=len(out) + s)
                 for s in range(group_size)]
        for traj in group:
            sample_luck = rng.lognormal(0.0, 0.6 * spread)
            hardness = difficulty * sample_luck
            gen, lat, fail, touts = [], [], [], []
            fail_p = min(0.85, tool[2] * (0.4 + 0.6 * hardness))
            base_n = base_steps + steps_mult * hardness
            n_steps = int(np.clip(round(rng.lognormal(np.log(base_n), 0.22)), 1, 64))
            total, s = 0, 0
            while True:
                scale = (0.5 + 0.7 * hardness) if s == 0 else (0.9 + 0.1 * hardness)
                g = int(np.clip(rng.lognormal(
                    np.log(mean_step_tokens * tok_mult * scale), 0.35), 8, 8192))
                g = min(g, max(max_output_tokens - total, 8))
                total += g
                failed = bool(rng.random() < fail_p)
                if failed:
                    n_steps = min(n_steps + 1, 64)
                gen.append(g)
                lat.append(_sample_latency(tool, rng))
                touts.append(int(_sample_output_tokens(tool, rng, failed)
                                 * (0.7 + 0.35 * hardness)))
                s += 1
                stop = total >= max_output_tokens or s >= n_steps
                fail.append(failed and not stop)
                if stop:
                    break
            traj.payload = Plan(gen, lat, fail, touts)
            traj.true_total_tokens = sum(gen)
            traj.true_num_steps = len(gen)
        out.extend(group)
    return out


def miniaturize(trajectories: list[Trajectory], *, max_steps: int | None,
                max_total_tokens: int, max_prompt: int, max_tool_tokens: int,
                min_step_tokens: int) -> list[Trajectory]:
    """Rescale paper-scale plans by one shared factor per quantity, so that the
    lognormal tail keeps its shape (runtime.miniaturize).  Mutates in place."""
    n_steps = {t.traj_id: (len(t.payload.gen_tokens) if max_steps is None
                           else min(len(t.payload.gen_tokens), max_steps))
               for t in trajectories}
    peak_total = max(sum(t.payload.gen_tokens[:n_steps[t.traj_id]])
                     for t in trajectories)
    peak_prompt = max(t.prompt_tokens for t in trajectories)
    peak_tool = max((o for t in trajectories
                     for o in t.payload.tool_output_tokens[:n_steps[t.traj_id]]),
                    default=1)
    g_scale = max_total_tokens / max(peak_total, 1)
    p_scale = max_prompt / max(peak_prompt, 1)
    o_scale = max_tool_tokens / max(peak_tool, 1)
    for t in trajectories:
        p = t.payload
        n = n_steps[t.traj_id]
        gen = [max(min_step_tokens, round(g * g_scale)) for g in p.gen_tokens[:n]]
        touts = [max(1, round(o * o_scale)) for o in p.tool_output_tokens[:n]]
        fail = list(p.tool_failed[:n])
        fail[-1] = False
        lat = [x * g_scale for x in p.tool_latency[:n]]
        t.payload = Plan(gen, lat, fail, touts)
        t.prompt_tokens = max(4, round(t.prompt_tokens * p_scale))
        t.context_tokens = t.prompt_tokens
        t.true_total_tokens = sum(gen)
        t.true_num_steps = n
    return trajectories


def replay_finished(trajectories: list[Trajectory]) -> list[Trajectory]:
    """Plans materialized as finished trajectories: the predictor's history."""
    out = []
    for t in trajectories:
        plan = t.payload
        ft = Trajectory(traj_id=t.traj_id, prompt_id=t.prompt_id,
                        sample_id=t.sample_id, prompt_tokens=t.prompt_tokens,
                        context_tokens=t.prompt_tokens)
        for s in range(plan.num_steps):
            ft.record_step(StepRecord(s, plan.gen_tokens[s], plan.tool_latency[s],
                                      tool_failed=plan.tool_failed[s],
                                      tool_output_tokens=plan.tool_output_tokens[s]))
            ft.record_tool_output(plan.tool_output_tokens[s])
        ft.true_total_tokens = t.true_total_tokens
        ft.true_num_steps = t.true_num_steps
        ft.finished = True
        out.append(ft)
    return out


def required_capacity(trajectories: list[Trajectory]) -> int:
    """Most positions a lane can hold: prompt + all generated + all tool tokens."""
    return max(t.prompt_tokens + t.payload.total_tokens
               + sum(t.payload.tool_output_tokens) for t in trajectories)


def capacity_bound(traffic: dict) -> int:
    """Lane capacity that covers every batch of the mix, rounded up to whole pages
    of 16: the longest prompt, the longest trajectory's tokens and the largest tool
    output on every step."""
    cap = (traffic["max_prompt"] + traffic["max_total_tokens"]
           + traffic["max_steps"] * traffic["max_tool_tokens"])
    return -(-cap // 16) * 16


def _plans(traffic: dict, seed: int) -> list[Trajectory]:
    return miniaturize(
        generate(traffic["task"], traffic["n_prompts"], traffic["group_size"], seed,
                 base_steps=traffic["base_steps"]),
        max_steps=traffic["max_steps"], max_total_tokens=traffic["max_total_tokens"],
        max_prompt=traffic["max_prompt"], max_tool_tokens=traffic["max_tool_tokens"],
        min_step_tokens=traffic["min_step_tokens"])


def history(traffic: dict) -> list[Trajectory]:
    """Finished trajectories the predictor is fitted on (a disjoint history at the
    same scale, as the program's ``build_workbench`` does)."""
    hist = _plans(dict(traffic, n_prompts=traffic["history_prompts"]),
                  traffic["plan_seed"] + 10_000)
    return replay_finished(hist)


def batch(traffic: dict, seed: int, index: int) -> tuple[list[Trajectory], dict[int, list[int]]]:
    """Batch ``index`` of a run: the traffic's plans, in their own order, and
    prompt token ids drawn from ``(seed, index)``.  GRPO siblings share their
    prompt.  Returns (trajectories, prompts by trajectory id)."""
    plans = _plans(traffic, traffic["plan_seed"])
    rng = np.random.default_rng(sub_seed(seed, index, 1))
    out: list[Trajectory] = []
    prompts: dict[int, list[int]] = {}
    for src in plans:
        t = Trajectory(prompt_id=src.prompt_id, sample_id=src.sample_id,
                       prompt_tokens=src.prompt_tokens,
                       context_tokens=src.prompt_tokens, traj_id=src.traj_id)
        t.payload = src.payload
        t.true_total_tokens = src.true_total_tokens
        t.true_num_steps = src.true_num_steps
        if src.sample_id == 0:
            ids = [int(x) for x in rng.integers(*TOKEN_IDS, src.prompt_tokens)]
        prompts[t.traj_id] = list(ids)
        out.append(t)
    return out, prompts


def prompt_lengths(traffic: dict) -> list[int]:
    """Prompt length of each GRPO group: the program generator's paper-scale
    prompts (``generate`` with the traffic's ``plan_seed``), scaled by one factor
    so that the longest is ``max_prompt``, as ``miniaturize`` scales them."""
    plans = generate(traffic["task"], traffic["n_prompts"], traffic["group_size"],
                     traffic["plan_seed"])
    lengths = [t.prompt_tokens for t in plans if t.sample_id == 0]
    scale = traffic["max_prompt"] / max(lengths)
    return [max(4, round(n * scale)) for n in lengths]


def admission_batch(traffic: dict, seed: int, index: int,
                    vocab: int) -> list[tuple[int, list[int]]]:
    """Batch ``index`` of an admission mix: (trajectory id, prompt) for every
    sibling of every group, in ``EngineBackend.admit``'s order (by group, then
    sibling).  Every batch has the same prompt lengths; ``(seed, index)`` draws
    the token ids, from the whole vocabulary, so that groups share no prefix."""
    rng = np.random.default_rng(sub_seed(seed, index, 1))
    g = traffic["group_size"]
    out = []
    for pid, n in enumerate(prompt_lengths(traffic)):
        ids = [int(x) for x in rng.integers(TOKEN_IDS[0], vocab, n)]
        out.extend((pid * g + s, list(ids)) for s in range(g))
    return out


def tool_tokens(env_seed: int, traj_id: int, step: int, n: int) -> list[int]:
    """Token ids of a tool output, as the program's ``ToolEnvironment`` draws them
    (an rng seeded by ``(seed, traj_id, step)``)."""
    rng = np.random.default_rng((env_seed, traj_id, step))
    return [int(x) for x in rng.integers(*TOKEN_IDS, n)]
