"""End-to-end behaviour tests: full agentic RL iteration on the real engine, the
orchestration stack against the simulator, and the sharding/dry-run contract."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.configs import get_config
from repro.rl import data as D
from repro.rl.loop import HeddleTrainer, TrainerConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_end_to_end_rollout_and_grpo_update():
    """Rollout on real workers (tool calls in the loop) -> GRPO update, twice."""
    cfg = get_config("smollm_135m").reduced(n_periods=2)
    tr = HeddleTrainer(cfg, TrainerConfig(group_size=4, n_workers=2, seed=0))
    history = tr.train(2, tasks_per_iter=2)
    assert len(history) == 2
    for h in history:
        assert np.isfinite(h["loss"])
        assert 0.0 <= h["mean_reward"] <= 1.0
    assert tr.step_count == 2


def test_rollout_records_are_well_formed():
    cfg = get_config("smollm_135m").reduced(n_periods=1)
    tr = HeddleTrainer(cfg, TrainerConfig(group_size=2, n_workers=2, seed=1))
    tasks = D.sample_tasks(2, seed=5)
    records = tr.rollout(tasks)
    assert len(records) == 4                     # 2 tasks x group 2
    for r in records:
        assert r.prompt_len == 4
        assert len(r.tokens) > r.prompt_len      # something was generated
        assert r.reward in (0.0, 0.25, 1.0)


def test_grpo_update_with_reward_spread_moves_policy():
    """With shaped rewards, the advantage machinery produces nonzero updates when
    any group has reward spread (sanity of the learning loop, not convergence)."""
    from repro.rl.loop import RolloutRecord
    cfg = get_config("smollm_135m").reduced(n_periods=1)
    tr = HeddleTrainer(cfg, TrainerConfig(group_size=4, n_workers=1, seed=0))
    task = D.sample_tasks(1, seed=0)[0]
    recs = [
        RolloutRecord(task.prompt_tokens() + [D.TOOL_CALL, 20, D.EOS], 4, 1.0, 1),
        RolloutRecord(task.prompt_tokens() + [7, 8, D.EOS], 4, 0.0, 1),
        RolloutRecord(task.prompt_tokens() + [D.TOOL_CALL, D.EOS], 4, 0.25, 1),
        RolloutRecord(task.prompt_tokens() + [11, D.EOS], 4, 0.0, 1),
    ]
    m = tr.update(recs)
    assert abs(m["pg_loss"]) > 1e-8


@pytest.mark.slow
def test_dryrun_single_combo_subprocess():
    """The multi-pod dry-run contract: lower+compile one (arch, shape) on the 16x16
    production mesh with 512 host devices (subprocess: device count is locked at
    first jax init, so it cannot run in-process)."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "smollm-135m",
         "--shape", "decode_32k", "--out", "/tmp/dryrun_test.json"],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, PYTHONPATH=os.path.join(REPO, "src")), cwd=REPO)
    assert out.returncode == 0, out.stdout + out.stderr
    with open("/tmp/dryrun_test.json") as f:
        rec = json.load(f)[0]
    assert rec["status"] == "ok"
    assert rec["chips"] == 256
    assert rec["hlo_flops"] > 0
    assert rec["collective_total_bytes"] >= 0


@pytest.mark.parametrize("env", ["", "/shared/jax-cache"])
def test_compile_cache_dir_is_the_env_or_the_checkout(monkeypatch, env):
    """``JAX_COMPILATION_CACHE_DIR`` is left to JAX; otherwise the cache sits at a
    fixed ``<checkout>/.jax_cache``.  Either way lowered programs carry no source
    locations, so their cache keys do not depend on the checkout's path."""
    from repro.launch import compile_cache
    updates = {}
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda name, value: updates.__setitem__(name, value))
    if env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = env or os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert updates.get("jax_compilation_cache_dir") == (None if env else want)
    assert updates["jax_traceback_in_locations_limit"] == 0


def test_roofline_reader_on_committed_dryrun_artifacts():
    path = os.path.join(REPO, "dryrun_16x16.json")
    if not os.path.exists(path):
        pytest.skip("dry-run artifact not generated yet")
    sys.path.insert(0, REPO)
    from benchmarks.roofline import roofline_row
    with open(path) as f:
        records = json.load(f)
    rows = [r for r in (roofline_row(rec) for rec in records) if r]
    assert len(rows) >= 39
    for r in rows:
        assert r["dominant"] in ("compute", "memory", "collective")
        assert r["t_compute_s"] > 0
