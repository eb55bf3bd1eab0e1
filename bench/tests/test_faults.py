"""The rest of a run, on the CPU and past the look for a chip, with the timed
path broken underneath: ``correct`` comes out false for each fault a served
cell can have on one chip."""

import pytest

from bench import run as R
from bench.tests.tiny import CPU, tiny_cell


def _run(cell, seed=2 ** 32 + 3):
    return R.run_cell(cell, seed, 1.0, False, CPU, log=lambda m: None)


@pytest.fixture
def loud_weights(monkeypatch):
    """Weights ten times the usual scale, so that a wrong context moves the
    toy model's logits well past the toy limit."""
    import bench.weights as Wt

    monkeypatch.setattr(Wt, "STD", 0.2)


def test_run_is_well_formed():
    res = _run(tiny_cell())
    assert res["attempted"] >= 4 and res["failed"] == 0
    for name in ("unfinished", "wrong_steps", "leaked_blocks"):
        assert res["checks"][name]["value"] == 0
    assert res["checks"]["logit_gap"]["value"] >= 0
    assert set(res["metrics"]) == {"rollout_tokens_per_s", "turn_latency_p95_s",
                                   "setup_s"}


def test_altered_token_is_not_correct(monkeypatch, loud_weights):
    from repro.engine.worker import RolloutWorker

    decode = RolloutWorker.decode

    def altered(self, seq_ids, n_tokens, stop_token=None):
        out = decode(self, seq_ids, n_tokens, stop_token)
        for toks in out.values():
            if toks:
                toks[0] = (toks[0] + 1) % self.cfg.vocab
                break
        return out

    monkeypatch.setattr(RolloutWorker, "decode", altered)
    res = _run(tiny_cell(rope_theta=500000))
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > res["checks"]["logit_gap"]["limit"]


def test_decode_that_keeps_its_state_is_not_correct(monkeypatch, loud_weights):
    from repro.models import model as M

    step = M.decode_step

    def stale(cfg, params, cache, tokens, active=None):
        logits, _ = step(cfg, params, cache, tokens, active)
        pos = cache["pos"] + (1 if active is None else active.astype(cache["pos"].dtype))
        return logits, dict(cache, pos=pos)       # KV never written

    monkeypatch.setattr(M, "decode_step", stale)
    res = _run(tiny_cell(rope_theta=250000))
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > res["checks"]["logit_gap"]["limit"]


def test_half_the_batch_left_out_is_not_correct(monkeypatch):
    from repro.engine import runtime as RT

    init = RT.RolloutRuntime.__init__

    def half(self, workers, controller, trajectories, *a, **k):
        init(self, workers, controller, trajectories[: len(trajectories) // 2], *a, **k)

    monkeypatch.setattr(RT.RolloutRuntime, "__init__", half)
    res = _run(tiny_cell())
    assert not res["correct"]
    assert res["checks"]["unfinished"]["value"] > 0
