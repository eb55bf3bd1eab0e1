"""The admission mix on the CPU, past the look for a chip: a sound run is
correct, and ``correct`` comes out false for each fault admission can have on
one chip, with the timed path broken underneath."""

import pytest

from bench import admit as A
from bench.tests.tiny import CPU, tiny_cell

SEED = 2 ** 32 + 9


def _run(seed=SEED):
    return A.run_cell(tiny_cell("grpo_admit"), seed, 0.3, False, CPU,
                      log=lambda m: None)


def test_run_is_well_formed_and_correct():
    res = _run()
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 6
    assert set(res["metrics"]) == {"admit_tokens_per_s", "setup_s"}
    assert 0 < res["checks"]["kv_err"]["value"] <= res["checks"]["kv_err"]["limit"]
    for name in ("lanes_missing", "pos_wrong", "leaked_blocks"):
        assert res["checks"][name]["value"] == 0


def test_every_seed_admits_the_same_work():
    from bench.traffic import generator as G

    tr = tiny_cell("grpo_admit").traffic
    sizes = [[len(t) for _, t in G.admission_batch(tr, s, i, 512)]
             for s, i in ((1, 0), (2 ** 40 + 3, 5))]
    assert sizes[0] == sizes[1]
    a, b = (G.admission_batch(tr, s, 0, 512) for s in (1, 2))
    assert a != b


def test_half_the_batch_left_out_is_not_correct(monkeypatch):
    from repro.engine.worker import RolloutWorker

    prefill = RolloutWorker.prefill

    def half(self, seq_id, tokens):
        if seq_id % 2 == 0:
            prefill(self, seq_id, tokens)

    monkeypatch.setattr(RolloutWorker, "prefill", half)
    res = _run()
    assert not res["correct"] and res["checks"]["lanes_missing"]["value"] > 0


def test_altered_token_is_not_correct(monkeypatch):
    from repro.engine.worker import RolloutWorker

    prefill = RolloutWorker.prefill

    def altered(self, seq_id, tokens):
        tokens = list(tokens)
        tokens[len(tokens) // 2] = (tokens[len(tokens) // 2] + 1) % self.cfg.vocab
        prefill(self, seq_id, tokens)

    monkeypatch.setattr(RolloutWorker, "prefill", altered)
    res = _run()
    assert not res["correct"]
    assert res["checks"]["kv_err"]["value"] > res["checks"]["kv_err"]["limit"]


@pytest.mark.parametrize("keep", ["pool", "position"])
def test_chunk_that_keeps_its_state_is_not_correct(monkeypatch, keep):
    """The chunk program returns the pool unchanged, or writes the keys and
    values but leaves the lane's position where it was."""
    from repro.engine import worker as W

    chunk = W._paged_chunk

    def stale(cfg, params, pool, slot, tokens, length, mesh=None):
        if keep == "pool":
            return pool
        pos = pool["pos"].copy()              # the chunk program donates the pool
        return dict(chunk(cfg, params, pool, slot, tokens, length, mesh=mesh), pos=pos)

    monkeypatch.setattr(W, "_paged_chunk", stale)
    res = _run()
    assert not res["correct"]
    assert res["checks"]["pos_wrong"]["value"] > 0
