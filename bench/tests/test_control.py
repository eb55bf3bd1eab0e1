"""The control at a size a test run can hold: the reference with int8 weights in
the program's place, judged as a run judges the served tokens (``run.checks``
and ``run.passed`` against the cell's limits), is not correct, where the same
reference in bf16 is."""

from bench.tests.tiny import CPU, tiny_cell
from bench.tools import control


def test_int8_control_is_not_correct(monkeypatch):
    import bench.weights as Wt

    monkeypatch.setattr(Wt, "STD", 0.2)
    rows = control.readings(tiny_cell(rope_theta=125000), [11, 2 ** 31 + 12, 13], CPU,
                            log=lambda m: None)
    assert len(rows) == 3
    for r in rows:
        assert r["n"] > 0
        assert all(v == 0 for v in r["data_plane"].values())
        assert r["bf16_correct"] and not r["int8_correct"]
        assert r["int8"]["logit_gap"] > r["bf16"]["logit_gap"]


def test_admission_controls_are_not_correct():
    """int8 and fp8 weights in the program's place fail the admission cell's
    limits, where the bf16 reference and the program pass."""
    rows = control.admit_readings(tiny_cell("grpo_admit"), [21, 2 ** 31 + 22, 23],
                                  batches=2, log=lambda m: None)
    for r in rows:
        assert r["n"] == 4 and all(v == 0 for v in r["guarantees"].values())
        assert r["program_correct"] and r["bf16_correct"]
        assert not r["int8_correct"] and not r["fp8_correct"]
        assert r["fp8"]["kv_err"] > r["int8"]["kv_err"] > r["program"]["kv_err"]
