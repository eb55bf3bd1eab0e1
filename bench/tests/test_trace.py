"""The trace-to-metrics reduction on a small recorded trace."""

from types import SimpleNamespace as NS

import pytest

from bench import trace as T


def _ev(name, start, dur, stats=()):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=list(stats))


def _profile():
    """Two chips' ops and the host's spans, as ProfileData lays them out."""
    ops0 = [_ev("%while.1 = (s32[]) while(...)", 100, 120),
            _ev("%fusion.1 = bf16[2] fusion(...)", 100, 50),
            _ev("%paged_decode_attention_pallas.3 = bf16[2] custom-call(...)", 160, 40),
            _ev("%fusion.1 = bf16[2] fusion(...)", 180, 40),
            _ev("%copy.2 = bf16[2] copy(...)", 400, 100)]
    ops1 = [_ev("%fusion.1 = bf16[2] fusion(...)", 100, 300)]
    host = [_ev(T.WINDOW, 100, 500), _ev("decode", 90, 200), _ev("admit", 300, 90),
            _ev("other", 0, 10)]
    tpu = lambda evs: [NS(name="XLA Modules", events=[]),  # noqa: E731
                       NS(name=T.OPS_LINE, events=evs)]
    return NS(planes=[NS(name="/device:TPU:0", lines=tpu(ops0)),
                      NS(name="/device:TPU:1", lines=tpu(ops1)),
                      NS(name="/host:CPU", lines=[NS(name="python", events=host)])])


def test_merge_busy_and_gaps():
    ops = [("a", 100, 50), ("b", 160, 40), ("a", 180, 40), ("c", 400, 100)]
    assert T.merge([(5, 9), (1, 3), (2, 4)]) == [(1, 4), (5, 9)]
    assert T.busy_ns(ops, 100, 600) == 50 + 60 + 100
    assert T.gaps(ops, 100, 600) == [(150, 160), (220, 400), (500, 600)]
    assert T.busy_ns(ops, 120, 170) == 30 + 10


def test_top_ops_and_kernel_time():
    ops = [("a", 0, 5, ""), ("b", 10, 7, "x"), ("a", 20, 5, ""),
           ("c", 40, 1, "paged_decode_attn")]
    assert T.top_ops(ops, 2) == [["a", pytest.approx(10e-9)], ["b", pytest.approx(7e-9)]]
    assert T.kernel_ns(ops, "paged_decode_attn", 0, 100) == 1
    assert T.kernel_ns(ops, "^b$", 0, 100) == 7
    assert T.kernel_ns(ops, "^b$", 11, 100) == 0


def test_gap_is_named_by_innermost_host_span():
    spans = [("decode", 0, 100), ("advance", 0, 300), (T.WINDOW, 0, 1000)]
    assert T.label((10, 20), spans) == "decode"
    assert T.label((150, 200), spans) == "advance"
    assert T.label((500, 600), spans) == "control"


def test_reduce_recorded_trace():
    r = T.reduce(_profile(), 2, ["decode", "admit"])
    assert (r["t0"], r["t1"]) == (100, 600)
    assert r["window_s"] == pytest.approx(500e-9)
    # chip 0 busy 210 ns, chip 1 300 ns: the mean over the chips used
    assert r["busy_s"] == pytest.approx(255e-9)
    assert r["device_ops"][0] == ["%fusion.1", pytest.approx(390e-9)]
    assert r["device_ops"][1] == ["%copy.2", pytest.approx(100e-9)]
    assert "%while.1" not in [o[0] for o in r["device_ops"]]
    assert r["idle_gaps"][0] == ["admit", pytest.approx(180e-9)]
    assert [g[0] for g in r["idle_gaps"]] == ["admit", "control", "decode"]
    assert T.kernel_ns(r["ops"][0], "^%paged_decode_attention_pallas", 100, 600) == 40


def test_leaves_drop_the_ops_that_hold_others():
    ops = [("loop", 0, 100), ("a", 0, 10), ("inner", 20, 50), ("b", 20, 5),
           ("c", 30, 10), ("d", 200, 5)]
    assert [o[0] for o in T.leaves(ops)] == ["a", "b", "c", "d"]


def test_reduce_refuses_a_trace_without_the_chip():
    p = _profile()
    p.planes = p.planes[2:]
    with pytest.raises(RuntimeError, match="TPU:0"):
        T.reduce(p, 1, ["decode"])
