"""The worker's host spans, the chunk program's named scopes, and the benchmark
readers that turn both into per-layer metrics."""

import re
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.engine import worker as W
from repro.engine.spans import SpanTotals
from repro.models import model as M

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

CHILDREN = ("radix_match", "map_pages", "chunk_dispatch", "seq_key", "radix_insert")


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen3_1_7b").reduced(n_periods=2)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _grpo_worker(setup, chunk=8):
    """Two prompts x three siblings, admitted in group order as the backend
    admits them: the first sibling of each group prefills in chunks, the
    others find the whole prompt in the radix cache."""
    cfg, params = setup
    w = W.RolloutWorker(cfg, params, capacity=64, max_slots=8, page_size=16,
                        chunk_size=chunk)
    prompts = [list(range(3, 43)), list(range(7, 28))]
    sid = 0
    for p in prompts:
        for _ in range(3):
            w.prefill(sid, p)
            sid += 1
    return w, prompts


def test_span_totals_count_and_time():
    t = SpanTotals()
    with t.span("outer", seq_id=3) as outer:
        with t.span("inner", seq_id=3):
            pass
        with t.span("inner"):
            pass
    assert t.n == {"outer": 1, "inner": 2}
    assert outer.ns == t.ns["outer"] >= t.ns["inner"] >= 0
    assert t.stats() == {"span_outer_ns": t.ns["outer"], "span_outer_n": 1,
                         "span_inner_ns": t.ns["inner"], "span_inner_n": 2}


def test_admission_spans_nest_under_one_prefill_per_lane(setup):
    w, prompts = _grpo_worker(setup)
    s = w.dispatch_stats()
    lanes = 3 * len(prompts)
    assert s["span_prefill_n"] == lanes
    for name in ("radix_match", "map_pages", "seq_key", "radix_insert"):
        assert s[f"span_{name}_n"] == lanes
    # chunks ran for the first sibling of each group only; the rest reused it all
    assert s["span_chunk_dispatch_n"] == len(prompts)
    assert s["prefill_dispatches"] == sum(-(-len(p) // 8) for p in prompts)
    assert s["reused_tokens"] == 2 * sum(len(p) for p in prompts)
    assert sum(s[f"span_{c}_ns"] for c in CHILDREN) <= s["span_prefill_ns"]
    assert all(isinstance(v, int) for k, v in s.items() if k.startswith("span_"))


def test_no_chunk_dispatch_span_where_no_chunk_ran(setup):
    cfg, params = setup
    w = W.RolloutWorker(cfg, params, capacity=64, max_slots=4, page_size=16)
    w.prefill(0, [5, 6, 7])
    w.prefill(1, [5, 6, 7])                        # whole prompt from the cache
    s = w.dispatch_stats()
    assert s["span_prefill_n"] == 2 and s["span_chunk_dispatch_n"] == 1


def test_decode_span_feeds_the_calibration_timers(setup):
    cfg, params = setup
    w = W.RolloutWorker(cfg, params, capacity=64, max_slots=4, page_size=16)
    w.prefill(0, [5, 6, 7, 8])
    w.decode([0], 3)                               # may compile: not timed
    before = w.dispatch_stats()
    w.decode([0], 3)                               # warm
    s = w.dispatch_stats()
    assert s["span_decode_n"] == 2 and s["decode_calls"] == 2
    warm_s = s["decode_wall_s"] - before["decode_wall_s"]
    assert 0 < warm_s <= (s["span_decode_ns"] - before["span_decode_ns"]) * 1e-9
    assert s["decode_timed_steps"] - before["decode_timed_steps"] == 3
    assert s["decode_timed_lane_steps"] - before["decode_timed_lane_steps"] == 3


def test_chunk_program_carries_its_scopes(setup):
    cfg, params = setup
    pool = M.init_paged_pool(cfg, None, 2, 9, 16, 4)
    text = W._paged_chunk.lower(cfg, params, pool, jnp.asarray(0, jnp.int32),
                                jnp.zeros((1, 8), jnp.int32),
                                jnp.asarray(8, jnp.int32)).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    assert any(n.startswith("jit(_paged_chunk)/prefill_chunk/") for n in names)
    for scope in ("norm", "qkv", "attn", "mlp", "kv_write"):
        assert any(re.search(rf"/prefill_chunk/.*/{scope}/", n) for n in names), scope


# ---------------------------------------------------------------- the readers

P = "jit(_paged_chunk)/prefill_chunk/while/body/closed_call"
HLO = f"""
ENTRY %main {{
  %fusion.1 = bf16[2] fusion(%p), metadata={{op_name="{P}/qkv/dot_general"}}
  %fusion.2 = bf16[2] fusion(%p), metadata={{op_name="{P}/attn/exp;attn/reduce_max"}}
  %fusion.3 = bf16[2] fusion(%p), metadata={{op_name="{P}/mlp/dot_general"}}
  %fusion.4 = bf16[2] fusion(%p), metadata={{op_name="{P}/norm/mul"}}
  %scatter.5 = bf16[2] scatter(%p), metadata={{op_name="{P}/kv_write/scatter"}}
  %dus.6 = bf16[2] fusion(%p), metadata={{op_name="jit(_paged_chunk)/prefill_chunk/while/body/dynamic_update_slice"}}
  ROOT %copy.7 = bf16[2] copy(%p)
}}
"""


def test_scope_of_each_instruction_of_a_compiled_program():
    from bench.tools import scopes as S

    assert S.op_scopes(HLO) == {
        "%fusion.1": "qkv", "%fusion.2": "attn", "%fusion.3": "mlp",
        "%fusion.4": "norm", "%scatter.5": "kv_write", "%dus.6": "prefill_chunk",
        "%copy.7": "unnamed"}
    assert S.scope_of("jit(_prefill_chunk)/while/body/add") == "other"


def test_scopes_tool_puts_device_time_in_programs_and_scopes():
    from bench import trace as T
    from bench.tools import scopes as S

    ops = [("%fusion.1", 100, 300, ""), ("%fusion.2", 400, 100, ""),
           ("%fusion.3", 500, 40, ""), ("%fusion.4", 540, 60, ""),
           ("%scatter.5", 600, 100, ""), ("%copy.7", 700, 200, ""),
           ("%fusion.1", 950, 20, "")]                  # another program's fusion.1
    modules = [("jit__paged_chunk(123)", 100, 800), ("jit__paged_lane(9)", 950, 20)]
    spans = [("prefill", 0, 1200), ("radix_insert", 920, 25), (T.WINDOW, 100, 1000)]
    r = S.reduce(ops, modules, spans, 100, 1100, S.op_scopes(HLO), 4)
    assert S.programs_of(ops, modules)[-2:] == ["jit__paged_chunk", "jit__paged_lane"]
    assert r["chunk_device_ms"] == pytest.approx(800e-6 / 4)
    assert r["chunk_compute_share"] == pytest.approx(100 * 500 / 800)
    assert r["chunk_scope_s"]["unnamed"] == pytest.approx(200e-9)
    assert r["program_s"]["jit__paged_lane"] == pytest.approx(20e-9)
    assert r["busy_s"] == pytest.approx(820e-9)
    assert r["top_ops"][0] == ["%fusion.1", pytest.approx(300e-9), "qkv"]
    # the gaps (900, 950) and (970, 1100), each named whole by the innermost
    # span at its midpoint
    assert r["top_gaps"] == [["prefill", pytest.approx(130e-9)],
                             ["radix_insert", pytest.approx(50e-9)]]
    assert r["idle_by_span_s"]["radix_insert"] == pytest.approx(50e-9)
    assert S.reduce(ops[-1:], modules, spans, 100, 1100, {}, 0)["chunk_device_ms"] is None


def _measures(ops, chunks=4, drained=None, n_batches=1):
    trace = {"t0": 100, "t1": 1000, "ops": [ops]}
    batches = [{"counters": [{"prefill_dispatches": chunks}],
                "drained": (drained or [[{}]] * n_batches)[i]}
               for i in range(n_batches)]
    return NS(trace=trace, batches=batches)


def test_admit_host_ms_per_lane_reads_spans_after_the_first_batch():
    from bench.metrics import admit_host_ms_per_lane as R

    def totals(n, prefill_ms, chunk_ms, key_ms):
        return [{"span_prefill_n": n, "span_prefill_ns": int(prefill_ms * 1e6),
                 "span_chunk_dispatch_n": n // 4,
                 "span_chunk_dispatch_ns": int(chunk_ms * 1e6),
                 "span_seq_key_n": n, "span_seq_key_ns": int(key_ms * 1e6)}]

    drained = [totals(16, 400.0, 200.0, 150.0), totals(32, 800.0, 400.0, 250.0),
               totals(48, 1200.0, 600.0, 350.0)]
    # batches 2 and 3: 800 ms in prefill less 400 ms of chunk loop and 200 ms
    # in seq_key, over 32 lanes
    assert R.read(_measures([], drained=drained, n_batches=3)) == pytest.approx(200 / 32)
    assert R.read(_measures([], drained=drained[:1], n_batches=1)) is None
    parent = [[{"prefilled_tokens": 5}], [{"prefilled_tokens": 9}]]
    assert R.read(_measures([], drained=parent, n_batches=2)) is None


def test_gap_is_named_by_the_innermost_program_span():
    from bench import trace as T

    spans = [("prefill", 0, 100), ("prefill", 10, 80), ("seq_key", 60, 20),
             ("chunk_dispatch", 20, 30), (T.WINDOW, 0, 1000)]
    assert T.label((65, 75), spans) == "seq_key"
    assert T.label((30, 40), spans) == "chunk_dispatch"
    assert T.label((85, 88), spans) == "prefill"
    assert T.label((200, 300), spans) == "control"
