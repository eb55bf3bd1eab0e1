"""Slot-pool engine invariants: token-exact parity with the legacy concat/slice
worker, preemption self-healing, migration round-trips, pool growth, and the
chunked/prefix-reuse prefill plane (fixed-shape admission, radix KV implants)."""

import functools

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # optional dep: deterministic fallback
    from _hypothesis_fallback import given, settings, st

from repro.configs import get_config
from repro.engine.legacy import LegacyRolloutWorker
from repro.engine.sampler import SamplerConfig
from repro.engine.worker import RolloutWorker
from repro.models import model as M

KEY = jax.random.PRNGKey(0)


@functools.lru_cache(maxsize=1)
def _setup():
    cfg = get_config("qwen3_1_7b").reduced(n_periods=1)
    params = M.init_params(cfg, KEY)
    return cfg, params


@pytest.fixture(scope="module")
def setup():
    return _setup()


@pytest.mark.parametrize("temperature", [0.0, 1.0])
def test_parity_interleaved_lifecycle(setup, temperature):
    """The slot-pool engine reproduces the legacy engine's tokens exactly through an
    interleaved admit / decode / extend / finish schedule (same seed, same prompts).

    This is the contract that lets the pool replace the per-sequence store: each
    lane's math (and, at temperature > 0, its per-sequence RNG stream) is independent
    of what else is resident.
    """
    cfg, params = setup
    sampler = SamplerConfig(temperature=temperature, top_p=0.9)
    pool = RolloutWorker(cfg, params, capacity=64, max_slots=4, sampler=sampler)
    legacy = LegacyRolloutWorker(cfg, params, capacity=64, sampler=sampler)

    for w in (pool, legacy):
        w.prefill(1, [5, 7, 9, 11])
        w.prefill(2, [5, 7, 9])
    assert pool.decode([1, 2], 4) == legacy.decode([1, 2], 4)

    for w in (pool, legacy):                      # admission mid-flight
        w.prefill(3, [2, 4, 6, 8, 10])
    assert pool.decode([1, 2, 3], 3) == legacy.decode([1, 2, 3], 3)

    for w in (pool, legacy):                      # tool absorption, one lane only
        w.extend(2, [101, 102, 103])
    assert pool.decode([2, 3], 3) == legacy.decode([2, 3], 3)

    for w in (pool, legacy):                      # finish one, keep decoding the rest
        w.release(1)
    assert pool.decode([2], 2) == legacy.decode([2], 2)
    assert pool.store[2].tokens == legacy.store[2].tokens


def test_preempt_then_resume_self_heals(setup):
    """A preempted lane rides along masked-out while others decode, then resumes with
    exactly the tokens it would have produced had nothing else run (frozen pos +
    self-healing KV writes)."""
    cfg, params = setup
    sampler = SamplerConfig(temperature=1.0, top_p=0.9)
    w = RolloutWorker(cfg, params, capacity=64, max_slots=4, sampler=sampler)
    ref = RolloutWorker(cfg, params, capacity=64, max_slots=4, sampler=sampler)
    for e in (w, ref):
        e.prefill(1, [5, 7, 9, 11])
        e.prefill(2, [3, 5, 8])
    assert w.decode([1, 2], 3) == ref.decode([1, 2], 3)
    w.preempt(1)
    w.decode([2], 5)                              # lane 1 is masked but co-resident
    out = w.decode([1], 4)                        # implicit resume (mask flip back)
    want = ref.decode([1], 4)                     # reference never preempted
    assert out == want


def test_migrate_round_trip_across_workers(setup):
    """migrate_out -> migrate_in -> back again: the trajectory's tokens are identical
    to an unmigrated run, and co-resident lanes on both workers are undisturbed."""
    cfg, params = setup
    sampler = SamplerConfig(temperature=1.0, top_p=0.9)
    w0 = RolloutWorker(cfg, params, capacity=64, max_slots=4, worker_id=0,
                       sampler=sampler)
    w1 = RolloutWorker(cfg, params, capacity=64, max_slots=4, worker_id=1,
                       sampler=sampler)
    ref = RolloutWorker(cfg, params, capacity=64, max_slots=4, worker_id=0,
                        sampler=sampler)
    for e in (w0, ref):
        e.prefill(1, [5, 7, 9, 11])               # the migrating trajectory
        e.prefill(2, [2, 4, 6])                   # co-resident on the source
    w1.prefill(3, [8, 8, 8])                      # co-resident on the destination
    bystander = w1.decode([3], 2)

    assert w0.decode([1, 2], 3) == ref.decode([1, 2], 3)
    pkg = w0.migrate_out(1)
    assert 1 not in w0.store
    w1.migrate_in(pkg)
    assert w1.decode([1], 4)[1] == ref.decode([1], 4)[1]

    pkg = w1.migrate_out(1)                       # and back again
    w0.migrate_in(pkg)
    assert w0.decode([1], 3)[1] == ref.decode([1], 3)[1]
    # bystanders on both workers keep decoding their own streams
    assert w0.decode([2], 2) == ref.decode([2], 2)
    assert len(w1.decode([3], 2)[3]) == 2 and len(bystander[3]) == 2


def test_chunked_parity_straddles_chunk_boundaries(setup):
    """Prompt lengths below / at / above multiples of the chunk size all admit
    through the one fixed-shape chunk kernel and reproduce legacy full-prefill
    tokens exactly, interleaved with tool absorption, preemption, and migration."""
    cfg, params = setup
    sampler = SamplerConfig(temperature=1.0, top_p=0.9)
    pool = RolloutWorker(cfg, params, capacity=64, max_slots=6, sampler=sampler,
                         chunk_size=4)
    legacy = LegacyRolloutWorker(cfg, params, capacity=64, sampler=sampler)
    assert pool._chunked
    prompts = {1: [5, 7, 9], 2: [5, 7, 9, 11], 3: [2, 4, 6, 8, 10],
               4: [1, 2, 3, 4, 5, 6, 7, 8], 5: [9, 8, 7, 6, 5, 4, 3, 2, 1]}
    for w in (pool, legacy):
        for sid, p in prompts.items():
            w.prefill(sid, p)
    ids = list(prompts)
    assert pool.decode(ids, 3) == legacy.decode(ids, 3)

    for w in (pool, legacy):                  # 5-token tool output straddles chunk 4
        w.extend(3, [101, 102, 103, 104, 105])
    assert pool.decode([3], 3) == legacy.decode([3], 3)

    pool.preempt(1)                           # masked lane rides along
    assert pool.decode([2], 2) == legacy.decode([2], 2)
    assert pool.decode([1], 2) == legacy.decode([1], 2)   # implicit resume

    dst = RolloutWorker(cfg, params, capacity=64, max_slots=2, sampler=sampler,
                        chunk_size=4)
    dst.migrate_in(pool.migrate_out(4))       # chunk-admitted lane migrates intact
    assert dst.decode([4], 3) == legacy.decode([4], 3)


def test_prefix_reuse_admission_parity_and_accounting(setup):
    """GRPO siblings and released-lane re-entries implant the shared prefix from
    the radix cache (O(suffix) prefill) with token-exact parity, and the engine
    reports the implanted token counts."""
    cfg, params = setup
    sampler = SamplerConfig(temperature=1.0, top_p=0.9)
    w = RolloutWorker(cfg, params, capacity=64, max_slots=4, sampler=sampler,
                      chunk_size=4)
    legacy = LegacyRolloutWorker(cfg, params, capacity=64, sampler=sampler)
    assert w._reuse
    P = [5, 7, 9, 11, 13]
    for e in (w, legacy):
        e.prefill(1, P)
    assert w.decode([1], 3) == legacy.decode([1], 3)

    for e in (w, legacy):                     # sibling: full-prompt implant
        e.prefill(2, P)
    assert w.reused_tokens >= len(P)
    assert w.decode([1, 2], 3) == legacy.decode([1, 2], 3)

    for e in (w, legacy):                     # released lane retires, stays reusable
        e.release(1)
    assert len(w.retired) == 1
    before = w.reused_tokens
    for e in (w, legacy):
        e.prefill(3, P + [40, 41, 42])
    assert w.reused_tokens >= before + len(P)
    assert w.decode([2, 3], 3) == legacy.decode([2, 3], 3)


def test_retired_lane_byte_budget_evicts_lru(setup):
    """The retired set honours its byte budget (LRU eviction) and an evicted
    lane's refs go stale — later admissions fall back to a full, correct prefill."""
    cfg, params = setup
    sampler = SamplerConfig(temperature=1.0, top_p=0.9)
    probe = RolloutWorker(cfg, params, capacity=64, max_slots=4, sampler=sampler)
    one_lane = probe._lane_bytes
    w = RolloutWorker(cfg, params, capacity=64, max_slots=4, sampler=sampler,
                      chunk_size=4, retired_kv_bytes=one_lane)   # budget: 1 lane
    legacy = LegacyRolloutWorker(cfg, params, capacity=64, sampler=sampler)
    A, B = [5, 7, 9, 11], [2, 4, 6, 8]
    for e in (w, legacy):
        e.prefill(1, A)
        e.prefill(2, B)
        e.release(1)
        e.release(2)
    assert len(w.retired) == 1               # A's lane evicted, B's retained (LRU)
    for e in (w, legacy):                    # A's refs are stale -> full prefill
        e.prefill(3, A + [90])
    assert w.decode([3], 3) == legacy.decode([3], 3)


def test_reset_cache_drops_retired_prefixes(setup):
    """Weight sync must clear retired KV: after reset_cache() nothing implants."""
    cfg, params = setup
    w = RolloutWorker(cfg, params, capacity=64, max_slots=4,
                      sampler=SamplerConfig(temperature=0.0), chunk_size=4)
    w.prefill(1, [5, 7, 9, 11])
    w.release(1)
    w.reset_cache()
    assert not w.store and not w.retired
    w.prefill(2, [5, 7, 9, 11])
    assert w.reused_tokens == 0              # no stale implant after reset


@settings(max_examples=5, deadline=None)
@given(st.integers(2, 5), st.integers(4, 12), st.integers(0, 10), st.integers(0, 9999))
def test_chunked_reuse_parity_random_split_points(chunk, plen, raw_split, seed):
    """Property: for random prompts, chunk sizes, and shared-prefix split points,
    chunked + prefix-reuse admission is token-exact with legacy full prefill."""
    cfg, params = _setup()
    rng = np.random.default_rng(seed)
    prompt = [5 + int(t) for t in rng.integers(0, 100, plen)]
    split = min(raw_split, plen)
    sibling = prompt[:split] + [5 + int(t) for t in rng.integers(100, 200, plen - split)]
    sampler = SamplerConfig(temperature=1.0, top_p=0.9)
    w = RolloutWorker(cfg, params, capacity=64, max_slots=4, sampler=sampler,
                      chunk_size=chunk)
    legacy = LegacyRolloutWorker(cfg, params, capacity=64, sampler=sampler)
    for e in (w, legacy):
        e.prefill(1, prompt)
        e.prefill(2, sibling)                # implants the shared split prefix
    assert w.decode([1, 2], 2) == legacy.decode([1, 2], 2)


def test_chunk_window_past_capacity_edge_stays_exact(setup):
    """A fixed-shape chunk whose window hangs past the capacity edge
    (off + chunk_size > capacity while off + length <= capacity) must scatter each
    key to its absolute slot — a clamping slice-write would smear the tail chunk
    over resident positions.  Pins the dense (``paged=False``) lane layout the
    raw-KV comparison below assumes; the paged twin of this edge lives in
    tests/test_paging.py (page-boundary straddling).  The two paths compute
    the same keys through differently fused programs, so they agree to float
    rounding (~1e-6); a smeared slot would be off by O(1)."""
    cfg, params = setup
    sampler = SamplerConfig(temperature=1.0, top_p=0.9)
    w = RolloutWorker(cfg, params, capacity=16, max_slots=2, sampler=sampler,
                      chunk_size=8, paged=False)
    legacy = LegacyRolloutWorker(cfg, params, capacity=16, sampler=sampler)
    for e in (w, legacy):
        e.prefill(1, [5, 7, 9, 11, 13])
        e.extend(1, [21, 22, 23, 24, 25, 26])   # off=5..10
        e.extend(1, [31, 32, 33, 34])           # off=11: window 11..19 > cap 16
    lane = M.gather_slots(w.pool, np.asarray([w.store[1].slot]))
    for name, blk in lane["blocks"].items():
        for key in ("k", "v"):
            got = np.asarray(blk[key])
            want = np.asarray(legacy.store[1].cache["blocks"][name][key])
            np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    assert w.decode([1], 1) == legacy.decode([1], 1)


def test_pool_grows_on_overflow_and_reuses_freed_lanes(setup):
    cfg, params = setup
    w = RolloutWorker(cfg, params, capacity=32, max_slots=2,
                      sampler=SamplerConfig(temperature=0.0))
    w.prefill(1, [5, 7])
    w.prefill(2, [5, 9])
    slot1 = w.store[1].slot
    w.release(1)
    w.prefill(3, [5, 11])
    assert w.store[3].slot == slot1               # freed lane is reused first
    assert w.max_slots == 2 and w.pool_grows == 0
    w.prefill(4, [5, 13])                         # overflow: pool doubles
    assert w.max_slots == 4 and w.pool_grows == 1
    out = w.decode([2, 3, 4], 3)
    assert all(len(v) == 3 for v in out.values())
