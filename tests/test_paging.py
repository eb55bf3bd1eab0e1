"""Paged-KV data plane: PagePool accounting, paged kernel parity, bitwise
token parity against the dense lane pool (dense / MoE / recurrent configs,
page-boundary straddles), zero-copy prefix sharing, D2D migration + resume,
and resident-pages-only byte accounting."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.sanitize import check_block_conservation
from repro.configs import get_config
from repro.engine.paging import PagePool, PagePoolExhausted
from repro.engine.sampler import SamplerConfig
from repro.engine.worker import RolloutWorker
from repro.kernels import ops
from repro.kernels.ref import paged_decode_attention_ref
from repro.models import model as M

KEY = jax.random.PRNGKey(0)
GREEDY = SamplerConfig(temperature=0.0)


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen3_1_7b").reduced(n_periods=2)
    params = M.init_params(cfg, KEY)
    return cfg, params


def _pair(cfg, params, **kw):
    """(paged, dense) twin workers: identical params, greedy sampling."""
    kw.setdefault("capacity", 64)
    paged = RolloutWorker(cfg, params, worker_id=0, sampler=GREEDY,
                          paged=True, **kw)
    dense = RolloutWorker(cfg, params, worker_id=0, sampler=GREEDY,
                          paged=False, **kw)
    assert paged._paged and not dense._paged
    return paged, dense


# ------------------------------------------------------------------ PagePool

def test_pagepool_scratch_reserved_and_lowest_first():
    p = PagePool(8)
    assert p.alloc(3) == [1, 2, 3]                 # block 0 never handed out
    p.free([2])
    assert p.alloc(2) == [2, 4]                    # min-heap: lowest id first


def test_pagepool_share_and_free_refcounts():
    p = PagePool(8)
    blocks = p.alloc(2)
    p.share(blocks)
    assert p.refcount(blocks[0]) == 2 and p.shared_refs == 2
    assert p.free(blocks) == []                    # still referenced
    assert p.free(blocks) == blocks                # last ref: back on the heap
    assert p.resident_blocks == 0 and p.free_blocks == 7


def test_pagepool_exhaustion_and_grow():
    p = PagePool(4)
    p.alloc(3)
    with pytest.raises(PagePoolExhausted):
        p.alloc(1)
    p.grow(6)
    assert p.alloc(2) == [4, 5]
    with pytest.raises(ValueError):
        p.grow(2)                                  # cannot shrink


def test_pagepool_misuse_raises():
    p = PagePool(4)
    with pytest.raises(ValueError):
        p.free([1])                                # never allocated
    with pytest.raises(ValueError):
        p.share([2])
    with pytest.raises(ValueError):
        PagePool(1)                                # scratch needs a companion


def test_pagepool_conservation_stats():
    p = PagePool(16)
    a = p.alloc(4)
    p.share(a[:2])
    p.free(a[3:])
    s = p.stats()
    assert s["allocated_total"] - s["freed_total"] == s["resident"] + s["shared"]
    assert s["total"] == s["free"] + s["resident"]
    assert s["used_high_watermark"] == 4


# ------------------------------------------------------------------ the gate

def test_supports_paged_kv_gate():
    assert M.supports_paged_kv(get_config("qwen3_1_7b"))
    assert M.supports_paged_kv(get_config("qwen2_moe_a2_7b"))
    assert M.supports_paged_kv(get_config("xlstm_350m"))
    assert M.supports_paged_kv(get_config("jamba_v0_1_52b"))
    assert not M.supports_paged_kv(get_config("whisper_medium"))       # audio
    assert not M.supports_paged_kv(get_config("llama_3_2_vision_11b"))  # vlm
    ring = dataclasses.replace(get_config("qwen3_1_7b"), sliding_window=64)
    assert not M.supports_paged_kv(ring)           # ring writes wrap pages


def test_unsupported_config_falls_back_to_dense(setup):
    cfg, params = setup
    ring = dataclasses.replace(cfg, sliding_window=32)
    w = RolloutWorker(ring, params, capacity=64, worker_id=0)  # paged=None
    assert not w._paged
    assert "blocks_total" not in w.dispatch_stats()


# ------------------------------------------------------------- kernel parity

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [
    # (B, KV, G, hd, page_size, num_pages)
    (2, 2, 2, 64, 16, 4),
    (1, 1, 4, 64, 8, 7),       # odd page count
    (3, 4, 1, 128, 32, 2),
])
def test_paged_kernel_matches_ref(shape, dtype):
    B, KV, G, hd, ps, num_pages = shape
    NB = B * num_pages + 1                         # + scratch
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, KV, G, hd), dtype)
    k_pool = jax.random.normal(ks[1], (NB, ps, KV, hd), dtype)
    v_pool = jax.random.normal(ks[2], (NB, ps, KV, hd), dtype)
    rng = np.random.default_rng(0)
    pt = np.zeros((B, num_pages), np.int32)        # unmapped -> scratch
    vl = rng.integers(1, num_pages * ps + 1, B)
    for b in range(B):
        used = -(-int(vl[b]) // ps)
        pt[b, :used] = rng.choice(np.arange(1, NB), used, replace=False)
    pt, vl = jnp.asarray(pt), jnp.asarray(vl, jnp.int32)
    out_p = ops.paged_decode_attention(q, k_pool, v_pool, pt, vl,
                                       force_pallas=True)
    out_r = paged_decode_attention_ref(q, k_pool, v_pool, pt, vl)
    tol = 1e-5 if dtype == "float32" else 2.5e-2
    err = float(jnp.abs(out_p.astype(jnp.float32)
                        - out_r.astype(jnp.float32)).max())
    assert err < tol, (shape, dtype, err)


def test_paged_kernel_ignores_unmapped_and_invalid_blocks():
    """Scratch garbage and blocks past valid_len must not leak into the output."""
    B, KV, G, hd, ps, num_pages = 1, 2, 2, 64, 8, 4
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, KV, G, hd))
    k_pool = jax.random.normal(ks[1], (6, ps, KV, hd))
    v_pool = jax.random.normal(ks[2], (6, ps, KV, hd))
    pt = jnp.asarray([[1, 2, 0, 0]], jnp.int32)
    vl = jnp.asarray([11], jnp.int32)              # mid-page-2 valid boundary
    base = ops.paged_decode_attention(q, k_pool, v_pool, pt, vl,
                                      force_pallas=True)
    k2 = k_pool.at[0].set(99.0).at[3:].set(99.0)   # poison scratch + unused
    v2 = v_pool.at[0].set(-99.0).at[3:].set(-99.0)
    k2 = k2.at[2, 3:].set(77.0)                    # poison past valid_len
    v2 = v2.at[2, 3:].set(-77.0)
    out = ops.paged_decode_attention(q, k2, v2, pt, vl, force_pallas=True)
    np.testing.assert_allclose(np.asarray(base), np.asarray(out), atol=1e-5)


def test_meshed_pallas_decode_rejects_indivisible_kv_heads():
    """Under a worker mesh the Pallas kernels run per KV-head shard; a model axis
    the heads do not divide (smollm's 3 KV heads at mp 2) is an error, not a
    replicated run that all-gathers the pool onto every device."""
    from types import SimpleNamespace

    from repro.distributed.sharding import axis_rules
    mesh = SimpleNamespace(size=2, shape={"data": 1, "model": 2})
    B, KV, G, hd, ps = 1, 3, 3, 64, 8
    q = jnp.zeros((B, KV, G, hd))
    pool = jnp.zeros((3, ps, KV, hd))
    pt, vl = jnp.asarray([[1, 2]], jnp.int32), jnp.asarray([9], jnp.int32)
    with axis_rules(mesh), pytest.raises(ValueError, match="3 KV heads do not divide"):
        ops.paged_decode_attention(q, pool, pool, pt, vl, force_pallas=True)


# ------------------------------------------------------------ the chunk program

def _chunk_sliced(cfg, params, pool, slot, tokens, length):
    """The chunk program in the form that slices each layer's pool in as the
    layer scan's ``xs`` and writes it back whole as its ``ys``: the reference
    the carried form must match bit for bit."""
    slot = jnp.asarray(slot, jnp.int32)
    length = jnp.asarray(length, jnp.int32)
    off = pool["pos"][slot]
    pt_row = pool["page_table"][slot]
    zero = jnp.zeros((), jnp.int32)

    def body(x, xs):
        p_period, c_period = xs
        new_c = {}
        for i, kind in enumerate(cfg.block_pattern):
            key = f"{i:02d}_{kind}"
            one = jax.tree.map(lambda c: c[None], c_period[key])
            x, out = M._layer_chunk_paged(cfg, kind, p_period[key], x, one, zero,
                                          pt_row, slot, off, length)
            new_c[key] = jax.tree.map(lambda c: c[0], out)
        return x, new_c

    _, blocks = jax.lax.scan(body, params["tok_embed"][tokens],
                             (params["blocks"], pool["blocks"]))
    return {"pos": pool["pos"].at[slot].add(length),
            "page_table": pool["page_table"], "blocks": blocks}


_SLICED = jax.jit(_chunk_sliced, static_argnums=(0,))


def _hybrid():
    return get_config("jamba_v0_1_52b").reduced(
        n_periods=2, block_pattern=("mamba+mlp", "attn+mlp"))


def _paged_leaves(pool):
    return {key: c for key, c in pool["blocks"].items() if M._paged_kind(key[3:])}


@pytest.mark.parametrize("make_cfg", [
    lambda: get_config("qwen3_1_7b").reduced(n_periods=2),
    _hybrid,                                       # mamba + attention
    lambda: get_config("xlstm_350m").reduced(n_periods=2),  # mlstm + slstm
], ids=["qwen3", "hybrid", "xlstm"])
def test_chunk_program_carries_the_pool_bitwise(make_cfg):
    """Several chunks on two lanes, the second mapping the first's two full
    prefix pages and prefilling its suffix on top of them: the carried pool
    equals the sliced form's bit for bit, and a chunk writes no block its lane
    does not map (scratch block 0 aside) and no other lane's state."""
    from repro.engine.worker import _paged_chunk

    cfg = make_cfg()
    params = M.init_params(cfg, KEY)
    ps, C, pages = 8, 8, 5
    pool = M.init_paged_pool(cfg, None, 2, 11, ps, pages)
    leaves, tree = jax.tree.flatten(pool["blocks"])
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    pool["blocks"] = jax.tree.unflatten(tree, [
        jax.random.normal(k, x.shape, x.dtype) for k, x in zip(keys, leaves)])
    rows = {0: [1, 2, 3, 4, 0], 1: [1, 2, 5, 6, 0]}    # lane 1 shares blocks 1, 2
    rng = np.random.default_rng(0)
    prompt0 = rng.integers(1, cfg.vocab, 27)
    prompts = {0: prompt0, 1: np.concatenate([prompt0[:16],
                                              rng.integers(1, cfg.vocab, 13)])}
    # (lane, first position, valid tokens): lane 1 starts once lane 0 has
    # written its first 16 positions, and the two lanes' chunks interleave
    plan = [(0, 0, 8), (0, 8, 8), (1, 16, 8), (0, 16, 8), (1, 24, 5), (0, 24, 3)]

    def chunk(prog, pool, slot, start, n):
        tok = np.zeros((1, C), np.int32)
        tok[0, :n] = prompts[slot][start:start + n]
        return prog(cfg, params, pool, jnp.asarray(slot, jnp.int32),
                    jnp.asarray(tok), jnp.asarray(n, jnp.int32))

    ours = ref = M.paged_set_lane(M.paged_set_lane(pool, 0, rows[0], 0),
                                  1, rows[1], 16)
    ref = jax.tree.map(jnp.copy, ref)              # ``_paged_chunk`` donates
    for slot, start, n in plan:
        before = jax.tree.map(np.asarray, ours)
        ours = chunk(_paged_chunk, ours, slot, start, n)
        ref = chunk(_SLICED, ref, slot, start, n)
        after = jax.tree.map(np.asarray, ours)
        jax.tree.map(np.testing.assert_array_equal, after, jax.tree.map(np.asarray, ref))
        other = 1 - slot
        keep = [b for b in range(1, 11) if b not in rows[slot]]
        if start >= 16:
            keep += [1, 2]                         # written ahead of the chunk
        for key, c in _paged_leaves(after).items():
            for name in c:
                np.testing.assert_array_equal(
                    c[name][:, keep], _paged_leaves(before)[key][name][:, keep])
        for key, c in after["blocks"].items():
            if not M._paged_kind(key[3:]):
                for name in c:
                    np.testing.assert_array_equal(
                        c[name][:, other], before["blocks"][key][name][:, other])
    assert [int(p) for p in ours["pos"]] == [27, 29]


def test_chunk_program_keeps_no_copy_of_the_pool():
    """Guard against the pool's round trip through the layer scan: the compiled
    chunk program's temporaries stay below the bytes of the paged blocks (the
    sliced form copies every layer's blocks and needs more than all of them)."""
    from repro.engine.worker import _paged_chunk

    cfg = get_config("qwen3_1_7b").reduced(n_periods=4)
    params = M.init_params(cfg, KEY)
    pool = jax.eval_shape(lambda: M.init_paged_pool(cfg, None, 4, 33, 16, 8))
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    compiled = _paged_chunk.lower(cfg, params, pool, i32,
                                  jax.ShapeDtypeStruct((1, 32), jnp.int32),
                                  i32).compile()
    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(_paged_leaves(pool)))
    assert pool_bytes == 4_325_376
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes


# ------------------------------------------------------- bitwise token parity

def test_paged_decode_bitwise_matches_dense(setup):
    cfg, params = setup
    paged, dense = _pair(cfg, params)
    prompt = [5, 7, 9, 11, 13, 17, 19, 23]
    paged.prefill(1, prompt)
    dense.prefill(1, prompt)
    assert paged.decode([1], 8)[1] == dense.decode([1], 8)[1]


def test_page_boundary_straddling_sequence(setup):
    """Prompt, tool extension, and decode all straddle page boundaries
    (page_size=4): writes land split across blocks, reads gather across the
    page table — tokens must still match the dense lane bitwise."""
    cfg, params = setup
    paged, dense = _pair(cfg, params, page_size=4, chunk_size=8)
    prompt = [3 + i for i in range(6)]             # 6 = 1.5 pages
    paged.prefill(1, prompt)
    dense.prefill(1, prompt)
    assert paged.decode([1], 5)[1] == dense.decode([1], 5)[1]  # 11 = 2.75 pages
    ext = [101, 102, 103, 104, 105]                # -> 16: exact page edge
    paged.extend(1, ext)
    dense.extend(1, ext)
    assert paged.decode([1], 6)[1] == dense.decode([1], 6)[1]
    assert paged.store[1].tokens == dense.store[1].tokens


def test_paged_chunk_window_past_capacity_edge(setup):
    """Paged twin of test_slot_pool's capacity-edge test: decode right up to
    the lane capacity with on-demand page allocation covering the tail."""
    cfg, params = setup
    paged, dense = _pair(cfg, params, capacity=16, page_size=4, chunk_size=8)
    prompt = list(range(3, 16))                    # 13 tokens
    paged.prefill(1, prompt)
    dense.prefill(1, prompt)
    assert paged.decode([1], 3)[1] == dense.decode([1], 3)[1]  # fills to 16
    assert len(paged.lane_pages[paged.store[1].slot]) == 4     # full coverage


def test_moe_paged_parity_non_chunked_admission():
    """qwen2_moe: chunked prefill is unsupported (capacity dispatch), so paged
    admission runs the whole-prompt ``_admit_paged`` path — tokens must match
    the dense pool bitwise through the MoE mixers."""
    full = get_config("qwen2_moe_a2_7b")
    cfg = full.reduced(n_periods=1)
    cfg = dataclasses.replace(
        cfg, capacity_factor=float(cfg.n_experts) / cfg.top_k + 1)
    params = M.init_params(cfg, KEY)
    paged, dense = _pair(cfg, params, capacity=32, page_size=8)
    assert not paged._chunked                      # MoE: whole-prompt admit
    prompt = [5, 7, 9, 11, 13, 17]
    paged.prefill(1, prompt)
    dense.prefill(1, prompt)
    assert paged.decode([1], 4)[1] == dense.decode([1], 4)[1]


def test_recurrent_paged_parity():
    """xlstm: zero attention layers — the paged pool is pure dense state, the
    page machinery is bookkeeping-only, and decode must match exactly."""
    cfg = get_config("xlstm_350m").reduced(n_periods=1)
    params = M.init_params(cfg, KEY)
    paged, dense = _pair(cfg, params, capacity=32, page_size=8)
    assert paged._page_bytes == 0                  # no paged leaves to price
    prompt = [5, 7, 9, 11, 13, 17]
    paged.prefill(1, prompt)
    dense.prefill(1, prompt)
    assert paged.decode([1], 4)[1] == dense.decode([1], 4)[1]


# --------------------------------------------------------------- page sharing

def test_sibling_share_zero_copy_and_parity(setup):
    """A GRPO sibling's full prefix pages are refcount-shared (no KV copy);
    only the boundary partial page is D2D-copied.  The sibling's decode must
    still match the dense pool's copy-based implant bitwise."""
    cfg, params = setup
    paged, dense = _pair(cfg, params, page_size=16, chunk_size=8)
    prompt = [3 + i for i in range(20)]            # 1 full page + 4 boundary
    paged.prefill(1, prompt)
    dense.prefill(1, prompt)
    free_before = paged.pages.free_blocks
    paged.prefill(2, prompt)
    dense.prefill(2, prompt)
    s = paged.dispatch_stats()
    assert s["blocks_shared"] == 1                 # the full page, by refcount
    assert s["reused_tokens"] == 20 and s["full_hits"] == 1
    # sibling cost: 1 boundary block + pages for the suffix beyond reuse (none)
    assert free_before - paged.pages.free_blocks == 1
    assert paged.decode([1, 2], 5) == dense.decode([1, 2], 5)
    # shared page stays intact after the sibling decodes past it
    assert paged.pages.refcount(paged.lane_pages[paged.store[1].slot][0]) == 2


# ------------------------------------------------------------------ migration

def test_d2d_migration_resume_parity(setup):
    """Paged -> paged migration ships device-resident page stacks; the
    destination resumes exactly where the source stopped."""
    cfg, params = setup
    w0 = RolloutWorker(cfg, params, capacity=64, worker_id=0, sampler=GREEDY)
    w1 = RolloutWorker(cfg, params, capacity=64, worker_id=1, sampler=GREEDY)
    ref = RolloutWorker(cfg, params, capacity=64, worker_id=0, sampler=GREEDY)
    assert w0._paged and w1._paged
    w0.prefill(1, [5, 7, 9, 11])
    ref.prefill(2, [5, 7, 9, 11])
    w0.decode([1], 3)
    ref.decode([2], 3)
    pkg = w0.migrate_out(1)
    assert "pages" in pkg and "cache" not in pkg   # page stacks, not a lane
    for leaf in jax.tree.leaves(pkg["pages"]):
        assert isinstance(leaf, jax.Array)         # stayed on device (D2D)
    w1.migrate_in(pkg)
    assert w1.decode([1], 4)[1] == ref.decode([2], 4)[2]


def test_cross_layout_migration_both_directions(setup):
    cfg, params = setup
    paged, dense = _pair(cfg, params)
    ref = RolloutWorker(cfg, params, capacity=64, worker_id=0, sampler=GREEDY)
    prompt = [5, 7, 9, 11, 13]
    for w, sid in ((paged, 1), (dense, 2), (ref, 3)):
        w.prefill(sid, prompt)
        w.decode([sid], 3)
    want = ref.decode([3], 4)[3]
    # paged package flattened onto a dense pool
    dense.migrate_in(paged.migrate_out(1))
    assert dense.decode([1], 4)[1] == want
    # dense lane scattered onto a paged pool
    paged.migrate_in(dense.migrate_out(2))
    assert paged.decode([2], 4)[2] == want


def test_checkpoint_restore_parity_and_equal_logical_bytes(setup):
    """The host-gathered checkpoint and the D2D migration package of the same
    lane must price identical logical bytes (resident pages + state), and a
    restore from the checkpoint must resume bitwise."""
    cfg, params = setup
    w0 = RolloutWorker(cfg, params, capacity=64, worker_id=0, sampler=GREEDY)
    ref = RolloutWorker(cfg, params, capacity=64, worker_id=0, sampler=GREEDY)
    w0.prefill(1, [5, 7, 9, 11])
    ref.prefill(2, [5, 7, 9, 11])
    w0.decode([1], 3)
    ref.decode([2], 3)
    ck = w0.checkpoint_out(1)
    for leaf in jax.tree.leaves(ck["pages"]):
        assert isinstance(leaf, np.ndarray)        # durability: host buffers
    pkg = w0.migrate_out(1)
    assert ck["logical_bytes"] == pkg["logical_bytes"]
    w1 = RolloutWorker(cfg, params, capacity=64, worker_id=1, sampler=GREEDY)
    w1.migrate_in(ck)
    assert w1.decode([1], 4)[1] == ref.decode([2], 4)[2]


def test_migration_bytes_account_resident_pages_only(setup):
    """Regression (cost-model fix): a short lane's transfer prices its resident
    pages + dense state, not the full ``capacity`` lane the dense fallback
    ships.  The dense package still reports its true (full-lane) bytes."""
    cfg, params = setup
    paged, dense = _pair(cfg, params)              # capacity 64, page_size 16
    paged.prefill(1, [5, 7, 9, 11])
    dense.prefill(1, [5, 7, 9, 11])
    ppkg = paged.migrate_out(1)
    dpkg = dense.migrate_out(1)
    assert ppkg["logical_bytes"] == paged._page_bytes + paged._state_bytes
    assert dpkg["logical_bytes"] == sum(x.nbytes
                                        for x in jax.tree.leaves(dpkg["cache"]))
    assert ppkg["logical_bytes"] < dpkg["logical_bytes"]


# ------------------------------------------------------ accounting / telemetry

def test_paged_kv_bytes_prices_resident_pages(setup):
    cfg, params = setup
    w = RolloutWorker(cfg, params, capacity=64, worker_id=0, sampler=GREEDY,
                      page_size=4)
    w.prefill(1, [5, 7, 9])                        # 3 tokens -> 1 block
    assert w.kv_bytes(1) == w._page_bytes + w._state_bytes
    w.decode([1], 4)                               # 7 tokens -> 2 blocks
    assert w.kv_bytes(1) == 2 * w._page_bytes + w._state_bytes
    assert w.kv_bytes(1) < w._lane_bytes           # the admission win


def test_dispatch_stats_block_telemetry(setup):
    cfg, params = setup
    w = RolloutWorker(cfg, params, capacity=64, worker_id=0, sampler=GREEDY)
    w.prefill(1, [5, 7, 9, 11])
    s = w.dispatch_stats()
    for k in ("blocks_total", "blocks_free", "blocks_resident", "blocks_shared",
              "blocks_allocated_total", "blocks_freed_total",
              "blocks_used_high_watermark", "page_size", "block_grows"):
        assert k in s, k
    assert s["blocks_resident"] == 1 and s["page_size"] == w.page_size


def test_block_conservation_through_lifecycle(setup):
    """allocated - freed == resident + shared at every lifecycle edge, and the
    sanitizer's drain check agrees."""
    cfg, params = setup
    w = RolloutWorker(cfg, params, capacity=64, worker_id=0, sampler=GREEDY,
                      page_size=16, chunk_size=8, max_slots=2)

    def conserved():
        s = w.pages.stats()
        assert (s["allocated_total"] - s["freed_total"]
                == s["resident"] + s["shared"]), s
        assert check_block_conservation({0: w.dispatch_stats()}) == []

    prompt = [3 + i for i in range(20)]
    w.prefill(1, prompt)
    conserved()
    w.prefill(2, prompt)                           # sibling: shares a page
    conserved()
    w.decode([1, 2], 4)
    conserved()
    w.release(1)                                   # retires; pages trimmed
    conserved()
    w.migrate_out(2)                               # gathered out + retired
    conserved()
    w.reset_cache()                                # weight sync: all freed
    conserved()
    s = w.pages.stats()
    assert s["resident"] == 0 and s["shared"] == 0
    assert s["allocated_total"] == s["freed_total"] > 0


def test_block_conservation_check_flags_leak():
    stats = {"blocks_total": 8, "blocks_free": 5, "blocks_resident": 3,
             "blocks_shared": 0, "blocks_allocated_total": 6,
             "blocks_freed_total": 2}             # 4 live refs != 3 held
    assert any("leak" in v for v in check_block_conservation({0: stats}))
    stats["blocks_freed_total"] = 3
    stats["blocks_free"] = 4                       # partition broken
    assert any("partition" in v for v in check_block_conservation({0: stats}))
    assert check_block_conservation({0: {"decode_steps": 1}}) == []  # dense: skip
