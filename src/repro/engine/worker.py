"""Slot-pool rollout worker: the real JAX data plane with true continuous batching.

The engine owns one preallocated **slot-pool KV cache** — ``max_slots`` lanes built by
``model.init_cache`` — instead of a per-sequence cache store:

  * admission: prefill writes its cache straight into a free lane
    (``lax.dynamic_update_slice`` via ``model.write_slot``; the pool buffer is donated,
    so XLA updates the lane in place),
  * decode: one persistent jitted loop (``lax.scan``) over the whole resident batch
    with an active-slot mask — no ``concat``/``slice`` round-trips per call,
  * preemption: a mask flip — the lane stays resident, nothing moves,
  * migration: ``model.gather_slots`` lifts one lane out; the destination implants it
    into a free lane without disturbing co-resident sequences (§5.3),
  * tool absorption: chunked prefill into the lane at its current offset
    (ceil(L/C) fixed-shape dispatches, no prefix recompute),
  * prefix reuse: a radix cache owning resident + retired lane KV — matched
    prefixes are implanted by an on-device lane-slice copy and only the unmatched
    suffix is prefilled (O(suffix) admission for GRPO siblings / tool re-entries).

Admission itself is chunked: ceil(S/C) reuses of ONE compiled (1, C) kernel replace
the legacy one-compile-per-prompt-length full forward (kept in ``_admit`` for
configs chunking can't serve — see ``model.supports_chunked_prefill``).

Sampling is per-slot: every sequence draws from
``fold_in(fold_in(PRNGKey(seed + worker_id), seq_id), context_len)``, making its token
stream independent of co-resident lanes and stable across preemption and migration
(the key travels in the migration package).  ``repro.engine.legacy`` keeps the old
concat/slice engine as the parity reference; see docs/engine.md for invariants.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.distributed.sharding import (axis_rules, cache_shardings,
                                        param_shardings)
from repro.engine.paging import PagePool, PagePoolExhausted
from repro.engine.sampler import SamplerConfig, sample_slots
from repro.engine.spans import SpanTotals
from repro.models import model as M
from repro.models.config import ModelConfig


# ---------------------------------------------------------------- radix cache

class _TrieNode:
    __slots__ = ("children", "refs", "last_used")

    def __init__(self):
        self.children: dict[int, _TrieNode] = {}
        self.refs: dict[int, int] = {}       # lane slot -> epoch at insert
        self.last_used = 0


class PrefixCacheIndex:
    """Radix cache over token prefixes: accounting trie + (lane, span) KV refs.

    Accounting: every ``match_len``/``match_lane`` counts a lookup and classifies it
    as a **full** hit (the whole query matched) or a **partial** hit (a nonzero
    proper prefix matched) — ``hits`` aggregates both, so controller affinity stats
    can consume the honest split.  Node count is bounded by ``max_nodes``: inserts
    past the cap first prune the least-recently-used subtrees (a parent is always at
    least as recent as its children, so pruning by timestamp cutoff removes whole
    cold subtrees) and then truncate, keeping memory bounded even in pure
    accounting mode.

    KV ownership: ``insert(tokens, slot=...)`` tags every node on the path with a
    ``(slot, epoch)`` ref, claiming that lane ``slot`` holds valid KV for this
    prefix at positions ``[0, depth)``.  ``invalidate(slot)`` bumps the slot's epoch
    (lane overwritten / evicted); stale refs are dropped lazily during matching.
    ``match_lane`` returns the deepest live ref, which the engine implants with an
    on-device lane-slice copy so only the unmatched suffix is prefilled.
    """

    def __init__(self, max_nodes: int = 65_536):
        self.root = _TrieNode()
        self.max_nodes = max_nodes
        self.node_count = 0                  # root excluded
        self._clock = 0
        self._epochs: dict[int, int] = {}
        self.lookups = 0
        self.full_hits = 0
        self.partial_hits = 0
        self.hit_tokens = 0

    @property
    def hits(self) -> int:
        return self.full_hits + self.partial_hits

    def invalidate(self, slot: int) -> None:
        """Mark lane ``slot``'s KV refs stale (lane reassigned or evicted)."""
        self._epochs[slot] = self._epochs.get(slot, 0) + 1

    # ------------------------------------------------------------ insert / match
    def insert(self, tokens: list[int], slot: int | None = None) -> None:
        self._clock += 1
        now = self._clock
        epoch = self._epochs.setdefault(slot, 0) if slot is not None else 0
        node = self.root
        node.last_used = now
        for t in tokens:
            child = node.children.get(int(t))
            if child is None:
                if self.node_count >= self.max_nodes:
                    self._prune()
                if self.node_count >= self.max_nodes:
                    return                   # cap still binding: truncate the insert
                child = _TrieNode()
                node.children[int(t)] = child
                self.node_count += 1
            child.last_used = now
            if slot is not None:
                child.refs[slot] = epoch
            node = child

    def _walk(self, tokens: list[int]) -> tuple[int, int, int | None]:
        """Walk + account one lookup; returns (trie depth, reuse depth, lane)."""
        self._clock += 1
        now = self._clock
        node = self.root
        n = 0
        reuse_n, reuse_slot = 0, None
        for t in tokens:
            node = node.children.get(int(t))
            if node is None:
                break
            node.last_used = now
            n += 1
            if node.refs:
                stale = [s for s, e in node.refs.items()
                         if self._epochs.get(s, 0) != e]
                for s in stale:
                    del node.refs[s]
                if node.refs:
                    reuse_n, reuse_slot = n, next(iter(node.refs))
        self.lookups += 1
        if n and n == len(tokens):
            self.full_hits += 1
        elif n:
            self.partial_hits += 1
        self.hit_tokens += n
        return n, reuse_n, reuse_slot

    def match_len(self, tokens: list[int]) -> int:
        return self._walk(tokens)[0]

    def match_lane(self, tokens: list[int]) -> tuple[int, int | None]:
        """Deepest prefix of ``tokens`` backed by a live lane: (length, slot)."""
        _, reuse_n, reuse_slot = self._walk(tokens)
        return reuse_n, reuse_slot

    # ------------------------------------------------------------ LRU pruning
    def _subtree_size(self, node: _TrieNode) -> int:
        count, stack = 0, [node]
        while stack:
            n = stack.pop()
            count += 1
            stack.extend(n.children.values())
        return count

    def _prune(self) -> None:
        """Evict least-recently-used subtrees down to ~3/4 of the node cap."""
        target = max(1, self.max_nodes * 3 // 4)
        stamps: list[int] = []
        stack = [self.root]
        while stack:
            node = stack.pop()
            for c in node.children.values():
                stamps.append(c.last_used)
                stack.append(c)
        excess = len(stamps) - target
        if excess <= 0:
            return
        # never evict the in-flight insert path (stamped with the current clock)
        cutoff = min(sorted(stamps)[excess - 1], self._clock - 1)
        removed = 0
        stack = [self.root]
        while stack:
            node = stack.pop()
            doomed = [t for t, c in node.children.items() if c.last_used <= cutoff]
            for t in doomed:
                removed += self._subtree_size(node.children.pop(t))
            stack.extend(node.children.values())
        self.node_count -= removed


# ---------------------------------------------------------------- jitted kernels
# Module-level jits keyed on (cfg, shapes): workers sharing a config share compiles.
# Kernels whose model code emits sharding constraints (``sharding.shard``) also key
# on the worker's ``mesh`` as a *static* argument: pjit caches the traced jaxpr by
# avals alone, so a constraint traced under worker A's mesh would otherwise be
# replayed — with A's device set baked in — for worker B's differently-meshed
# arguments.  ``axis_rules`` runs at trace time, once per (cfg, shapes, mesh).

@partial(jax.jit, static_argnames=("cfg", "capacity", "mesh"), donate_argnums=(2,))
def _admit(cfg: ModelConfig, params, pool, tokens, slot, capacity: int, mesh=None):
    """Full-sequence prefill fallback: one compile per distinct prompt length.

    Used only for configs ``supports_chunked_prefill`` rejects (MoE, sliding-window,
    cross-attention); everything else admits through the chunked path below."""
    with axis_rules(mesh):
        _, _, lane = M.forward_full(cfg, params, {"tokens": tokens},
                                    capacity=capacity)
        return M.write_slot(pool, lane, slot)


@partial(jax.jit, static_argnames=("cfg", "batch", "capacity"))
def _fresh_lane(cfg: ModelConfig, batch: int, capacity: int):
    """Empty batch-1 lane cache (chunked admission starts here)."""
    return M.init_cache(cfg, None, batch, capacity)


@partial(jax.jit, static_argnames=("cfg", "mesh"), donate_argnums=(2,))
def _prefill_chunk(cfg: ModelConfig, params, lane, tokens, length, mesh=None):
    """One fixed-shape (1, C) chunk into a batch-1 lane at its current ``pos``.

    ``length`` is traced, so ONE compile serves every offset and tail length —
    admission cost is bounded by chunk count, not by distinct prompt lengths."""
    with axis_rules(mesh):
        return M.prefill_chunk(cfg, params, lane, tokens, length)


@partial(jax.jit, donate_argnums=(2,))
def _copy_prefix(pool, src_slot, lane, n):
    """Implant the first ``n`` positions of pool lane ``src_slot`` into ``lane``
    (radix-cache prefix reuse: an on-device lane-slice copy, no recompute)."""
    return M.copy_prefix(pool, src_slot, lane, n)


@jax.jit
def _gather_lane(pool, slot):
    """Lift one lane out of the pool as a batch-1 cache (chunked tool absorption)."""
    return M.gather_slots(pool, slot[None])


@partial(jax.jit, donate_argnums=(0,))
def _implant(pool, lane, slot):
    """Write a migrated batch-1 cache into lane ``slot`` (migration ingress)."""
    return M.write_slot(pool, lane, slot)


@partial(jax.jit, static_argnames=("cfg", "mesh"), donate_argnums=(2,))
def _extend_slot(cfg: ModelConfig, params, pool, tool_tokens, slot, mesh=None):
    """Teacher-force ``tool_tokens`` (L,) into lane ``slot`` only (active mask)."""
    B = pool["pos"].shape[0]
    active = jnp.arange(B) == slot

    def body(pool, tok):
        _, pool = M.decode_step(cfg, params, pool,
                                jnp.broadcast_to(tok, (B,))[:, None], active=active)
        return pool, None

    with axis_rules(mesh):
        pool, _ = lax.scan(body, pool, tool_tokens)
    return pool


# ---- paged-pool kernels (model.supports_paged_kv data plane) -------------------
# The pool dict grows a ``page_table`` leaf; every kernel donates the pool so XLA
# updates blocks/rows in place.  Host-side block accounting (PagePool) never sees
# the device: the worker keeps lane -> block lists and mirrors them into the
# device page table through ``_paged_row`` / ``_paged_lane``.

@partial(jax.jit, static_argnames=("cfg", "mesh"), donate_argnums=(2,))
def _paged_chunk(cfg: ModelConfig, params, pool, slot, tokens, length, mesh=None):
    """One fixed-shape (1, C) chunk straight into lane ``slot``'s pages."""
    with axis_rules(mesh):
        return M.prefill_chunk_paged(cfg, params, pool, slot, tokens, length)


@partial(jax.jit, donate_argnums=(0,))
def _paged_lane(pool, slot, row, pos0):
    """Map a lane: page-table row + position reset (admission ingress)."""
    return M.paged_set_lane(pool, slot, row, pos0)


@partial(jax.jit, donate_argnums=(0,))
def _paged_row(pool, slot, row):
    """Rewrite one page-table row without touching ``pos`` (coverage extension,
    retire-trim: unmapped tail entries go back to scratch so a masked lane's
    self-healing write can never land in a reassigned block)."""
    return dict(pool, page_table=pool["page_table"].at[slot].set(row))


@partial(jax.jit, donate_argnums=(0,))
def _copy_block(pool, dst, src):
    """Device-to-device copy of one physical block (prefix-share boundary page)."""
    return M.paged_copy_block(pool, dst, src)


@jax.jit
def _gather_pages(pool, idx):
    """Lift resident physical blocks out of the pool (D2D migration payload)."""
    return M.paged_gather_pages(pool, idx)


@partial(jax.jit, donate_argnums=(0,))
def _paged_ingest(pool, pages, idx, state, slot, row):
    """Migration ingress: scatter page stacks into freshly allocated blocks and
    write the lane's dense state + page-table row."""
    pool = M.paged_scatter_pages(pool, pages, idx)
    return M.paged_write_state(pool, state, slot, row)


@partial(jax.jit, donate_argnums=(0,))
def _paged_implant(pool, lane, slot, row, n):
    """Scatter a dense batch-1 lane into mapped pages (cross-layout ingress)."""
    return M.paged_write_lane(pool, lane, slot, row, n)


@partial(jax.jit, static_argnames=("cfg", "capacity", "mesh"), donate_argnums=(2,))
def _admit_paged(cfg: ModelConfig, params, pool, tokens, slot, row,
                 capacity: int, mesh=None):
    """Full-sequence paged admission (non-chunkable configs: MoE, etc.) — the
    dense ``_admit`` followed by a page scatter instead of a lane write."""
    with axis_rules(mesh):
        _, _, lane = M.forward_full(cfg, params, {"tokens": tokens},
                                    capacity=capacity)
        return M.paged_write_lane(pool, lane, slot, row, tokens.shape[1])


@partial(jax.jit,
         static_argnames=("cfg", "n_tokens", "stop_token", "sampler", "mesh"),
         donate_argnums=(2,))
def _decode_loop(cfg: ModelConfig, params, pool, last, live, keys,
                 n_tokens: int, stop_token: int | None, sampler: SamplerConfig,
                 mesh=None):
    """The persistent decode loop: ``n_tokens`` masked steps over the whole pool.

    last: (B,) int32 last context token per lane; live: (B,) bool active mask;
    keys: (B, 2) uint32 per-sequence base keys.  Returns (pool', emitted (T, B))
    where emitted is -1 for lanes that were inactive (or already stopped) at a step.
    """

    def body(carry, _):
        pool, last, live = carry
        step_keys = jax.vmap(jax.random.fold_in)(keys, pool["pos"])
        logits, pool = M.decode_step(cfg, params, pool, last[:, None], active=live)
        toks = sample_slots(step_keys, logits, sampler, active=live)
        last = jnp.where(live, toks, last)
        if stop_token is not None:
            live = live & (toks != stop_token)
        return (pool, last, live), toks

    with axis_rules(mesh):
        (pool, last, live), emitted = lax.scan(body, (pool, last, live), None,
                                               length=n_tokens)
    return pool, last, live, emitted


# host-side chunk size for stop-token decodes: one device round-trip per CHUNK steps
# buys back the legacy early exit (all requested lanes stopped -> stop paying for
# masked full-pool steps) while bounding jit variants to {CHUNK, tail}
_DECODE_CHUNK = 8


# ---------------------------------------------------------------- worker

@dataclass
class Sequence:
    seq_id: int
    tokens: list[int]                    # full context (prompt + generated + tool)
    slot: int                            # lane index in the worker's slot pool
    key: np.ndarray                      # (2,) uint32 per-sequence sampling key
    generated: int = 0
    preempted: bool = False
    finished: bool = False


class RolloutWorker:
    """One rollout worker holding model params and a slot-pool KV cache.

    Admission runs the **chunked prefill plane** whenever the architecture supports
    it (``model.supports_chunked_prefill``): a prompt of any length is ceil(S/C)
    dispatches of one fixed-shape compiled chunk kernel, with the radix cache
    implanting any matched prefix from a resident or retired lane first, so GRPO
    siblings and multi-turn re-entries pay O(suffix).  Released lanes retire into an
    LRU set (bounded by ``retired_kv_bytes``) instead of being dropped, keeping
    their KV reusable until admission pressure reclaims them.
    """

    def __init__(self, cfg: ModelConfig, params, capacity: int = 256,
                 max_slots: int = 8, worker_id: int = 0,
                 sampler: SamplerConfig = SamplerConfig(), seed: int = 0,
                 chunk_size: int = 32, prefix_reuse: bool = True,
                 use_chunked: bool | None = None,
                 retired_kv_bytes: int | None = None,
                 prefix_index_nodes: int = 65_536,
                 mesh=None, mp: int = 1,
                 paged: bool | None = None, page_size: int = 16,
                 num_blocks: int | None = None):
        self.cfg = cfg
        self.capacity = capacity
        self.max_slots = max_slots
        self.worker_id = worker_id
        self.sampler = sampler
        # model parallelism: `mp` is the worker's declared MP degree (drives the
        # control plane's latency model); `mesh` is its physical realization — a
        # ("data", "model") sub-mesh over `mp` devices.  When the device set can't
        # host the mesh (un-forced CPU), mesh is None and the worker runs the
        # identical un-meshed code path (sharding.shard() is the identity).
        self.mp = max(int(mp), 1)
        self.mesh = mesh
        self.base_key = jax.random.PRNGKey(seed + worker_id)
        if mesh is not None:
            self.params = jax.device_put(params, param_shardings(params, mesh))
        else:
            self.params = params
        # paged KV data plane: default ON whenever the architecture supports it —
        # admission capacity then scales with resident tokens, not max_len * slots
        self._paged = ((paged if paged is not None else True)
                       and M.supports_paged_kv(cfg))
        if self._paged:
            ps = max(int(page_size), 1)
            while capacity % ps:                   # page size must tile the lane
                ps //= 2
            self.page_size = ps
            self.num_pages = capacity // ps
            # default block budget: the dense pool's HBM footprint (+ scratch)
            self.num_blocks = (num_blocks if num_blocks is not None
                               else max_slots * self.num_pages + 1)
            self.pages = PagePool(self.num_blocks)
            self.lane_pages: dict[int, list[int]] = {}   # slot -> ordered blocks
            self.block_grows = 0
            self.pool = self._place_cache(M.init_paged_pool(
                cfg, None, max_slots, self.num_blocks, ps, self.num_pages))
        else:
            self.pool = self._place_cache(
                M.init_cache(cfg, None, max_slots, capacity))
        self.store: dict[int, Sequence] = {}       # resident sequences (incl. preempted)
        self.chunk_size = chunk_size
        self._chunked = ((use_chunked if use_chunked is not None else True)
                         and M.supports_chunked_prefill(cfg))
        self._reuse = prefix_reuse and self._chunked and M.supports_prefix_reuse(cfg)
        # stable per-lane cache footprint (shape math only — nothing is allocated),
        # independent of later pool growth
        lane = jax.eval_shape(lambda: M.init_cache(cfg, None, 1, capacity))
        self._lane_bytes = sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
                               for x in jax.tree.leaves(lane))
        if self._paged:
            # per-block bytes (k+v across every paged layer) and the per-lane
            # dense-state remainder — kv_bytes() prices *resident pages* only
            itemsize = jnp.dtype(cfg.dtype).itemsize
            n_attn = sum(1 for k in cfg.block_pattern
                         if k.partition("+")[0] == "attn")
            self._page_bytes = (2 * cfg.n_periods * n_attn * self.page_size
                                * cfg.n_kv_heads * cfg.hd * itemsize)
            state = jax.eval_shape(lambda: M.init_cache(cfg, None, 1, 0))
            self._state_bytes = sum(
                int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
                for x in jax.tree.leaves(state))
        budget = (retired_kv_bytes if retired_kv_bytes is not None
                  else self._lane_bytes * max_slots)
        self._max_retired = budget // self._lane_bytes if self._lane_bytes else 0
        self.retired: OrderedDict[int, int] = OrderedDict()   # slot -> token count
        self.prefix_index = PrefixCacheIndex(max_nodes=prefix_index_nodes)
        self.decode_steps = 0
        self.pool_grows = 0
        self.reused_tokens = 0                     # admission tokens implanted, not computed
        self.prefilled_tokens = 0                  # admission tokens actually computed
        self.absorbed_tokens = 0                   # tool tokens teacher-forced (extend)
        self.prefill_dispatches = 0                # chunk kernel launches
        self.spans = SpanTotals()                  # host spans (engine/spans.py)
        # measured decode timing (feeds WorkerLatencyModel calibration, §6): the
        # ``decode`` span of WARM calls only — a call that grew the jit cache
        # spent seconds compiling, and meshed workers pay per-mesh compiles that
        # un-meshed ones share, so compile-polluted samples would make mp>1
        # look slower than it is.  wall_s / timed_steps is the observed
        # per-STEP decode time (the full-pool masked kernel's cost is
        # batch-independent; one step advances every live lane one token), and
        # timed_lane_steps / timed_steps is the mean live batch the model's
        # comm/interference term regresses on.
        self.decode_wall_s = 0.0
        self.decode_timed_steps = 0
        self.decode_timed_lane_steps = 0
        self.decode_calls = 0

    def _place_cache(self, cache):
        """Place a cache pytree on this worker's sub-mesh (identity un-meshed).

        THE one path for cache placement: mixing a default-device-committed
        cache with sharded params/pool in one jit is rejected (committed arrays
        on disjoint device sets), so every cache that enters the worker —
        construction, fresh lanes, pool growth, migration ingress — funnels
        through here."""
        if self.mesh is None:
            return cache
        return jax.device_put(cache, cache_shardings(cache, self.mesh))

    def _new_lane(self):
        """Empty batch-1 lane, placed on this worker's mesh when it has one.

        The jitted ``_fresh_lane`` commits its output to the default device,
        which is only safe when the worker is un-meshed (see _place_cache)."""
        if self.mesh is None:
            return _fresh_lane(self.cfg, 1, self.capacity)
        return self._place_cache(M.init_cache(self.cfg, None, 1, self.capacity))

    # ------------------------------------------------------------ slot bookkeeping
    def _alloc_slot(self) -> int:
        """Lowest free lane, else the LRU retired lane, else pool growth (doubling).

        The returned lane is about to be overwritten, so its radix refs are
        invalidated here — one rule covers release, eviction, and external resets.
        In paged mode the reclaimed lane's pages are freed (shared blocks survive
        via their sharers' refcounts) and its page-table row reset to scratch."""
        used = {s.slot for s in self.store.values()}
        for slot in range(self.max_slots):
            if slot not in used and slot not in self.retired:
                self.prefix_index.invalidate(slot)
                if self._paged:
                    self._free_lane_pages(slot)
                return slot
        if self.retired:
            slot, _ = self.retired.popitem(last=False)
            self.prefix_index.invalidate(slot)
            if self._paged:
                self._free_lane_pages(slot)
            return slot
        slot = self.max_slots
        if self._paged:
            # lane growth only: page-table rows + dense per-lane state double,
            # the physical block pools are untouched (lanes and HBM decouple)
            self.pool = self._place_cache(
                M.grow_paged_lanes(self.cfg, self.pool, self.max_slots))
        else:
            fresh = self._place_cache(
                M.init_cache(self.cfg, None, self.max_slots, self.capacity))
            # re-pin after the eager concat, which drops the sharding
            self.pool = self._place_cache(M.concat_pools(self.pool, fresh))
        self.max_slots *= 2
        self.pool_grows += 1
        self.prefix_index.invalidate(slot)
        return slot

    def _retire_slot(self, slot: int, n_tokens: int) -> None:
        """Hand a released lane to the radix cache (LRU, byte-budgeted).

        Paged: the lane's over-allocated tail pages (decode headroom past the
        last resident token) are freed immediately — a retired lane holds
        exactly ceil(n_tokens / page_size) blocks."""
        if not (self._reuse and self._max_retired > 0 and n_tokens > 0):
            self.prefix_index.invalidate(slot)
            if self._paged:
                self._free_lane_pages(slot)
            return
        if self._paged:
            self._trim_lane_pages(slot, n_tokens)
        self.retired[slot] = n_tokens
        self.retired.move_to_end(slot)
        while len(self.retired) > self._max_retired:
            old, _ = self.retired.popitem(last=False)
            self.prefix_index.invalidate(old)
            if self._paged:
                self._free_lane_pages(old)

    # ------------------------------------------------------------ page bookkeeping
    def _row_of(self, blocks: list[int]) -> jnp.ndarray:
        """Fixed-shape (num_pages,) device row; unmapped tail -> scratch block 0."""
        row = np.zeros((self.num_pages,), np.int32)
        row[:len(blocks)] = blocks
        return jnp.asarray(row)

    def _sync_row(self, slot: int) -> None:
        """Mirror ``lane_pages[slot]`` into the device page table."""
        self.pool = _paged_row(self.pool, jnp.asarray(slot, jnp.int32),
                               self._row_of(self.lane_pages.get(slot, [])))

    def _free_lane_pages(self, slot: int) -> None:
        """Release every page a lane holds and point its row at scratch."""
        blocks = self.lane_pages.pop(slot, None)
        if blocks:
            self.pages.free(blocks)
            self._sync_row(slot)

    def _trim_lane_pages(self, slot: int, n_tokens: int) -> None:
        """Free pages past ceil(n_tokens / page_size) (retire headroom trim)."""
        blocks = self.lane_pages.get(slot, [])
        keep = -(-n_tokens // self.page_size)
        if len(blocks) > keep:
            self.pages.free(blocks[keep:])
            self.lane_pages[slot] = blocks[:keep]
            self._sync_row(slot)

    def _alloc_blocks(self, n: int) -> list[int]:
        """Allocate ``n`` physical blocks, evicting retired lanes under pressure
        and doubling the device block pool only once nothing is left to reclaim."""
        while True:
            try:
                return self.pages.alloc(n)
            except PagePoolExhausted:
                if self.retired:
                    old, _ = self.retired.popitem(last=False)
                    self.prefix_index.invalidate(old)
                    self._free_lane_pages(old)
                    continue
                self._grow_blocks(n)

    def _grow_blocks(self, min_extra: int) -> None:
        extra = max(min_extra, self.num_blocks)     # doubling growth
        self.pool = self._place_cache(M.grow_paged_blocks(self.pool, extra))
        self.pages.grow(self.num_blocks + extra)
        self.num_blocks += extra
        self.block_grows += 1

    def _ensure_coverage(self, slot: int, total_tokens: int) -> None:
        """Map enough pages on lane ``slot`` to hold ``total_tokens`` positions
        (capped at lane capacity — past it, writes self-heal into scratch)."""
        need = min(-(-total_tokens // self.page_size), self.num_pages)
        have = self.lane_pages.get(slot, [])
        if len(have) >= need:
            return
        self.lane_pages[slot] = have + self._alloc_blocks(need - len(have))
        self._sync_row(slot)

    # ------------------------------------------------------------ lifecycle
    def prefill(self, seq_id: int, tokens: list[int]) -> None:
        """Admit a sequence: implant any radix-matched prefix from a resident or
        retired lane (O(1) on-device slice copy), then chunk-prefill the suffix.

        Each step of admission runs in a span of its own, children of
        ``prefill`` (docs/engine.md, "Spans and counters")."""
        S = len(tokens)
        span = partial(self.spans.span, seq_id=seq_id)
        with span("prefill"):
            with span("radix_match"):
                reuse_n, src = 0, None
                if self._reuse:
                    reuse_n, src = self.prefix_index.match_lane(tokens)
                else:
                    self.prefix_index.match_len(tokens)
            with span("map_pages"):
                slot = self._alloc_slot()
                if self._paged:
                    reuse_n = self._map_paged(slot, S, reuse_n, src)
            if self._paged and self._chunked:
                if reuse_n < S:
                    with span("chunk_dispatch"):
                        self._chunk_into_paged(slot, tokens, reuse_n)
                self.prefilled_tokens += S - reuse_n
            elif self._paged:
                arr = jnp.asarray(tokens, jnp.int32)[None]
                self.pool = _admit_paged(self.cfg, self.params, self.pool, arr, slot,
                                         self._row_of(self.lane_pages[slot]), S,
                                         mesh=self.mesh)
                self.prefilled_tokens += S
            elif not self._chunked:
                arr = jnp.asarray(tokens, jnp.int32)[None]
                self.pool = _admit(self.cfg, self.params, self.pool, arr, slot,
                                   self.capacity, mesh=self.mesh)
                self.prefilled_tokens += S
            else:
                lane = self._new_lane()
                if src is not None and reuse_n > 0:
                    if src in self.retired:
                        self.retired.move_to_end(src)     # LRU touch
                    lane = _copy_prefix(self.pool, jnp.asarray(src, jnp.int32), lane,
                                        jnp.asarray(reuse_n, jnp.int32))
                    self.reused_tokens += reuse_n
                if reuse_n < S:
                    with span("chunk_dispatch"):
                        lane = self._chunk_into(lane, tokens, reuse_n)
                self.pool = _implant(self.pool, lane, slot)
                self.prefilled_tokens += S - reuse_n
            with span("seq_key"):
                key = np.asarray(jax.random.fold_in(self.base_key, seq_id))
            self.store[seq_id] = Sequence(seq_id, list(tokens), slot, key)
            with span("radix_insert"):
                self.prefix_index.insert(tokens, slot=slot)

    def _map_paged(self, slot: int, S: int, reuse_n: int, src: int | None) -> int:
        """Map lane ``slot``'s pages for an ``S``-token admission: share the
        matched prefix's full pages by refcount (zero KV copy), D2D-copy its
        boundary partial page, and map fresh pages for the suffix.  Returns the
        positions the lane already holds, where the chunk prefill starts.

        Warm GRPO siblings therefore pay page-table rows + O(suffix) compute —
        the dense path's O(reuse_n) lane-slice copy disappears entirely."""
        ps = self.page_size
        blocks: list[int] = []
        boundary: tuple[int, int] | None = None
        reuse_eff = 0
        if self._chunked and src is not None and reuse_n > 0:
            if src in self.retired:
                self.retired.move_to_end(src)             # LRU touch
            src_blocks = self.lane_pages.get(src, [])
            reuse_eff = min(reuse_n, len(src_blocks) * ps)
            n_full = reuse_eff // ps
            if n_full:
                blocks = list(src_blocks[:n_full])
                self.pages.share(blocks)
            if reuse_eff % ps:
                [b] = self._alloc_blocks(1)
                boundary = (b, src_blocks[n_full])
                blocks.append(b)
            self.reused_tokens += reuse_eff
        need = min(-(-S // ps), self.num_pages)
        if need > len(blocks):
            blocks = blocks + self._alloc_blocks(need - len(blocks))
        self.lane_pages[slot] = blocks
        self.pool = _paged_lane(self.pool, jnp.asarray(slot, jnp.int32),
                                self._row_of(blocks),
                                jnp.asarray(reuse_eff, jnp.int32))
        if boundary is not None:
            self.pool = _copy_block(self.pool, jnp.asarray(boundary[0], jnp.int32),
                                    jnp.asarray(boundary[1], jnp.int32))
        return reuse_eff

    def _chunk_into(self, lane, tokens: list[int], start: int):
        """Feed ``tokens[start:]`` through the fixed-shape chunk kernel."""
        C = self.chunk_size
        off, S = start, len(tokens)
        while off < S:
            step = min(C, S - off)
            buf = np.zeros((1, C), np.int32)
            buf[0, :step] = tokens[off:off + step]
            lane = _prefill_chunk(self.cfg, self.params, lane, jnp.asarray(buf),
                                  jnp.asarray(step, jnp.int32), mesh=self.mesh)
            off += step
            self.prefill_dispatches += 1
        return lane

    def _chunk_into_paged(self, slot: int, tokens: list[int], start: int) -> None:
        """Feed ``tokens[start:]`` straight into lane ``slot``'s pages — no
        lane gather/implant round trip; the pool is the chunk kernel's operand."""
        C = self.chunk_size
        off, S = start, len(tokens)
        while off < S:
            step = min(C, S - off)
            buf = np.zeros((1, C), np.int32)
            buf[0, :step] = tokens[off:off + step]
            self.pool = _paged_chunk(self.cfg, self.params, self.pool,
                                     jnp.asarray(slot, jnp.int32),
                                     jnp.asarray(buf),
                                     jnp.asarray(step, jnp.int32), mesh=self.mesh)
            off += step
            self.prefill_dispatches += 1

    def extend(self, seq_id: int, tool_tokens: list[int]) -> None:
        """Absorb tool output: chunked prefill into the lane at its current offset
        (ceil(L/C) lane-sized dispatches instead of L full-pool decode steps)."""
        seq = self.store[seq_id]
        if self._paged and self._chunked:
            ext = list(seq.tokens) + [int(t) for t in tool_tokens]
            self._ensure_coverage(seq.slot, len(ext))
            self._chunk_into_paged(seq.slot, ext, len(seq.tokens))
            self.absorbed_tokens += len(tool_tokens)
            seq.tokens = ext
        elif self._chunked:
            lane = _gather_lane(self.pool, jnp.asarray(seq.slot, jnp.int32))
            ext = list(seq.tokens) + [int(t) for t in tool_tokens]
            lane = self._chunk_into(lane, ext, len(seq.tokens))
            self.pool = _implant(self.pool, lane, seq.slot)
            self.absorbed_tokens += len(tool_tokens)
            seq.tokens = ext
        else:
            self.extend_per_token(seq_id, tool_tokens)
            return
        self.prefix_index.insert(seq.tokens, slot=seq.slot)

    def extend_per_token(self, seq_id: int, tool_tokens: list[int]) -> None:
        """Legacy tool absorption: one masked full-pool decode step per token.

        Kept as the fallback for non-chunkable configs and as the baseline
        ``benchmarks/bench_prefill.py`` measures the chunked path against."""
        seq = self.store[seq_id]
        if self._paged:
            self._ensure_coverage(seq.slot, len(seq.tokens) + len(tool_tokens))
        arr = jnp.asarray(tool_tokens, jnp.int32)
        self.pool = _extend_slot(self.cfg, self.params, self.pool, arr, seq.slot,
                                 mesh=self.mesh)
        self.absorbed_tokens += len(tool_tokens)
        seq.tokens.extend(int(t) for t in tool_tokens)
        self.prefix_index.insert(seq.tokens, slot=seq.slot)

    def decode(self, seq_ids: list[int], n_tokens: int, stop_token: int | None = None
               ) -> dict[int, list[int]]:
        """Batched decode of the requested resident sequences for ``n_tokens`` steps.

        Runs one fused device loop over the whole pool; lanes not requested (free,
        preempted, or co-resident but idle) ride along masked-out at frozen ``pos``.
        Requesting a preempted sequence implicitly resumes it (mask flip back).
        A sequence whose ``finished`` flag is set is never resumed: it stays
        masked-out at frozen ``pos`` and contributes an empty output stream, so a
        scheduler naming a stopped sequence cannot push tokens past its stop token.
        """
        requested = []
        for sid in seq_ids:
            if self.store[sid].finished:
                continue
            requested.append(sid)
        if not requested:
            return {sid: [] for sid in seq_ids}
        B = self.max_slots
        last = np.zeros((B,), np.int32)
        live = np.zeros((B,), bool)
        keys = np.zeros((B, 2), np.uint32)
        for seq in self.store.values():
            last[seq.slot] = seq.tokens[-1]
            keys[seq.slot] = seq.key
        for sid in requested:
            seq = self.store[sid]
            seq.preempted = False
            live[seq.slot] = True
            if self._paged:
                # map decode headroom up front: the loop writes positions
                # [len(tokens), len(tokens) + n_tokens) — one host-side check,
                # zero device syncs inside the loop (unused tail pages are
                # trimmed back at retire time)
                self._ensure_coverage(seq.slot, len(seq.tokens) + n_tokens)
        last, live, keys = jnp.asarray(last), jnp.asarray(live), jnp.asarray(keys)
        # without a stop token nothing can finish early: one fused dispatch; with one,
        # chunk so the loop exits once every requested lane has stopped
        chunk = n_tokens if stop_token is None else _DECODE_CHUNK
        parts = []
        remaining = n_tokens
        ran = 0
        lane_steps = 0
        cache0 = _decode_loop._cache_size()
        # the span ends once the emitted tokens are on the host: that transfer
        # is the sync that holds the device time of every step dispatched
        with self.spans.span("decode", lanes=len(requested)) as span:
            while remaining > 0:
                step = min(chunk, remaining)
                self.pool, last, live, em = _decode_loop(
                    self.cfg, self.params, self.pool, last, live, keys,
                    step, stop_token, self.sampler, mesh=self.mesh)
                parts.append(em)   # device-resident: D2H deferred past the loop
                remaining -= step
                ran += step
                self.decode_steps += step
                if stop_token is None:                          # nothing stops early
                    lane_steps += step * len(requested)
                else:
                    # live batch after the chunk: lanes stopping mid-call must not
                    # keep inflating the calibration's mean-batch regressor.  The
                    # sync is the point — it is the early-exit check that stops
                    # decoding once every requested lane hit its stop token.
                    n_live = int(np.asarray(live).sum())  # heddle: noqa HDL003 -- deliberate early-exit sync, one per chunk
                    lane_steps += step * n_live
                    if remaining > 0 and n_live == 0:
                        break
            emitted = (np.concatenate([np.asarray(p) for p in parts], axis=0)
                       if parts else np.zeros((0, B), np.int32))  # n_tokens == 0 edge
        if _decode_loop._cache_size() == cache0:            # warm: no compile inside
            self.decode_wall_s += span.ns * 1e-9
            self.decode_timed_steps += ran
            self.decode_timed_lane_steps += lane_steps
        self.decode_calls += 1
        out: dict[int, list[int]] = {sid: [] for sid in seq_ids}
        for sid in requested:
            seq = self.store[sid]
            toks = [int(t) for t in emitted[:, seq.slot] if t >= 0]
            out[sid] = toks
            seq.tokens.extend(toks)
            seq.generated += len(toks)
            if stop_token is not None and toks and toks[-1] == stop_token:
                seq.finished = True
            self.prefix_index.insert(seq.tokens, slot=seq.slot)
        return out

    # ------------------------------------------------------------ control ops
    def preempt(self, seq_id: int) -> None:
        """Evict from the running batch but persist the KV cache (Alg. 1 line 7).

        A pure mask flip: the lane stays resident at frozen ``pos``; the next
        ``decode()`` naming this sequence flips the mask back — zero data movement."""
        self.store[seq_id].preempted = True

    def release(self, seq_id: int) -> None:
        """Finish a sequence; its lane retires into the radix cache's LRU set
        (prefix stays implantable) until admission pressure or the byte budget
        reclaims it."""
        seq = self.store.pop(seq_id, None)
        if seq is not None:
            self._retire_slot(seq.slot, len(seq.tokens))

    def _package_meta(self, seq: Sequence, preempted: bool, finished: bool) -> dict:
        return {
            "seq_id": seq.seq_id,
            "tokens": list(seq.tokens),
            "generated": seq.generated,
            "key": np.asarray(seq.key),
            # lifecycle flags travel with the lane: a trajectory preempted before a
            # tool-interval migration must arrive preempted, not active
            "preempted": preempted,
            "finished": finished,
        }

    def _gather_resident(self, seq: Sequence) -> tuple[dict, dict, list[int], int]:
        """Pages + dense state of one paged lane, trimmed to resident tokens."""
        keep = -(-len(seq.tokens) // self.page_size)
        blocks = self.lane_pages.get(seq.slot, [])[:keep]
        pages = _gather_pages(self.pool, jnp.asarray(blocks, jnp.int32))
        state = M.paged_gather_state(self.pool, seq.slot)
        logical = len(blocks) * self._page_bytes + self._state_bytes
        return pages, state, blocks, logical

    def migrate_out(self, seq_id: int) -> dict:
        """Package one lane's context + cache for transfer (§5.3 KV migration).

        Gathers a single lane — co-resident sequences are untouched.  The local
        copy retires into the radix cache, so group siblings arriving later still
        find the shared prefix here.

        Paged workers package *device-resident* page stacks trimmed to the
        lane's resident tokens: a same-process move is block copies device to
        device, never a host bounce, and ``logical_bytes`` prices exactly the
        resident pages + dense state so the controller/simulator cost model
        stops charging full-lane bytes."""
        seq = self.store.pop(seq_id)
        if self._paged:
            pages, state, blocks, logical = self._gather_resident(seq)
            pkg = self._package_meta(seq, seq.preempted, seq.finished)
            pkg.update(pages=pages, state=state, page_size=self.page_size,
                       capacity=self.capacity, logical_bytes=logical)
            self._retire_slot(seq.slot, len(seq.tokens))
            return pkg
        lane = M.gather_slots(self.pool, np.asarray([seq.slot]))
        self._retire_slot(seq.slot, len(seq.tokens))
        pkg = self._package_meta(seq, seq.preempted, seq.finished)
        pkg["cache"] = jax.tree.map(np.asarray, lane)  # heddle: noqa HDL005 -- dense fallback pool has no page table; the host bounce is its only transport
        pkg["logical_bytes"] = sum(x.nbytes
                                   for x in jax.tree.leaves(pkg["cache"]))
        return pkg

    def checkpoint_out(self, seq_id: int) -> dict:
        """Host-gather one lane WITHOUT evicting it (tool-boundary checkpoint).

        Same package format as :meth:`migrate_out`, but the live lane keeps
        running here — the copy is a recovery source for the fault layer
        (``migrate_in`` on a survivor re-implants it after a worker death).
        Lifecycle flags are snapshotted clean: a restore always re-admits the
        trajectory parked at its tool boundary, never mid-preemption.

        The checkpoint must survive this worker's device dying, so the paged
        payload is host-gathered here — the one legitimate host bounce in the
        migration family (``logical_bytes`` still prices resident pages only,
        identical to the D2D package for the same lane)."""
        seq = self.store[seq_id]
        if self._paged:
            pages, state, blocks, logical = self._gather_resident(seq)
            pkg = self._package_meta(seq, False, False)
            pkg.update(
                pages=jax.tree.map(np.asarray, pages),  # heddle: noqa HDL005 -- checkpoint copy must outlive the source device
                state=jax.tree.map(np.asarray, state),  # heddle: noqa HDL005 -- checkpoint copy must outlive the source device
                page_size=self.page_size, capacity=self.capacity,
                logical_bytes=logical)
            return pkg
        lane = M.gather_slots(self.pool, np.asarray([seq.slot]))
        pkg = self._package_meta(seq, False, False)
        pkg["cache"] = jax.tree.map(np.asarray, lane)  # heddle: noqa HDL005 -- checkpoint copy must outlive the source device (dense fallback)
        pkg["logical_bytes"] = sum(x.nbytes
                                   for x in jax.tree.leaves(pkg["cache"]))
        return pkg

    def _ingest_pages(self, package: dict, slot: int) -> None:
        """Land a paged package: allocate blocks, D2D-scatter the page stacks."""
        pages, state = package["pages"], package["state"]
        n = next(iter(jax.tree.leaves(pages))).shape[1] if pages else 0
        blocks = self._alloc_blocks(n) if n else []
        self.lane_pages[slot] = blocks
        if self.mesh is not None:             # re-shard for THIS worker's sub-mesh
            pages = jax.device_put(pages, cache_shardings(pages, self.mesh))
            state = self._place_cache(state)
        self.pool = _paged_ingest(self.pool, pages,
                                  jnp.asarray(blocks, jnp.int32), state,
                                  jnp.asarray(slot, jnp.int32),
                                  self._row_of(blocks))

    def migrate_in(self, package: dict) -> None:
        """Implant a migrated lane into a free slot (capacities must match).

        Four ingress layouts meet here: a paged package landing on a paged
        worker with the same page size scatters its blocks device-to-device; a
        paged package on a mismatched/dense worker is flattened back to a lane
        (``model.pages_to_lane`` — the cross-degree fallback); a dense package
        on a paged worker scatters through ``model.paged_write_lane``; and the
        dense-to-dense path is the original lane implant.  Implanting re-shards
        for THIS worker's mesh, so migration crosses MP degrees — an mp=4 lane
        lands correctly on an mp=1 pool and vice versa."""
        slot = self._alloc_slot()
        n_tokens = len(package["tokens"])
        if "pages" in package:
            if (self._paged and package.get("page_size") == self.page_size
                    and package.get("capacity") == self.capacity):
                self._ingest_pages(package, slot)
                self._register_seq(package, slot)
                return
            # layout mismatch: flatten the pages back into a dense lane
            cache = M.pages_to_lane(package["pages"], package["state"],
                                    self.capacity)
        else:
            cache = package["cache"]

        def check(dst, src):                  # fail fast on capacity/arch mismatch
            if (dst.shape[0],) + dst.shape[2:] != (src.shape[0],) + src.shape[2:]:
                raise ValueError(
                    f"migrate_in: lane shape {src.shape} does not fit pool lane "
                    f"{dst.shape} — source and destination workers must share "
                    f"capacity and architecture")

        if not self._paged:
            jax.tree.map(check, self.pool["blocks"], cache["blocks"])
        if self.mesh is not None:             # host -> this worker's sub-mesh
            lane = self._place_cache(cache)
        else:
            lane = jax.tree.map(jnp.asarray, cache)
        if self._paged:
            need = min(-(-n_tokens // self.page_size), self.num_pages)
            blocks = self._alloc_blocks(need)
            self.lane_pages[slot] = blocks
            self.pool = _paged_implant(self.pool, lane,
                                       jnp.asarray(slot, jnp.int32),
                                       self._row_of(blocks),
                                       jnp.asarray(n_tokens, jnp.int32))
        else:
            self.pool = _implant(self.pool, lane, slot)
        self._register_seq(package, slot)

    def _register_seq(self, package: dict, slot: int) -> None:
        key = package.get("key")
        if key is None:                                     # foreign package: re-key
            key = np.asarray(jax.random.fold_in(self.base_key, package["seq_id"]))
        seq = Sequence(package["seq_id"], list(package["tokens"]), slot,
                       np.asarray(key), generated=package["generated"],
                       preempted=package.get("preempted", False),
                       finished=package.get("finished", False))
        self.store[package["seq_id"]] = seq
        self.prefix_index.insert(seq.tokens, slot=slot)

    # ------------------------------------------------------------ accounting
    def kv_bytes(self, seq_id: int) -> int:
        """Per-lane cache footprint.

        Dense pools report the fixed lane shape (``jax.eval_shape`` at
        construction, stable across growth).  Paged lanes report *resident*
        pages + dense state — the number that actually gates admission."""
        assert seq_id in self.store
        if self._paged:
            slot = self.store[seq_id].slot
            return (len(self.lane_pages.get(slot, [])) * self._page_bytes
                    + self._state_bytes)
        return self._lane_bytes

    def reset_cache(self) -> None:
        """Drop every resident and retired lane and all radix refs.

        Required on weight sync (RL loop): retired KV computed under old weights
        must never be implanted into post-update admissions.  Paged lanes free
        their blocks through the pool's normal accounting (conservation stats
        stay consistent); rows are reset to scratch lazily at reallocation."""
        if self._paged:
            for slot in list(self.lane_pages):
                self._free_lane_pages(slot)
        self.store.clear()
        self.retired.clear()
        self.prefix_index = PrefixCacheIndex(
            max_nodes=self.prefix_index.max_nodes)

    def dispatch_stats(self) -> dict:
        """Measured admission/reuse counters for the control plane (§3 telemetry).

        The controller aggregates these into ``measured_reuse_rate`` so placement
        and the simulator's cache model consume observed hit rates, not assumed
        ones."""
        idx = self.prefix_index
        stats = {}
        if self._paged:
            # page-pool occupancy watermarks + the block-conservation feed
            # (TraceSanitizer checks allocated == freed + resident + shared at
            # drain; serve.py surfaces the watermarks in the run report)
            stats = {"blocks_" + k: v for k, v in self.pages.stats().items()}
            stats["page_size"] = self.page_size
            stats["block_grows"] = self.block_grows
        return {
            **stats,
            "reused_tokens": self.reused_tokens,
            "prefilled_tokens": self.prefilled_tokens,
            "absorbed_tokens": self.absorbed_tokens,
            "prefill_dispatches": self.prefill_dispatches,
            "full_hits": idx.full_hits,
            "partial_hits": idx.partial_hits,
            "lookups": idx.lookups,
            "hit_tokens": idx.hit_tokens,
            "retired_lanes": len(self.retired),
            "decode_steps": self.decode_steps,
            "pool_grows": self.pool_grows,
            # §6 calibration feed: declared MP degree + measured decode timing
            # (warm calls only), consumed by calibration_observations()
            "mp": self.mp,
            "decode_wall_s": self.decode_wall_s,
            "decode_timed_steps": self.decode_timed_steps,
            "decode_timed_lane_steps": self.decode_timed_lane_steps,
            "decode_calls": self.decode_calls,
            **self.spans.stats(),
        }
