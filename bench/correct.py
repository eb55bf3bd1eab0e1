"""How ``correct`` is decided: served tokens against the plain reference, and the
data plane's guarantees.

Served tokens are sampled (temperature and top-p), so the comparison is made on
the sampler's own terms.  The program draws token ``i`` of a sequence as the
argmax, over its top-p nucleus, of ``logit / T + g``, where ``g`` is Gumbel
noise from the key ``fold_in(fold_in(PRNGKey(seed + worker), traj), i)``.  The
benchmark draws the same noise from the same key, and reads the reference's
``z = logit_ref / T + g``.  A served token's gap is how far its ``z`` lies below
the best ``z`` of the reference's nucleus; for a sound program it is rounding.
Tokens within ``nucleus_margin`` (in units of logit / T) of the reference's
nucleus cut-off are left out of that best, since rounding may move them across
the cut-off.  The reference runs over each sampled trajectory's natural token
sequence: prompt, then each turn's generated tokens and its tool output.

Two numbers are read over a sample of trajectories: the widest gap of any served
token (``logit_gap``) and the mean gap over all of them (``logit_gap_mean``).  A
configuration states a limit for each number it compares (``correct.limits``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
CHUNK = 128                      # positions whose logits are held at once


def seq_key(runtime_seed: int, worker: int, traj_id: int):
    """Sampling key of a trajectory, derived as the program's worker derives it."""
    base = jax.random.PRNGKey(runtime_seed + worker)
    return jax.random.fold_in(base, traj_id)


def nucleus_cutoff(z, top_p: float):
    """Smallest scaled logit kept by top-p: the prefix of the sorted distribution
    whose mass first reaches ``top_p`` (the sampler's definition)."""
    srt = jnp.sort(z, axis=-1)[..., ::-1]
    cum = jnp.cumsum(jax.nn.softmax(srt, axis=-1), axis=-1)
    idx = jnp.argmax(cum >= top_p, axis=-1)
    return jnp.take_along_axis(srt, idx[..., None], axis=-1)


def _gumbel(keys, vocab: int):
    return jax.vmap(lambda k: jax.random.gumbel(k, (vocab,), F32))(keys)


@partial(jax.jit, static_argnames=("top_p",))
def served_gaps(z_ref, served, keys, margin, top_p: float):
    """Per position: best safe ``z + g`` of the reference minus the served token's.
    ``served`` is -1 where no served token is compared (gap 0)."""
    g = _gumbel(keys, z_ref.shape[-1])
    safe = z_ref >= nucleus_cutoff(z_ref, top_p) + margin
    zg = z_ref + g
    best = jnp.max(jnp.where(safe, zg, -jnp.inf), axis=-1)
    got = jnp.take_along_axis(zg, jnp.maximum(served, 0)[:, None], axis=-1)[:, 0]
    return jnp.where(served >= 0, jnp.maximum(best - got, 0.0), 0.0)


@partial(jax.jit, static_argnames=("top_p",))
def first_choice(z, keys, top_p: float):
    """The token a sampler with scaled logits ``z`` puts first under these keys."""
    g = _gumbel(keys, z.shape[-1])
    keep = z >= nucleus_cutoff(z, top_p)
    return jnp.argmax(jnp.where(keep, z + g, -jnp.inf), axis=-1).astype(jnp.int32)


def _keys(key, positions):
    return jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        key, jnp.asarray(positions, jnp.uint32))


def sequence_gap(ref, spec, params, rec, capacity: int, temperature: float,
                 top_p: float, margin: float, alt_modes=()) -> dict:
    """Widest gap and summed gap of one trajectory's served tokens, and of the
    tokens each ``alt_modes`` precision of the reference would put first at the
    same positions.  ``rec``: {"tokens", "served" (indices), "key"}."""
    padded = -(-capacity // CHUNK) * CHUNK
    toks = np.zeros((padded,), np.int32)
    n = len(rec["tokens"])
    toks[:n] = rec["tokens"]
    served = np.full((padded + 1,), -1, np.int32)
    idx = np.asarray(rec["served"], np.int64)
    served[idx] = np.asarray(rec["tokens"], np.int32)[idx]
    h = ref.final_hidden(spec, params, toks, "f32")
    alts = {m: ref.final_hidden(spec, params, toks, m) for m in alt_modes}
    out = {"n": int(idx.size),
           **{m: {"max": 0.0, "sum": 0.0} for m in ("served",) + tuple(alt_modes)}}

    def add(m, gaps):
        out[m]["max"] = max(out[m]["max"], float(jnp.max(gaps)))
        out[m]["sum"] += float(jnp.sum(gaps))

    for a in range(0, padded, CHUNK):
        tgt = np.arange(a + 1, a + CHUNK + 1)              # tokens these rows predict
        srv = served[tgt]
        srv = np.where(tgt < n, srv, -1)
        if not (srv >= 0).any():
            continue
        keys = _keys(rec["key"], tgt)
        z = ref.logits(spec, params, h[a:a + CHUNK], "f32") / temperature
        add("served", served_gaps(z, jnp.asarray(srv), keys, margin, top_p))
        for m, hm in alts.items():
            zm = ref.logits(spec, params, hm[a:a + CHUNK], m) / temperature
            pick = first_choice(zm, keys, top_p)
            pick = jnp.where(jnp.asarray(srv) >= 0, pick, -1)
            add(m, served_gaps(z, pick, keys, margin, top_p))
    return out


def gap_numbers(parts: list[dict], mode: str = "served") -> dict:
    """The numbers ``correct`` compares, over the served tokens of a sample of
    trajectories (``sequence_gap`` results): the widest gap, and the mean gap."""
    n = sum(p["n"] for p in parts)
    return {"logit_gap": max((p[mode]["max"] for p in parts), default=0.0),
            "logit_gap_mean": sum(p[mode]["sum"] for p in parts) / max(n, 1)}


def data_plane(batches: list[dict]) -> dict:
    """Counts of broken guarantees over the window: trajectories not FINISHED, steps
    whose generated tokens differ from their plan, and page blocks not returned
    to the pool at drain."""
    unfinished = wrong_steps = 0
    for b in batches:
        for t in b["trajectories"]:
            if not t.finished:
                unfinished += 1
            plan = t.payload.gen_tokens
            got = [len(x) for x in b["gens"].get(t.traj_id, [])]
            if got != list(plan):
                wrong_steps += max(len(plan), len(got))
    return {"unfinished": unfinished, "wrong_steps": wrong_steps,
            "leaked_blocks": leaked_blocks(batches)}


def leaked_blocks(batches: list[dict]) -> int:
    """Page blocks not returned to the pool once each batch was drained."""
    leaked = 0
    for b in batches:
        for s in b["drained"]:
            if "blocks_total" not in s:
                continue
            leaked += abs(s["blocks_allocated_total"] - s["blocks_freed_total"])
            leaked += s["blocks_resident"] + s["blocks_shared"]
            leaked += abs(s["blocks_total"] - s["blocks_free"])
    return leaked
