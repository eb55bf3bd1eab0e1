"""The served-token comparison reads the program's sampler on its own terms."""

import jax
import jax.numpy as jnp
import numpy as np

from bench import correct as C


def test_gumbel_reconstruction_reproduces_the_sampler():
    """The token the program's sampler draws from (key, pos) is the one
    ``first_choice`` puts first, and its served gap is zero."""
    from repro.engine.sampler import SamplerConfig, sample_slots

    B, V, T, top_p = 6, 512, 0.8, 0.9
    logits = jax.random.normal(jax.random.PRNGKey(1), (B, V)) * 2.0
    base = jax.random.PRNGKey(77 + 1)
    keys = jnp.stack([jax.random.fold_in(base, sid) for sid in range(B)])
    pos = jnp.arange(100, 100 + B)
    step_keys = jax.vmap(jax.random.fold_in)(keys, pos)
    toks = sample_slots(step_keys, logits, SamplerConfig(temperature=T, top_p=top_p))
    z = logits / T
    ours = jnp.stack([C.first_choice(z[b:b + 1], step_keys[b:b + 1], top_p)[0]
                      for b in range(B)])
    assert np.array_equal(np.asarray(toks), np.asarray(ours))
    gaps = C.served_gaps(z, toks, step_keys, 0.5, top_p)
    assert float(jnp.max(gaps)) == 0.0


def test_altered_tokens_read_a_gap():
    V, top_p = 512, 0.9
    z = jax.random.normal(jax.random.PRNGKey(2), (8, V))
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        jax.random.PRNGKey(3), jnp.arange(8, dtype=jnp.uint32))
    good = C.first_choice(z, keys, top_p)
    bad = (good + 1) % V
    assert float(jnp.max(C.served_gaps(z, bad, keys, 0.5, top_p))) > 1.0
    unused = jnp.full((8,), -1, jnp.int32)
    assert float(jnp.max(C.served_gaps(z, unused, keys, 0.5, top_p))) == 0.0


def test_seq_key_is_the_workers():
    """The key a worker gives a sequence it admits (``RolloutWorker.prefill``)."""
    from repro.engine.worker import RolloutWorker
    from repro.models import model as M
    from repro.models.config import ModelConfig

    cfg = ModelConfig(name="k", arch_type="dense", d_model=8, n_heads=2, n_kv_heads=2,
                      d_ff=8, vocab=16, block_pattern=("attn+mlp",), n_periods=1,
                      dtype="float32")
    w = RolloutWorker(cfg, M.init_params(cfg, jax.random.PRNGKey(0)), capacity=16,
                      max_slots=1, worker_id=1, seed=40)
    w.prefill(9, [1, 2, 3])
    assert np.array_equal(w.store[9].key, np.asarray(C.seq_key(40, 1, 9)))
