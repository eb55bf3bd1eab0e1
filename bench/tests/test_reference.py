"""The plain reference against the program's model at a reduced size."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.configs import qwen3, qwen3_reference as ref
from bench.tests.tiny import SPEC
from bench.weights import make_params


@pytest.mark.parametrize("tied", [True, False])
def test_reference_matches_model_forward(tied):
    from repro.models import model as M

    spec = dict(SPEC, tie_word_embeddings=tied)
    cfg = dataclasses.replace(qwen3.program_config("tiny", spec), dtype="float32")
    params = make_params(cfg, 3)
    params = jax.tree.map(lambda x: x * 8 if x.ndim >= 2 else x, params)
    toks = np.random.default_rng(0).integers(0, spec["vocab_size"], 40)
    with jax.default_matmul_precision("highest"):
        want = M.forward_full(cfg, params, {"tokens": jnp.asarray(toks)[None]})[0][0]
    h = ref.final_hidden(spec, params, toks, "f32")
    got = ref.logits(spec, params, h, "f32")
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 1.0
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * scale


def test_lower_precisions_differ_from_f32():
    cfg = qwen3.program_config("tiny", SPEC)
    params = make_params(cfg, 3)
    toks = np.arange(5, 45)
    h = {m: ref.logits(SPEC, params, ref.final_hidden(SPEC, params, toks, m), m)
         for m in ref.MODES}
    d_bf16 = float(jnp.max(jnp.abs(h["bf16"] - h["f32"])))
    d_int8 = float(jnp.max(jnp.abs(h["int8"] - h["f32"])))
    assert 0 < d_bf16 and 0 < d_int8
