"""Qwen3 configurations: the published ``config.json`` keys, mapped to the program's
``ModelConfig``.  The plain reference of the family is ``qwen3_reference.py``."""

from __future__ import annotations

from repro.models.config import ModelConfig

# published keys whose value the program has no option for: each must hold as stated
FIXED = {"attention_bias": False, "hidden_act": "silu", "model_type": "qwen3",
         "rope_scaling": None, "torch_dtype": "bfloat16", "use_sliding_window": False}


def program_config(name: str, spec: dict) -> ModelConfig:
    """The program's configuration of ``spec``; raises where it cannot state it."""
    for key, want in FIXED.items():
        if spec.get(key) != want:
            raise ValueError(f"{name}: {key}={spec.get(key)!r}, the program runs {want!r}")
    return ModelConfig(
        name=name, arch_type="dense",
        d_model=spec["hidden_size"], n_heads=spec["num_attention_heads"],
        n_kv_heads=spec["num_key_value_heads"], head_dim=spec["head_dim"],
        d_ff=spec["intermediate_size"], vocab=spec["vocab_size"],
        block_pattern=("attn+mlp",), n_periods=spec["num_hidden_layers"],
        activation="swiglu", qk_norm=True, rope_theta=float(spec["rope_theta"]),
        norm="rmsnorm", tie_embeddings=bool(spec["tie_word_embeddings"]),
        norm_eps=float(spec["rms_norm_eps"]), dtype="bfloat16")
