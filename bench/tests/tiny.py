"""Cells small enough for the CPU: the qwen3 family at toy widths, short mixes."""

from __future__ import annotations

import copy
import json
from pathlib import Path

from bench.run import BENCH, Cell

ROOT = Path(__file__).resolve().parents[2]

SPEC = {
    "family": "qwen3", "attention_bias": False, "head_dim": 32, "hidden_act": "silu",
    "hidden_size": 128, "intermediate_size": 256, "model_type": "qwen3",
    "num_attention_heads": 4, "num_hidden_layers": 2, "num_key_value_heads": 2,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "tie_word_embeddings": True, "torch_dtype": "bfloat16",
    "use_sliding_window": False, "vocab_size": 512,
}

ROLLOUT = {
    "changes": {"n_prompts": 2, "group_size": 2, "max_steps": 3,
                "max_total_tokens": 40, "max_prompt": 24, "max_tool_tokens": 6,
                "history_prompts": 4, "max_active": 2, "quantum": 4},
    "limits": {"limits": {"logit_gap": 0.1, "logit_gap_mean": 0.002},
               "nucleus_margin": 0.5},
    "end_to_end": [{"name": n, "unit": u} for n, u in (
        ("rollout_tokens_per_s", "tokens/s"), ("turn_latency_p95_s", "s"),
        ("setup_s", "s"))],
}
# the toy model's bf16 reference reads about 0.01, its int8 one about 0.05
ADMIT = {
    "changes": {"n_prompts": 2, "group_size": 3, "max_prompt": 40},
    "limits": {"limits": {"kv_err": 0.025, "kv_err_mean": 0.01}},
    "end_to_end": [{"name": n, "unit": u} for n, u in (
        ("admit_tokens_per_s", "tokens/s"), ("setup_s", "s"))],
}


def tiny_cell(traffic: str = "agentic_grpo", **spec_changes) -> Cell:
    """A cell of the mix ``traffic`` at toy widths and a short length."""
    tr = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    kind = ADMIT if tr.get("serve") == "admit" else ROLLOUT
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec = copy.deepcopy(SPEC)
    spec.update(spec_changes)
    return Cell(f"tiny.{traffic}", 1, "tiny", spec, dict(tr, **kind["changes"]),
                kind["end_to_end"], bench["per_layer"], copy.deepcopy(kind["limits"]))


CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
