"""Operations and bytes against hand-computed shapes."""

import pytest

from bench import flops as F

# d 8, 4 heads of 2 over 2 KV heads, ff 16, vocab 10, 3 layers
SPEC = {"hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 2, "intermediate_size": 16, "vocab_size": 10,
        "num_hidden_layers": 3}


def test_matmul_flops_per_token():
    # q 8x4x2, k and v 8x2x2, o 4x2x8, mlp 3 x 8x16 -> 64+32+32+64+384 = 576 MACs
    assert F.matmul_flops_token(SPEC, head=False) == 3 * 2 * 576
    assert F.matmul_flops_token(SPEC, head=True) == 3 * 2 * 576 + 2 * 8 * 10


def test_attention_counts_valid_keys_only():
    # one lane, 3 steps from a 5-token cache: 6 + 7 + 8 keys
    keys = 6 + 7 + 8
    assert F.decode_flops(SPEC, 1, 3, 5) == (3 * F.matmul_flops_token(SPEC, True)
                                            + 4 * 4 * 2 * keys * 3)
    # two lanes of 5 and 9 positions, one step: 6 + 10 keys
    assert F.decode_flops(SPEC, 2, 1, 14) == (2 * F.matmul_flops_token(SPEC, True)
                                             + 4 * 4 * 2 * 16 * 3)


def test_fill_counts_positions_from_start():
    # positions 2, 3, 4 attend 3, 4, 5 keys; no head
    assert F.fill_flops(SPEC, 2, 5) == (3 * F.matmul_flops_token(SPEC, False)
                                        + 4 * 4 * 2 * 12 * 3)
    assert F.fill_flops(SPEC, 4, 4) == 0


def test_paged_attention_bytes():
    flops, nbytes = F.paged_attn_need(SPEC, 2, 1, 14)
    # K and V of 16 keys x 2 KV heads x 2 dims x 2 bytes, q and out of 4x2 per
    # lane, per layer
    assert nbytes == (2 * 16 * 2 * 2 * 2 + 2 * 2 * 4 * 2 * 2) * 3
    assert flops == pytest.approx(4 * 4 * 2 * 16 * 3)
