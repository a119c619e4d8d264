"""FLOP and byte counters against hand counts: the dense Qwen3 family's,
reached through the configuration's ``family`` as the harness reaches them."""
from __future__ import annotations

from slobench import rooflines, spec

CONF = {"family": "qwen3_dense", "num_hidden_layers": 2, "hidden_size": 8,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 2,
        "intermediate_size": 16, "vocab_size": 32}
L = CONF["num_hidden_layers"]


def test_decode_attention_by_hand():
    # rows of 3 and 5 tokens, one padding row; H=4, Hkv=2, D=2; every one
    # of the L layers is the same attention
    flops, nbytes = spec.family(CONF).attention(CONF, "decode", [3, 5, 0])
    assert flops == L * 4 * 4 * 2 * (3 + 5)             # QK and PV, 2 flops/MAC
    kv = 2 * 2 * 2 * 2 * (3 + 5)                        # K,V x Hkv x D x 2 B
    qo = 2 * 4 * 2 * 2 * 2                              # q and out, 2 rows
    assert nbytes == L * (kv + qo)


def test_prefill_attention_by_hand():
    # row 0: queries at 0..3 (keys 1+2+3+4 = 10); row 1: queries 4..5 of a
    # 6-token context (keys 5+6 = 11); row 2 is padding
    flops, nbytes = spec.family(CONF).attention(CONF, "chunk", ([0, 4, 0], [4, 6, 0]))
    assert flops == L * 4 * 4 * 2 * (10 + 11)
    kv = 2 * 2 * 2 * 2 * (4 + 6)
    qo = 2 * 4 * 2 * 2 * (4 + 2)
    assert nbytes == L * (kv + qo)


def test_step_flops_by_hand():
    d, H, Hkv, D, F, V, L = 8, 4, 2, 2, 16, 32, 2
    per_layer = d * H * D + 2 * d * Hkv * D + H * D * d + 3 * d * F
    assert spec.family(CONF).linear_params_per_layer(CONF) == per_layer
    got = spec.family(CONF).step_flops(CONF, "decode", [3, 0])
    assert got == 2 * per_layer * L + L * 4 * H * D * 3 + 2 * d * V
    got = spec.family(CONF).step_flops(CONF, "chunk", ([0, 0], [4, 0]))
    assert got == 2 * per_layer * L * 4 + L * 4 * H * D * 10 + 2 * d * V


def test_roofline_takes_the_larger_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_s": 10.0}
    assert rooflines.roofline_s(1000, 10, peak) == 10.0
    assert rooflines.roofline_s(100, 50, peak) == 5.0
