"""Operations and bytes of the model's step and of its attention kernels.

Counted from shapes and lengths, as the algorithm needs them: padding rows
and tokens do no useful work and are not counted, and each K/V page is read
once. bf16 operands are 2 bytes.
"""
from __future__ import annotations

import numpy as np

BF16 = 2


def dims(conf: dict) -> dict:
    return {"L": conf["num_hidden_layers"], "d": conf["hidden_size"],
            "H": conf["num_attention_heads"],
            "Hkv": conf["num_key_value_heads"], "D": conf["head_dim"],
            "F": conf["intermediate_size"], "V": conf["vocab_size"]}


def linear_params_per_layer(conf: dict) -> int:
    k = dims(conf)
    d, H, Hkv, D, F = k["d"], k["H"], k["Hkv"], k["D"], k["F"]
    return d * H * D + 2 * d * Hkv * D + H * D * d + 3 * d * F


def decode_attention(conf: dict, lengths) -> tuple:
    """(flops, bytes) of one paged decode attention call (one layer) over
    rows whose context lengths are ``lengths`` (0 = padding row)."""
    k = dims(conf)
    L = np.asarray(lengths, np.int64)
    L = L[L > 0]
    flops = 4 * k["H"] * k["D"] * int(L.sum())
    kv = 2 * k["Hkv"] * k["D"] * BF16 * int(L.sum())
    qo = 2 * k["H"] * k["D"] * BF16 * len(L)
    return flops, kv + qo


def prefill_attention(conf: dict, row_pos, row_lens) -> tuple:
    """(flops, bytes) of one paged prefill attention call (one layer): row i
    computes queries at positions ``row_pos[i] .. row_lens[i] - 1``, each
    attending causally to every earlier position."""
    k = dims(conf)
    pos = np.asarray(row_pos, np.int64)
    lens = np.asarray(row_lens, np.int64)
    n = lens - pos
    keep = n > 0
    pos, lens, n = pos[keep], lens[keep], n[keep]
    keys = int((n * pos + n * (n + 1) // 2).sum())
    flops = 4 * k["H"] * k["D"] * keys
    kv = 2 * k["Hkv"] * k["D"] * BF16 * int(lens.sum())
    qo = 2 * k["H"] * k["D"] * BF16 * int(n.sum())
    return flops, kv + qo


def step_flops(conf: dict, kind: str, meta) -> int:
    """Model FLOPs of one dispatch: every layer's matrix products and
    attention for its real tokens, and the LM head for each row's one
    sampled position. ``meta`` is the decode lengths, or the chunk's
    ``[row_pos, row_lens]``."""
    k = dims(conf)
    per_token = 2 * linear_params_per_layer(conf) * k["L"]
    head = 2 * k["d"] * k["V"]
    if kind == "decode":
        lens = np.asarray(meta, np.int64)
        rows = int((lens > 0).sum())
        attn = decode_attention(conf, lens)[0]
        return per_token * rows + k["L"] * attn + head * rows
    pos, lens = meta
    n = np.asarray(lens, np.int64) - np.asarray(pos, np.int64)
    rows = int((n > 0).sum())
    attn = prefill_attention(conf, pos, lens)[0]
    return per_token * int(n[n > 0].sum()) + k["L"] * attn + head * rows


def roofline_s(flops: int, nbytes: int, peak: dict) -> float:
    """Least seconds the chip needs for the work: the larger of compute and
    memory time at the device's peaks."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_s"])
