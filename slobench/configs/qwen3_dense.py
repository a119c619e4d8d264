"""Model family of a dense Qwen3 decoder (Hugging Face ``Qwen3ForCausalLM``).

A configuration file whose ``"family"`` is ``"qwen3_dense"`` holds the
model's published ``config.json`` keys; the harness reaches the model only
through the functions of this module:

* :func:`model_config`: the program's ``ModelConfig``;
* :func:`shapes`: the tensors that ``slobench/weights.py`` draws from the
  seed, in the benchmark's own naming, which the plain reference
  (``qwen3_dense_ref.py``) reads;
* :func:`engine_params`: those tensors as the engine's parameter tree;
* :func:`step_flops` and :func:`attention`: the work of one dispatch, for
  ``mfu`` and the attention kernels' roofline shares.

Counts are taken from shapes and lengths, as the algorithm needs them:
padding rows and tokens do no useful work and are not counted, and each K/V
page is read once. bf16 operands are 2 bytes.
"""
from __future__ import annotations

import numpy as np

BF16 = 2
GAIN_STD = 0.1           # RMSNorm gains: 1 + normal(0, 0.1), clipped


def model_config(conf: dict):
    """The program's ``ModelConfig``; raises for keys that would make the
    model something other than a dense Qwen3 decoder."""
    import jax.numpy as jnp

    from repro.configs.base import ATTN, DENSE, ModelConfig
    unsupported = {"attention_bias": False, "use_sliding_window": False,
                   "rope_scaling": None}
    for key, want in unsupported.items():
        if conf.get(key, want) != want:
            raise ValueError(f"{conf['name']}: {key}={conf[key]!r} is not "
                             f"a dense Qwen3 decoder")
    if conf["model_type"] != "qwen3":
        raise ValueError(f"{conf['name']}: model_type {conf['model_type']!r}")
    dtype = {"bfloat16": jnp.bfloat16}[conf["torch_dtype"]]
    return ModelConfig(
        name=conf["name"], family="dense",
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        layer_pattern=(ATTN,), ffn_pattern=(DENSE,),
        rope_theta=float(conf["rope_theta"]), qk_norm=True,
        tie_embeddings=conf["tie_word_embeddings"],
        norm_eps=conf["rms_norm_eps"], activation=conf["hidden_act"],
        dtype=dtype, param_dtype=dtype)


def shapes(conf: dict) -> dict:
    """name -> (shape, kind, std): per-layer tensors stacked on a leading
    layer axis, matrices stored ``[in, out]`` so that ``y = x @ w``, every
    matrix normal(0, ``initializer_range``); an untied LM head is
    ``lm_head``."""
    L, d = conf["num_hidden_layers"], conf["hidden_size"]
    H, Hkv, D = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 conf["head_dim"])
    F, V = conf["intermediate_size"], conf["vocab_size"]
    std = float(conf["initializer_range"])
    table = {
        "embed": ((V, d), "matrix"),
        "in_norm": ((L, d), "gain"),
        "q": ((L, d, H * D), "matrix"),
        "k": ((L, d, Hkv * D), "matrix"),
        "v": ((L, d, Hkv * D), "matrix"),
        "q_norm": ((L, D), "gain"),
        "k_norm": ((L, D), "gain"),
        "o": ((L, H * D, d), "matrix"),
        "post_norm": ((L, d), "gain"),
        "gate": ((L, d, F), "matrix"),
        "up": ((L, d, F), "matrix"),
        "down": ((L, F, d), "matrix"),
        "final_norm": ((d,), "gain"),
    }
    if not conf["tie_word_embeddings"]:
        table["lm_head"] = ((d, V), "matrix")
    return {n: (shape, kind, std if kind == "matrix" else GAIN_STD)
            for n, (shape, kind) in table.items()}


def engine_params(conf: dict, w: dict, offsets: dict) -> dict:
    """The tree ``repro.models.model.init_params`` makes for this model,
    holding the drawn matrices and each RMSNorm gain as its offset from
    one."""
    attn = {"ln": offsets["in_norm"], "wq": w["q"], "wk": w["k"],
            "wv": w["v"], "wo": w["o"], "q_norm": offsets["q_norm"],
            "k_norm": offsets["k_norm"]}
    ffn = {"ln": offsets["post_norm"], "wi_gate": w["gate"],
           "wi_up": w["up"], "wo": w["down"]}
    params = {"embed": w["embed"], "final_norm": offsets["final_norm"],
              "stacks": [[{"attn": attn, "ffn": ffn}]]}
    if not conf["tie_word_embeddings"]:
        params["lm_head"] = w["lm_head"]
    return params


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
    """(flops, bytes) of one layer's paged decode attention over rows whose
    context lengths are ``lengths`` (0 = padding row)."""
    k = dims(conf)
    L = np.asarray(lengths, np.int64)
    L = L[L > 0]
    flops = 4 * k["H"] * k["D"] * int(L.sum())
    kv = 2 * k["Hkv"] * k["D"] * BF16 * int(L.sum())
    qo = 2 * k["H"] * k["D"] * BF16 * len(L)
    return flops, kv + qo


def prefill_attention(conf: dict, row_pos, row_lens) -> tuple:
    """(flops, bytes) of one layer's paged prefill attention: row i computes
    queries at positions ``row_pos[i] .. row_lens[i] - 1``, each attending
    causally to every earlier position."""
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


def attention(conf: dict, kind: str, meta) -> tuple:
    """(flops, bytes) of the attention kernel over every layer of one
    dispatch: each layer is full causal GQA attention over the paged cache.
    ``meta`` is the decode lengths, or the chunk's ``[row_pos, row_lens]``."""
    if kind == "decode":
        f, b = decode_attention(conf, meta)
    else:
        f, b = prefill_attention(conf, meta[0], meta[1])
    layers = conf["num_hidden_layers"]
    return layers * f, layers * b


def step_flops(conf: dict, kind: str, meta) -> int:
    """Model FLOPs of one dispatch: every layer's matrix products and
    attention for its real tokens, and the LM head for each row's one
    sampled position."""
    k = dims(conf)
    per_token = 2 * linear_params_per_layer(conf) * k["L"]
    head = 2 * k["d"] * k["V"]
    attn = attention(conf, kind, meta)[0]
    if kind == "decode":
        rows = int((np.asarray(meta, np.int64) > 0).sum())
        return per_token * rows + attn + head * rows
    pos, lens = meta
    n = np.asarray(lens, np.int64) - np.asarray(pos, np.int64)
    rows = int((n > 0).sum())
    return per_token * int(n[n > 0].sum()) + attn + head * rows
