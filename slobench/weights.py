"""Random weights of a dense Qwen3 decoder, made on the device from the seed.

One jitted call draws every tensor in bfloat16 (the type it is served in) and
returns two views of the same matrices:

* ``w``: the benchmark's own naming, after Hugging Face's ``Qwen3ForCausalLM``
  (per-layer tensors stacked on a leading layer axis; matrices stored
  ``[in, out]`` so that ``y = x @ w``; RMSNorm gains ``g`` multiply the
  normalised input). The plain reference reads only this view.
* ``params``: the tree the serving engine loads (``repro.models.model``),
  which holds each RMSNorm gain as an offset from one. The gains are drawn
  in ``[0.5, 1.9]``, where ``g - 1`` is exact in bfloat16, so both views
  compute with the same numbers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

GAIN_STD = 0.1


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (wider than 32 bits
    too), by way of numpy's SeedSequence."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def shapes(conf: dict) -> dict:
    """name -> (shape, kind) with kind "matrix" or "gain"."""
    L, d = conf["num_hidden_layers"], conf["hidden_size"]
    H, Hkv, D = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 conf["head_dim"])
    F, V = conf["intermediate_size"], conf["vocab_size"]
    return {
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


def make(conf: dict, seed: int):
    """``(w, params)`` for the configuration file ``conf`` (see the module
    docstring), made in one jitted call on the default device."""
    if not conf["tie_word_embeddings"]:
        raise ValueError(f"{conf['name']}: untied LM head not drawn here")
    std = float(conf["initializer_range"])
    table = shapes(conf)

    @jax.jit
    def draw(key):
        w = {}
        for i, (name, (shape, kind)) in enumerate(sorted(table.items())):
            k = jax.random.fold_in(key, i)
            if kind == "matrix":
                w[name] = (jax.random.normal(k, shape, jnp.float32)
                           * std).astype(jnp.bfloat16)
            else:
                g = 1.0 + GAIN_STD * jax.random.normal(k, shape, jnp.float32)
                w[name] = jnp.clip(g, 0.5, 1.9).astype(jnp.bfloat16)
        offsets = {n: w[n] - jnp.bfloat16(1.0)
                   for n, (_, kind) in table.items() if kind == "gain"}
        return w, offsets

    w, off = draw(seed_key(seed))
    attn = {"ln": off["in_norm"], "wq": w["q"], "wk": w["k"], "wv": w["v"],
            "wo": w["o"], "q_norm": off["q_norm"], "k_norm": off["k_norm"]}
    ffn = {"ln": off["post_norm"], "wi_gate": w["gate"], "wi_up": w["up"],
           "wo": w["down"]}
    params = {"embed": w["embed"], "final_norm": off["final_norm"],
              "stacks": [[{"attn": attn, "ffn": ffn}]]}
    return w, params


def check_layout(cfg, params) -> None:
    """Raise unless ``params`` has exactly the engine's parameter tree
    (names, shapes and dtypes of ``repro.models.model.init_params``)."""
    from repro.models.model import init_params
    want = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("benchmark weights do not match the engine's "
                         "parameter tree")
