"""Plain reference of a dense Qwen3 decoder (Hugging Face ``Qwen3ForCausalLM``).

The forward pass as published, in ``jax.numpy`` and float32 with every
matrix product at ``Precision.HIGHEST``: token embedding; per layer
``x += o(attn(rope(norm_q(q(n1(x)))), rope(norm_k(k(n1(x)))), v(n1(x))))``
with grouped-query causal softmax attention, then
``x += down(silu(gate(n2(x))) * up(n2(x)))``; a final RMSNorm and the LM
head: the embedding's transpose where ``tie_word_embeddings`` holds, else
its own matrix ``lm_head``. RMSNorm is ``x / sqrt(mean(x^2) + eps) * g``; RoPE rotates the two
halves of each head (``rotate_half``) at ``rope_theta``. No cache, no pages,
no kernels, no batching: one sequence at a time, teacher-forced, attention
computed in blocks of queries so that long sequences fit beside the weights.
Weights are the benchmark's own (``qwen3_dense.shapes`` naming), read in
their served bfloat16 and widened layer by layer.

``quant="fp8"`` is the precision control: every linear layer (the LM head
too) multiplies operands rounded to float8 e4m3, weights scaled per output
column and activations per token; the rest is unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
E4M3_MAX = 448.0


def _fp8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _linear(x, w, quant):
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _rope(x, pos, theta):
    """x [T, heads, D]; Hugging Face rotate_half convention."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    half = D // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def _attention(q, k, v):
    """Causal GQA softmax attention, queries in blocks of Q_BLOCK.
    q [T, H, D]; k, v [T, Hkv, D]."""
    T, H, D = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    B = min(Q_BLOCK, T)
    qb = q.reshape(T // B, B, Hkv, G, D)
    keys = jnp.arange(T)

    def block(args):
        i, qi = args
        s = jnp.einsum("qhgd,khd->hgqk", qi, k, precision=HI) * D ** -0.5
        rows = i * B + jnp.arange(B)
        s = jnp.where(keys[None, :] <= rows[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI)

    out = jax.lax.map(block, (jnp.arange(T // B), qb))
    return out.reshape(T, H, D)


@functools.partial(jax.jit, static_argnames=("conf_items", "quant"))
def _forward(w, tokens, rows, conf_items, quant):
    conf = dict(conf_items)
    eps, theta = conf["rms_norm_eps"], float(conf["rope_theta"])
    H, Hkv, D = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 conf["head_dim"])
    T = tokens.shape[0]
    pos = jnp.arange(T)
    x = w["embed"][tokens].astype(jnp.float32)
    layers = {n: w[n] for n in ("in_norm", "q", "k", "v", "q_norm", "k_norm",
                                "o", "post_norm", "gate", "up", "down")}

    def layer(x, p):
        h = _rms(x, p["in_norm"], eps)
        q = _linear(h, p["q"], quant).reshape(T, H, D)
        k = _linear(h, p["k"], quant).reshape(T, Hkv, D)
        v = _linear(h, p["v"], quant).reshape(T, Hkv, D)
        q = _rope(_rms(q, p["q_norm"], eps), pos, theta)
        k = _rope(_rms(k, p["k_norm"], eps), pos, theta)
        x = x + _linear(_attention(q, k, v).reshape(T, H * D), p["o"], quant)
        h = _rms(x, p["post_norm"], eps)
        a = jax.nn.silu(_linear(h, p["gate"], quant)) * _linear(h, p["up"],
                                                                  quant)
        return x + _linear(a, p["down"], quant), None

    x, _ = jax.lax.scan(layer, x, layers)
    x = _rms(x[rows], w["final_norm"], eps)
    head = w["embed"].T if conf["tie_word_embeddings"] else w["lm_head"]
    return _linear(x, head, quant)


def logits(w, conf: dict, tokens, rows, quant=None):
    """float32 logits ``[len(rows), vocab]`` of the next token after each
    position in ``rows``, for the sequence ``tokens`` (its length a multiple
    of ``Q_BLOCK``; positions past the real sequence are never read)."""
    items = tuple(sorted((k, v) for k, v in conf.items()
                         if isinstance(v, (int, float, str, bool))))
    return _forward(w, tokens, rows, conf_items=items, quant=quant)
