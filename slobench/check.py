"""Whether the timed path served the right tokens.

Once the window has closed and the engine's state is freed, a sample of the
requests the run finished, drawn from the seed and always holding the
longest one (and one that was evicted and resumed, where any was), is run
through the configuration's plain reference, teacher-forced on its prompt
and served tokens. The number compared is the widest gap, over every served
token of the sample, by which its reference logit lies below the reference's
best logit at that position. Greedy serving that computes what the reference
computes, only rounded to bfloat16, keeps that gap small; a wrong token, a
wrong page, a wrong position or arithmetic in a lower precision widens it.

The precision control reads the same gap for the token that the reference
computed in float8 puts first, at the same positions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from slobench import spec

SAMPLE_TOKENS = 384      # served tokens compared per run, at least
SAMPLE_MAX = 12          # requests in the sample, at most
ROWS = 1024              # positions read per sequence (outputs are shorter)
SEQ_STEP = 2048          # reference sequences are padded to a multiple


def pick(finished: list, seed: int) -> list:
    """A sample of ``finished`` [(prompt, tokens, evictions)]: the longest
    (prompt + output), the most evicted if any was, then others drawn from
    the seed until SAMPLE_TOKENS served tokens or SAMPLE_MAX requests."""
    if not finished:
        return []
    idx = list(range(len(finished)))
    chosen = [max(idx, key=lambda i: len(finished[i][0]) + len(finished[i][1]))]
    ev = max(idx, key=lambda i: finished[i][2])
    if finished[ev][2] > 0 and ev not in chosen:
        chosen.append(ev)
    rng = np.random.default_rng(int(seed) + 1)
    for i in rng.permutation(len(finished)):
        if (sum(len(finished[j][1]) for j in chosen) >= SAMPLE_TOKENS
                or len(chosen) >= SAMPLE_MAX):
            break
        if int(i) not in chosen:
            chosen.append(int(i))
    return [finished[i] for i in chosen]


def _padded(prompt, tokens):
    """(sequence padded to a multiple of SEQ_STEP, rows, served, m)."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(tokens[:-1], np.int32)])
    T = -(-len(seq) // SEQ_STEP) * SEQ_STEP
    m = len(tokens)
    if m > ROWS:
        raise ValueError(f"{m} served tokens, more than {ROWS}")
    M = ROWS
    full = np.zeros(T, np.int32)
    full[:len(seq)] = seq
    rows = np.full(M, len(prompt) - 1, np.int32)
    rows[:m] += np.arange(m, dtype=np.int32)
    served = np.zeros(M, np.int32)
    served[:m] = tokens
    return full, rows, served, m


@jax.jit
def _gaps(ref, served):
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    return best - got


def gaps(conf: dict, w, sample: list, control: bool = False) -> tuple:
    """Per served token of the sample, the gap by which its reference logit
    lies below the reference's best at its position; with ``control``, also
    the gap of the token that the float8 reference puts first at the same
    position. Returns ``(program, control or None)`` as arrays."""
    ref_mod = spec.config_module(conf, "reference")
    prog, ctrl = [], []
    for prompt, tokens, _ in sample:
        full, rows, served, m = _padded(prompt, tokens)
        ref = ref_mod.logits(w, conf, full, rows)
        prog.append(np.asarray(_gaps(ref, jnp.asarray(served)))[:m])
        if control:
            low = ref_mod.logits(w, conf, full, rows, quant="fp8")
            first = jnp.argmax(low, axis=-1).astype(jnp.int32)
            ctrl.append(np.asarray(_gaps(ref, first))[:m])
    cat = lambda v: np.concatenate(v) if v else np.zeros(0)
    return cat(prog), (cat(ctrl) if control else None)


def widest(g: np.ndarray) -> float:
    """The number compared: the widest gap (inf where any is not finite)."""
    if not np.all(np.isfinite(g)):
        return float("inf")
    return float(g.max()) if g.size else 0.0


def summary(g: np.ndarray) -> dict:
    """Readings of a gap array beside the widest, for the record."""
    if not g.size or not np.all(np.isfinite(g)):
        return {"widest": widest(g)}
    return {"widest": float(g.max()), "p99": float(np.quantile(g, .99)),
            "mean": float(g.mean()), "off_top1": float(np.mean(g > 0))}
