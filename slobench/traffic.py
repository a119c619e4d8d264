"""Open-loop traffic from a mix file under ``slobench/traffic/``.

The length model is the paper's Table 2 (copied from
``repro.serving.workloads``): each component's prompt and output lengths
follow a lognormal matched to the published mean and P90, or, where no
lognormal can match both (ShareGPT prompts), a two-lognormal mixture fitted
by moments. Arrivals are Poisson at the file's fixed rate.

Every seed gets the same work in another order, so that a seed changes
which requests meet which others and not how much there is to do. The
lengths of each component are the distribution's quantiles at
``(i + 0.5) / n``; each output length is paired with a prompt length by
one fixed shuffle (the source draws the two independently, and pairing
them in sorted order would give the longest prompt the longest output as
well); the gaps between arrivals are the exponential's quantiles.
``--seed`` shuffles the gaps, draws the prompts' token ids, and scrambles
the even order (:func:`even_order`) in which the requests, ranked by
length, arrive: every stretch of the stream holds about the same share of
short and long requests, so a window that serves only part of what
arrived (an overloaded engine) sees the same work under every seed.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np

Z90 = 1.2815515655446004
PAIRING_SEED = 0     # the one shuffle that pairs outputs with prompts


def lognormal_params(mean: float, p90: float):
    """(mu, sigma) matching mean and P90, or None if no lognormal can."""
    L = math.log(mean / p90)
    disc = Z90 * Z90 + 2 * L
    if disc < 0:
        return None
    sigma = Z90 - math.sqrt(disc)
    return math.log(mean) - sigma * sigma / 2, sigma


def mixture_params(mean: float, p90: float, sigma_s: float = 0.55,
                   sigma_l: float = 0.35):
    """(q, mu_s, sigma_s, mu_l, sigma_l): a short body and a long tail of
    weight q, fitted by scanning q and the tail's mean for the P90."""
    from math import erf, sqrt
    verf = np.vectorize(erf)
    xs = np.exp(np.linspace(math.log(4), math.log(30 * p90), 512))
    best = None
    for q in np.linspace(0.02, 0.20, 37):
        for m_l in np.linspace(p90, 4 * p90, 25):
            m_s = (mean - q * m_l) / (1 - q)
            if m_s <= 1:
                continue
            mu_s = math.log(m_s) - sigma_s ** 2 / 2
            mu_l = math.log(m_l) - sigma_l ** 2 / 2
            cdf = ((1 - q) * 0.5 * (1 + verf((np.log(xs) - mu_s)
                                             / (sigma_s * sqrt(2))))
                   + q * 0.5 * (1 + verf((np.log(xs) - mu_l)
                                         / (sigma_l * sqrt(2)))))
            err = abs(float(np.interp(0.9, cdf, xs)) - p90) / p90
            if best is None or err < best[0]:
                best = (err, q, mu_s, sigma_s, mu_l, sigma_l)
    return best[1:]


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """n lengths at the quantiles (i + 0.5) / n of the distribution fitted to
    ``dist`` ("mean", "p90"), clipped to ["min", "max"]."""
    from statistics import NormalDist
    u = (np.arange(n) + 0.5) / n
    ln = lognormal_params(dist["mean"], dist["p90"])
    if ln is not None:
        mu, sigma = ln
        x = np.exp(mu + sigma * np.array([NormalDist().inv_cdf(p) for p in u]))
    else:
        q, mu_s, sig_s, mu_l, sig_l = mixture_params(dist["mean"], dist["p90"])
        grid = np.exp(np.linspace(0.0, math.log(60 * dist["p90"]), 8192))
        nd = NormalDist()
        cdf = np.array([(1 - q) * nd.cdf((math.log(g) - mu_s) / sig_s)
                        + q * nd.cdf((math.log(g) - mu_l) / sig_l)
                        for g in grid])
        x = np.interp(u, cdf, grid)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


@dataclasses.dataclass
class Arrival:
    offset: float          # seconds after the traffic starts
    prompt: np.ndarray     # int32 token ids
    max_output: int
    component: int         # index into the mix's components


def split_counts(n: int, shares: List[float]) -> List[int]:
    """n split in proportion to shares, by largest remainder."""
    raw = np.asarray(shares, float) / sum(shares) * n
    out = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - out))[: n - out.sum()]:
        out[i] += 1
    return out.tolist()


def even_order(n: int, rng) -> np.ndarray:
    """A permutation of ``range(n)`` whose every prefix draws evenly on
    ``range(n)``: bit-reversed counting, scrambled by random flips of each
    bit that depend on the bits above it (Owen scrambling), so that the
    first ``2**j`` draws of the padded range hold one from each of its
    ``2**j`` equal slices whatever the flips."""
    bits = max((n - 1).bit_length(), 1)
    flips = rng.integers(0, 2, 1 << bits)
    out = []
    for i in range(1 << bits):
        r, node = 0, 1
        for j in range(bits):               # the most significant bit first
            b = (i >> j) & 1                # bit-reversed: i's lowest first
            r = (r << 1) | (b ^ int(flips[node]))
            node = 2 * node + b
        if r < n:
            out.append(r)
    return np.asarray(out)


def make(mix: dict, seconds: float, seed: int, vocab: int) -> List[Arrival]:
    """The arrivals of one run: ``lead_in_s + seconds + lead_out_s`` of
    traffic at ``rate_rps``, ordered by offset."""
    span = mix["lead_in_s"] + seconds + mix["lead_out_s"]
    n = max(int(round(mix["rate_rps"] * span)), 1)
    comps = mix["components"]
    counts = split_counts(n, [c["share"] for c in comps])
    work = []
    for ci, (c, k) in enumerate(zip(comps, counts)):
        if k:
            outs = quantile_lengths(c["output"], k)
            outs = outs[np.random.default_rng(PAIRING_SEED).permutation(k)]
            work += list(zip(quantile_lengths(c["prompt"], k), outs,
                             [ci] * k))
    work.sort()
    rng = np.random.default_rng(int(seed))
    order = even_order(len(work), rng)
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u) / mix["rate_rps"])
    offsets = np.cumsum(gaps)
    out = []
    for t, j in zip(offsets, order):
        p, o, ci = work[j]
        ids = rng.integers(1, vocab, int(p), dtype=np.int64).astype(np.int32)
        out.append(Arrival(float(t), ids, int(o), ci))
    return out


def exclusive_prefill_s(mix: dict, prompt_len: int) -> float:
    """Seconds to prefill ``prompt_len`` tokens on an idle engine, by linear
    interpolation in the mix file's table measured on the chip (linear
    extrapolation past its ends)."""
    pts = np.asarray(mix["exclusive_prefill_s"], float)
    x, y = pts[:, 0], pts[:, 1]
    if prompt_len <= x[0]:
        return float(y[0])
    if prompt_len >= x[-1]:
        slope = (y[-1] - y[-2]) / (x[-1] - x[-2])
        return float(y[-1] + slope * (prompt_len - x[-1]))
    return float(np.interp(prompt_len, x, y))


def limits(mix: dict, arrival: Arrival) -> tuple:
    """(ttft_limit_s, tbt_limit_s, slo_class) of one arrival."""
    c = mix["components"][arrival.component]
    slo = c["slo"]
    return (slo["ttft_slowdown"] * exclusive_prefill_s(mix, len(arrival.prompt)),
            slo["tbt_s"], slo["class"])
