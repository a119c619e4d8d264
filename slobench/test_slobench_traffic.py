"""The traffic generator: determinism per seed, the same work for every
seed, and Table 2's moments."""
from __future__ import annotations

import json

import numpy as np

from slobench import spec, traffic

MIX = json.loads((spec.HERE / "traffic" /
                  "mixed-v1.overload.qwen3-1.7b.json").read_text())


def lengths(arrs):
    return sorted((len(a.prompt), a.max_output, a.component) for a in arrs)


def test_same_seed_same_inputs():
    a = traffic.make(MIX, 20.0, 2 ** 31 + 5, 1000)
    b = traffic.make(MIX, 20.0, 2 ** 31 + 5, 1000)
    assert [x.offset for x in a] == [x.offset for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_every_seed_gets_the_same_work_in_another_order():
    a = traffic.make(MIX, 20.0, 1, 1000)
    b = traffic.make(MIX, 20.0, 2, 1000)
    assert lengths(a) == lengths(b)
    ga, gb = np.diff([0] + [x.offset for x in a]), np.diff(
        [0] + [x.offset for x in b])
    assert np.allclose(sorted(ga), sorted(gb))
    assert [x.offset for x in a] != [x.offset for x in b]


def test_even_order_is_a_permutation_with_even_prefixes():
    for n in (1, 2, 5, 157, 256, 1000):
        for seed in (0, 2 ** 31 + 7):
            o = traffic.even_order(n, np.random.default_rng(seed))
            assert sorted(o.tolist()) == list(range(n))
    o = traffic.even_order(256, np.random.default_rng(9))
    for j in range(9):      # the first 2**j ranks: one in each 2**j slice
        k = 2 ** j
        assert sorted(o[:k] // (256 // k)) == list(range(k))


def test_outputs_are_not_paired_by_size():
    a = traffic.make(MIX, 100.0, 4, 1000)
    share = [x for x in a if x.component == 0]
    p = np.array([len(x.prompt) for x in share], float)
    o = np.array([x.max_output for x in share], float)
    assert abs(np.corrcoef(np.argsort(np.argsort(p)),
                           np.argsort(np.argsort(o)))[0, 1]) < 0.3


def test_table2_moments():
    for comp in MIX["components"]:
        for key in ("prompt", "output"):
            d = comp[key]
            x = traffic.quantile_lengths(d, 4000)
            assert abs(x.mean() - d["mean"]) / d["mean"] < 0.05, (comp, key)
            p90 = np.quantile(x, 0.9)
            assert abs(p90 - d["p90"]) / d["p90"] < 0.05, (comp, key)


def test_rate_and_share():
    mix = dict(MIX, rate_rps=5.0)
    a = traffic.make(mix, 100.0, 3, 1000)
    span = mix["lead_in_s"] + 100.0 + mix["lead_out_s"]
    assert len(a) == round(5.0 * span)
    assert abs(a[-1].offset - span) / span < 0.1
    share = sum(x.component == 1 for x in a) / len(a)
    assert abs(share - 0.25) < 0.01


def test_limits_follow_the_table():
    mix = dict(MIX, exclusive_prefill_s=[[16, 0.1], [1016, 1.1]])
    assert abs(traffic.exclusive_prefill_s(mix, 516) - 0.6) < 1e-9
    assert abs(traffic.exclusive_prefill_s(mix, 2016) - 2.1) < 1e-9
    arr = traffic.Arrival(0.0, np.zeros(516, np.int32), 4, 0)
    ttft, tbt, cls = traffic.limits(mix, arr)
    assert abs(ttft - 5.0 * 0.6) < 1e-9 and tbt == 0.040
    assert cls == "dialogue"
