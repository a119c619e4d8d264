"""A whole run of a tiny cell on the CPU, with the chip check skipped: sound,
with a token altered where the engine produces it, and the precision
control in the program's place. Each must come out as the check says."""
from __future__ import annotations

import json
from unittest import mock

import numpy as np
import pytest

from slobench import check, spec

SECONDS = 4.0
LIMIT = 0.2                  # CPU readings 0.03-0.05; fp8 control 0.45-1.4
SEED = 2 ** 31 + 11          # wider than 32 signed bits


def tiny_cell(config: str = "qwen3-1.7b", **keys):
    """A tiny cell of the configuration file ``config``, with ``keys``
    changed last."""
    from slobench.run import Cell
    conf = json.loads((spec.HERE / "configs" / f"{config}.json").read_text())
    conf.update(name="tiny", hidden_size=64, intermediate_size=128,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                num_hidden_layers=2, vocab_size=512, initializer_range=0.2)
    conf.update(keys)
    conf["engine"] = dict(conf["engine"], max_request_tokens=320)
    conf["kv_pool_tokens"] = 2048
    conf["check"] = {"max_logit_gap": LIMIT, "min_tokens": 16}
    mix = json.loads((spec.HERE / "traffic" /
                      "mixed-v1.overload.qwen3-1.7b.json").read_text())
    for c in mix["components"]:
        c["prompt"].update(mean=c["prompt"]["mean"] // 20,
                           p90=c["prompt"]["p90"] // 20, max=128)
        c["output"].update(mean=8, p90=14, max=24)
    mix.update(rate_rps=3.0, lead_in_s=1.0, lead_out_s=5.0,
               exclusive_prefill_s=[[16, 0.05], [256, 0.3]],
               warm={"decode": [[rb, nb] for rb in (1, 2, 4, 8, 16)
                                for nb in (8, 16)],
                     "chunk": [[rb, lb, nb] for rb in (1, 2, 4, 8)
                               for lb in (16, 32, 64, 128)
                               for nb in (1, 2, 4, 8) if 16 * nb >= lb]})
    e2e = [{"name": n, "unit": "x"} for n in ("output_tok_s", "setup_s")]
    return Cell("tiny.cell", 1, conf, mix, e2e, [])


def tiny_run(cell=None, trace: bool = False, **kw):
    from slobench.run import run_cell
    with mock.patch.object(check, "SEQ_STEP", 256):
        return run_cell(cell or tiny_cell(), SEED, SECONDS, trace,
                        need_chip=False, persistent_cache=False, **kw)


def test_sound_run_is_correct_and_precision_control_is_not():
    res = tiny_run()
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"output_tok_s", "setup_s"}
    # the float8 reference in the program's place, judged by the run itself
    ctl = tiny_run(control=True)
    assert not ctl["correct"], ctl["checks"]
    assert ctl["program_gap"] <= LIMIT
    assert ctl["checks"]["logit_gap"]["value"] > LIMIT


def test_token_altered_where_produced_is_not_correct():
    from repro.serving.engine import EngineCore
    real = EngineCore._readback

    def altered(self, arr):
        vals = np.array(real(self, arr))
        vals[0] = (vals[0] + 1) % self.cfg.vocab_size
        return vals

    with mock.patch.object(EngineCore, "_readback", altered):
        res = tiny_run()
    assert not res["correct"], res["checks"]


def test_refuses_without_a_tpu():
    from slobench.run import Refused, run_cell
    with pytest.raises(Refused):
        run_cell(tiny_cell(), 1, 1.0, False, persistent_cache=False)


def test_traced_run_labels_every_phase_as_it_ends(capsys):
    bench = spec.load_benchmark()
    cell = tiny_cell()
    cell.per_layer = spec.metrics_for(bench, bench["workloads"][0]["name"],
                                      "per_layer")
    res = tiny_run(cell, trace=True)
    labels = [json.loads(line) for line in capsys.readouterr().out.splitlines()
              if line.startswith('{"label": "phase"')]
    assert [x["phase"] for x in labels] == [
        "start", "weights", "build", "warm", "lead_in", "window", "lead_out",
        "trace_load", "read", "check"]
    assert all(x["seconds"] >= 0 for x in labels)
    assert res["correct"], res["checks"]
    assert res["device"]["busy_s"] > 0 and "breakdown" in res
