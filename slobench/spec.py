"""Cells, configurations, traffic mixes and metric readers, found by name.

``BENCHMARK.json`` at the root of the checkout names each cell's
configuration (a JSON file under ``slobench/configs/``) and traffic mix (a
JSON file under ``slobench/traffic/``). Each metric is read by a module of its
own: ``slobench/e2e/<name>.py`` for end-to-end metrics and
``slobench/layers/<name>.py`` for per-layer metrics, each with a
``read(run)`` function. Nothing here knows a cell by name.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent


def load_benchmark() -> dict:
    return json.loads((CHECKOUT / "BENCHMARK.json").read_text())


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_config_by_name(name: str, bench: dict | None = None) -> dict:
    bench = bench or load_benchmark()
    entry = find(bench["configs"], name, "configuration")
    return json.loads((CHECKOUT / entry["file"]).read_text())


def load_traffic(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def metrics_for(bench: dict, cell: str, kind: str) -> list:
    """The cell's metric entries of ``kind`` ("end_to_end" | "per_layer"): a
    metric with a ``workloads`` key is the cell's if it lists it; one
    without is every cell's (end-to-end) or that of every cell reporting the
    end-to-end metric it moves (per-layer)."""
    e2e = [m["name"] for m in metrics_for_e2e(bench, cell)]
    if kind == "end_to_end":
        return metrics_for_e2e(bench, cell)
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif m["moves"] in e2e:
            out.append(m)
    return out


def metrics_for_e2e(bench: dict, cell: str) -> list:
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]


def reader(kind: str, name: str):
    """The ``read`` function of ``slobench/<kind>/<name>.py``. A metric split
    by the end-to-end metric it moves, ``<base>.<part>``, is read by
    ``<base>.py`` where it has no file of its own."""
    path = HERE / kind / f"{name}.py"
    if not path.exists():
        path = HERE / kind / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(
        f"slobench.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def max_context(conf: dict) -> int:
    """Longest prompt + output + decode reserve the deployment admits."""
    eng = conf["engine"]
    return eng["max_request_tokens"] + eng["decode_reserve_tokens"]


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file that holds a
    Qwen3-style dense decoder's published ``config.json`` keys."""
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
