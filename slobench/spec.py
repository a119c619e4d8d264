"""Cells, configurations, traffic mixes and metric readers, found by name.

``BENCHMARK.json`` at the root of the checkout names each cell's
configuration (a JSON file under ``slobench/configs/``) and traffic mix (a
JSON file under ``slobench/traffic/``). Each metric is read by a module of its
own: ``slobench/e2e/<name>.py`` for end-to-end metrics and
``slobench/layers/<name>.py`` for per-layer metrics, each with a
``read(run)`` function. A configuration names its model family and its
plain reference, each a module under ``slobench/configs/`` (see
:func:`family`). Nothing here knows a cell or a model by name.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent
CONFIGS = HERE / "configs"


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
    return _module(path).read


@functools.lru_cache(maxsize=None)
def _module(path: Path):
    """The module in the file ``path``, loaded once."""
    mod_spec = importlib.util.spec_from_file_location(
        f"slobench.{path.parent.name}.{path.stem.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def max_context(conf: dict) -> int:
    """Longest prompt + output + decode reserve the deployment admits."""
    eng = conf["engine"]
    return eng["max_request_tokens"] + eng["decode_reserve_tokens"]


def config_module(conf: dict, key: str):
    """The module ``slobench/configs/<conf[key]>.py``."""
    if key not in conf:
        raise KeyError(f"configuration {conf.get('name')!r} has no {key!r} "
                       f"key naming a module under slobench/configs/")
    path = CONFIGS / f"{conf[key]}.py"
    if not path.is_file():
        raise FileNotFoundError(f"configuration {conf.get('name')!r}: no "
                                f"{key} module {path}")
    return _module(path)


def family(conf: dict):
    """The configuration's model family, ``slobench/configs/<family>.py``:
    ``model_config(conf)``, the program's ``ModelConfig``; ``shapes(conf)``,
    the table of tensors ``weights.make`` draws (name -> (shape, kind,
    std)); ``engine_params(conf, w, offsets)``, the engine's parameter tree
    made of them; ``step_flops(conf, kind, meta)``, the model FLOPs of one
    dispatch; and ``attention(conf, kind, meta)``, the (flops, bytes) of its
    attention kernel summed over every layer of one dispatch. Only the
    family and the reference read the model's own keys."""
    return config_module(conf, "family")
