"""A configuration names its model family, and the harness reaches the model
only through it: the weights a seed gives stay bit for bit what they were,
an untied LM head is drawn, loaded and read, and a family added as new
files runs a whole cell with no other file changed."""
from __future__ import annotations

import hashlib
import json
import re
import shutil
from unittest import mock

import numpy as np
import pytest

from slobench import spec, weights
from slobench.test_slobench_run import SEED, tiny_cell, tiny_run

# sha256 (first 16 hex digits) of weights.make's (w, params), taken before
# the model moved from the harness into its family module: the tiny
# configuration of test_slobench_run.py, and one layer of each cell's
# configuration at its published widths (vocabulary 512)
DIGESTS = {
    ("tiny", "qwen3-1.7b", SEED): ("3009b11cb1af674f", "1f4121ed33963ca5"),
    ("tiny", "qwen3-1.7b", 0): ("344458613a99762c", "fe5c12a68e6b547b"),
    ("tiny", "qwen3-4b", SEED): ("3009b11cb1af674f", "1f4121ed33963ca5"),
    ("layer", "qwen3-1.7b", SEED): ("7f56fd26ca1f67c1", "5e76a65e02bac864"),
    ("layer", "qwen3-4b", SEED): ("ea095bf148fdc602", "50cf7a7f6fd7640f"),
}
HARNESS = ("spec", "weights", "rooflines", "run", "sweep", "fit", "layers/mfu")
MODEL_KEYS = ("model_type", "intermediate_size", "num_hidden_layers",
              "qk_norm", "hidden_size", "num_attention_heads", "head_dim",
              "tie_word_embeddings", "initializer_range")


def digest(tree) -> str:
    import jax
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, a in sorted(leaves, key=lambda p: jax.tree_util.keystr(p[0])):
        a = np.asarray(a)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("size,config,seed", sorted(DIGESTS, key=str))
def test_same_seed_gives_the_same_weights(size, config, seed):
    if size == "tiny":
        conf = tiny_cell(config).conf
    else:
        conf = json.loads((spec.CONFIGS / f"{config}.json").read_text())
        conf.update(num_hidden_layers=1, vocab_size=512)
    w, params = weights.make(conf, seed)
    assert (digest(w), digest(params)) == DIGESTS[size, config, seed]


@pytest.mark.parametrize("name", HARNESS)
def test_harness_names_no_key_of_the_model(name):
    text = (spec.HERE / f"{name}.py").read_text()
    found = [k for k in MODEL_KEYS if re.search(rf"[\"']{k}[\"']", text)]
    assert not found, f"slobench/{name}.py reads {found}"


@pytest.mark.parametrize("family,error", [(None, KeyError),
                                          ("no_such_family", FileNotFoundError)])
def test_family_is_named_and_never_guessed(family, error):
    conf = dict(tiny_cell().conf)
    conf.pop("family")
    if family:
        conf["family"] = family
    with pytest.raises(error):
        spec.family(conf)


def test_untied_head_is_drawn_loaded_and_read():
    cell = tiny_cell(tie_word_embeddings=False)
    w, params = weights.make(cell.conf, SEED)
    assert w["lm_head"].shape == (64, 512)
    assert params["lm_head"] is w["lm_head"]
    assert not np.array_equal(np.asarray(w["lm_head"]),
                              np.asarray(w["embed"]).T)
    res = tiny_run(cell)
    assert res["correct"], res["checks"]


def test_new_family_from_new_files_only(tmp_path):
    configs = tmp_path / "configs"
    configs.mkdir()
    shutil.copy(spec.CONFIGS / "qwen3_dense.py", configs / "qwen3_copy.py")
    shutil.copy(spec.CONFIGS / "qwen3_dense_ref.py",
                configs / "qwen3_copy_ref.py")
    cell = tiny_cell(family="qwen3_copy", reference="qwen3_copy_ref")
    with mock.patch.object(spec, "CONFIGS", configs):
        assert spec.family(cell.conf).__file__ == str(configs /
                                                      "qwen3_copy.py")
        res = tiny_run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0
