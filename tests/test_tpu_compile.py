"""Ahead-of-time compiles of the fused paged-attention kernels for a TPU v5e.

The TPU compiler ships with jaxlib and compiles for a described, unattached
chip, so these tests catch what interpret mode cannot: block shapes the
Mosaic tiling rules refuse, VMEM over-use, renamed Pallas APIs. Shapes are
llama3.2-3b's attention at serving widths (8 KV heads, 3 query heads per KV
head, head dim 128, 16-token pages, bf16) over a 16384-token page pool.
Nothing runs; a compile that passes is not a chip run.

The topology is described inside a fixture (never at import time): only one
process at a time may load the TPU library, and every pytest worker imports
every test file.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention.kernel import paged_attention_fused
from repro.kernels.paged_prefill_attention.kernel import (
    paged_prefill_attention_fused)

HKV, G, D, PAGE = 8, 3, 128, 16
POOL_PAGES = 16384 // PAGE + 1        # + the engine's trash page
TABLE = 128                           # pages per row: 2048-token context


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A single described v5e chip, with JAX's persistent compilation cache
    off: an entry written for a described chip cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _check(compiled, kernel):
    """The program holds the Pallas kernel, under its own name (the device
    trace tells the decode and prefill kernels apart by it)."""
    text = compiled.as_text()
    assert "tpu_custom_call" in text, \
        "the compiled program holds no Pallas kernel"
    assert f"%{kernel}" in text, f"no kernel named {kernel!r}"


@pytest.mark.parametrize("partial", [False, True], ids=["full", "partial"])
@pytest.mark.parametrize("depth", [2, 4])
@pytest.mark.parametrize("rows", [1, 64])
def test_decode_kernel_compiles_for_v5e(one_chip, rows, depth, partial):
    fn = jax.jit(functools.partial(paged_attention_fused, scale=D ** -0.5,
                                   partial=partial, dma_depth=depth))
    _check(fn.lower(
        _spec(one_chip, (rows, HKV * G, D), jnp.bfloat16),
        _spec(one_chip, (HKV, POOL_PAGES, 2, PAGE, D), jnp.bfloat16),
        _spec(one_chip, (rows, TABLE)),
        _spec(one_chip, (rows,))).compile(), "paged_decode_attention")


# Sq=2: a speculative verify row; 16: the smallest chunk bucket; 2048: the
# largest chunk bucket (16 query tiles of 128).
@pytest.mark.parametrize("partial", [False, True], ids=["full", "partial"])
@pytest.mark.parametrize("depth", [2, 4])
@pytest.mark.parametrize("rows,sq", [(8, 2), (8, 16), (1, 2048)])
def test_prefill_kernel_compiles_for_v5e(one_chip, rows, sq, depth, partial):
    fn = jax.jit(functools.partial(paged_prefill_attention_fused,
                                   scale=D ** -0.5, partial=partial,
                                   dma_depth=depth))
    _check(fn.lower(
        _spec(one_chip, (rows, sq, HKV, G, D), jnp.bfloat16),
        _spec(one_chip, (HKV, POOL_PAGES, 2, PAGE, D), jnp.bfloat16),
        _spec(one_chip, (rows, TABLE)),
        _spec(one_chip, (rows,)),
        _spec(one_chip, (rows,))).compile(), "paged_prefill_attention")
