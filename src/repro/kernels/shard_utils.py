"""shard_map wrapper + mesh helpers for sharded kernel dispatch.

The serving executor runs the fused paged steps under ``jax.jit`` on a mesh;
inside those steps the attention ops are the only mesh-aware computation
(everything else is replicated math on replicated operands). The ops modules
use :func:`shard_map` from here, so MoE expert parallelism and the
paged-attention shards share one default (``check_vma=False``).
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=False):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def axis_size(mesh, axis: str) -> int:
    """Size of ``axis`` on ``mesh``; 1 when there is no mesh (single-device
    dispatch) or the mesh does not carry the axis."""
    if mesh is None or axis not in mesh.axis_names:
        return 1
    return int(mesh.shape[axis])


def head_shards(num_kv_heads: int, mesh, axis: str) -> int:
    """The ONE partition rule for paged KV: how many ways the KV heads (and
    with them the page pools) split on ``axis`` — the axis size when it
    divides the head count, else 1 (replicated pools + sequence-sharded
    attention fallback). Both ops dispatchers, ``paged_cache_specs`` and
    ``EngineCore.kv_shards`` consult this so cache placement, kernel
    dispatch and reporting can never disagree."""
    m = axis_size(mesh, axis)
    return m if m > 1 and num_kv_heads % m == 0 else 1
