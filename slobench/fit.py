"""Size a configuration's KV pool by compiling its serving steps for a v5e.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m slobench.fit qwen3-1.7b 40000 60000

For each pool size (in tokens) it compiles the engine's fused decode step
(64 rows) and chunk step (8 rows of 512 tokens) at the widest block table
the configuration's traffic can reach, for one chip described by
``jax.experimental.topologies`` (no chip is needed), and prints what
``memory_analysis()`` reports: argument, output, alias and temporary bytes.
Nothing runs; the step's kernels are traced on their TPU branch. The numbers
are those of the compiled programs alone; what else the process holds on the
device (the eager cache construction, upload buffers) is not in them.
"""
from __future__ import annotations

import json
import os
import sys
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def analyse(cfg, pool_tokens: int, page_size: int, max_pages: int) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.models.model import (RunCtx, init_paged_cache, init_params,
                                    paged_chunk_step, paged_decode_step)

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    rctx = RunCtx(block_q=32, block_k=32, mlstm_block=32)   # EngineCore's
    pages = pool_tokens // page_size + 1                    # + trash page

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    params = shaped(jax.eval_shape(lambda k: init_params(cfg, k),
                                   jax.random.PRNGKey(0)))
    cache = shaped(jax.eval_shape(
        lambda: init_paged_cache(cfg, pages, page_size)))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    def decode(params, tokens, cache, lengths, bt, ws, nonce):
        return paged_decode_step(cfg, params, tokens, cache, rctx=rctx,
                                 lengths=lengths, block_tables=bt,
                                 write_slots=ws, sampling=None, nonce=nonce)

    def chunk(params, tokens, cache, row_pos, row_lens, bt, ws, logits_at,
              nonce):
        return paged_chunk_step(cfg, params, tokens, cache, row_pos,
                                rctx=rctx, row_lens=row_lens, block_tables=bt,
                                write_slots=ws, logits_at=logits_at,
                                sampling=None, nonce=nonce)

    out = {"pool_tokens": pool_tokens, "pages": pages}
    R, Rc, L = 64, 8, 512
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        progs = {
            "decode": jax.jit(decode, donate_argnums=(2,)).lower(
                params, i32(R, 1), cache, i32(R), i32(R, max_pages), i32(R),
                i32()),
            "chunk": jax.jit(chunk, donate_argnums=(2,)).lower(
                params, i32(Rc, L), cache, i32(Rc), i32(Rc),
                i32(Rc, max_pages), i32(Rc * L), i32(Rc), i32()),
        }
        for name, lowered in progs.items():
            compiled = lowered.compile()
            m = compiled.memory_analysis()
            text = compiled.as_text()
            out[name] = {
                "argument_bytes": m.argument_size_in_bytes,
                "output_bytes": m.output_size_in_bytes,
                "alias_bytes": m.alias_size_in_bytes,
                "temp_bytes": m.temp_size_in_bytes,
                "peak_bytes": (m.argument_size_in_bytes + m.output_size_in_bytes
                               - m.alias_size_in_bytes + m.temp_size_in_bytes),
                "tpu_custom_call": "tpu_custom_call" in text,
            }
    return out


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) < 2:
        print(__doc__, file=sys.stderr)
        return 2
    from slobench import spec
    conf = spec.load_config_by_name(argv[0])
    cfg = spec.model_config(conf)
    eng = conf["engine"]
    max_pages = spec.pow2(-(-spec.max_context(conf) // eng["page_size"]))
    for tokens in argv[1:]:
        print(json.dumps(analyse(cfg, int(tokens), eng["page_size"],
                                 max_pages)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
