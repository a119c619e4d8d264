"""Roofline share of the paged prefill attention kernel (the Pallas
``tpu_custom_call``, named ``_fused_kernel``, inside ``jit_chunk_fused``), in %."""
from slobench import rooflines


def read(run):
    return rooflines.share(run, "jit_chunk_fused", "chunk")
