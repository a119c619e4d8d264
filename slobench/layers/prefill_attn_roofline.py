"""Roofline share of the paged prefill attention kernel (the Pallas
``tpu_custom_call`` named ``paged_prefill_attention``, inside
``jit_chunk_fused``), in %."""
from slobench import rooflines

KERNEL = r"paged_prefill_attention.*tpu_custom_call"


def read(run):
    return rooflines.share(run, "jit_chunk_fused", "chunk", KERNEL)
