"""Roofline share of the paged decode attention kernel (the Pallas
``tpu_custom_call`` named ``paged_decode_attention``, inside
``jit_decode_fused``), in %."""
from slobench import rooflines

KERNEL = r"paged_decode_attention.*tpu_custom_call"


def read(run):
    return rooflines.share(run, "jit_decode_fused", "decode", KERNEL)
