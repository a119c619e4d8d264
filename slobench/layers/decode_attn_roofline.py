"""Roofline share of the paged decode attention kernel (the Pallas
``tpu_custom_call``, named ``_fused_kernel``, inside ``jit_decode_fused``), in %."""
from slobench import rooflines


def read(run):
    return rooflines.share(run, "jit_decode_fused", "decode")
