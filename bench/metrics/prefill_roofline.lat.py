"""prefill_roofline: the least time the chip needs for the admissions'
prefills (``bench.counts``) over the device time of their executable
(``jit_admit_fused``), in the traced window (%)."""

from bench.trace import roofline_pct


def read(ctx):
    return roofline_pct(ctx, "admit")
