"""decode_roofline: the least time the chip needs for the decode blocks'
needed work (``bench.counts``) over the device time of their executable
(``jit_multi``), in the traced window (%)."""

from bench.trace import roofline_pct


def read(ctx):
    return roofline_pct(ctx, "block")
