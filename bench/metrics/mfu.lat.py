"""mfu.lat: FLOPs the traced window's admissions and decode blocks needed
(``bench.counts``) over the device's busy time x chips x peak bf16 FLOP/s
(%).  Over busy time, since at a fixed offered rate the work in the window
is fixed by the rate."""

from bench.trace import call_work


def read(ctx):
    t = ctx.trace
    if t is None or not t["calls"] or t["busy_s"] <= 0:
        return None
    flops, _, _ = call_work(t, ctx.peak)
    return 100.0 * flops / (t["busy_s"] * ctx.chips
                            * ctx.peak["bf16_flops_per_s"])
