"""mfu.tput: FLOPs the traced window's admissions and decode blocks needed
(``bench.counts``) over window x chips x peak bf16 FLOP/s (%)."""

from bench.trace import call_work


def read(ctx):
    t = ctx.trace
    if t is None or not t["calls"]:
        return None
    flops, _, _ = call_work(t, ctx.peak)
    return 100.0 * flops / (t["window_s"] * ctx.chips
                            * ctx.peak["bf16_flops_per_s"])
