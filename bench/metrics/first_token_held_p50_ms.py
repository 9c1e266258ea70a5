"""first_token_held_p50_ms: how long each request of the window's first
token waited on the worker, from the end of its admission to the flush
that sent it (the ``ham.req.held`` span), nearest-rank p50 (ms).  Needs the
program's spans (``ctx.spans``); reads nothing without them."""

from bench.program_trace import durations_ms
from bench.stats import percentile


def read(ctx):
    spans = getattr(ctx, "spans", None)
    xs = durations_ms(spans, "ham.req.held") if spans else []
    return percentile(xs, 50) if xs else None
