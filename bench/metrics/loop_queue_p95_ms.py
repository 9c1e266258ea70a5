"""loop_queue_p95_ms: how long each request of the window waited in its
worker decode loop's queue, from ``enqueue_admit`` to the start of its
admission (the ``ham.req.queued`` span), nearest-rank p95 (ms).  Needs the
program's spans (``ctx.spans``); reads nothing without them."""

from bench.program_trace import durations_ms
from bench.stats import percentile


def read(ctx):
    spans = getattr(ctx, "spans", None)
    xs = durations_ms(spans, "ham.req.queued") if spans else []
    return percentile(xs, 95) if xs else None
