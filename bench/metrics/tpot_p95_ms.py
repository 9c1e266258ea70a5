"""tpot_p95_ms: time per output token of each request of the window,
(t_last - t_first) / (n - 1) on the host's receipt times, nearest-rank p95
over requests (ms)."""

from bench.stats import percentile


def read(ctx):
    xs = [(q["token_ts"][-1] - q["token_ts"][0]) / (len(q["token_ts"]) - 1)
          * 1e3 for q in ctx.requests if len(q["token_ts"]) > 1]
    return percentile(xs, 95) if xs else None
