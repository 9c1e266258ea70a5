"""ttft_p95_ms: time to first token, from when each request of the window
was due to the host's receipt of its first token, nearest-rank p95 (ms)."""

from bench.stats import percentile


def read(ctx):
    xs = [(q["t_first"] - q["due"]) * 1e3 for q in ctx.requests
          if q["t_first"] is not None]
    return percentile(xs, 95) if xs else None
