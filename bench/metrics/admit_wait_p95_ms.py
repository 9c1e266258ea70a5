"""admit_wait_p95_ms: from when each request of the window was due to the
worker's acceptance of its slot lease (``t_admit``), nearest-rank p95 (ms).
The wait in the host's admission queue."""

from bench.stats import percentile


def read(ctx):
    xs = [(q["t_admit"] - q["due"]) * 1e3 for q in ctx.requests
          if q["t_admit"] is not None]
    return percentile(xs, 95) if xs else None
