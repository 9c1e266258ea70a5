"""output_tokens_per_s: every output token the host received inside the
window, over the window's length (tokens/s)."""


def read(ctx):
    n = sum(1 for q in ctx.all_requests for t in q["token_ts"]
            if ctx.t0 <= t < ctx.t1)
    return n / ctx.seconds
