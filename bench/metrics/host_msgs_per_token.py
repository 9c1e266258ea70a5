"""host_msgs_per_token: messages across the host boundary in the window
(admission submits, oneways, and the decode loops' stream frames) per
output token the host received (msgs/token)."""


def read(ctx):
    a, b = ctx.snap.get("t0"), ctx.snap.get("t1")
    if a is None or b is None:
        return None
    msgs = sum(b[k] - a[k] for k in ("submitted", "oneways", "frames"))
    toks = sum(1 for q in ctx.all_requests for t in q["token_ts"]
               if ctx.t0 <= t < ctx.t1)
    return msgs / toks if toks else None
