"""route_imbalance: admissions the router sent to each replica in the
window (``Scheduler.stats["routed"]``), the most over the mean (ratio; 1
is even)."""


def read(ctx):
    a, b = ctx.snap.get("t0"), ctx.snap.get("t1")
    if a is None or b is None:
        return None
    sent = [b["routed"][n] - a["routed"].get(n, 0) for n in b["routed"]]
    mean = sum(sent) / len(sent) if sent else 0
    return max(sent) / mean if mean > 0 else None
