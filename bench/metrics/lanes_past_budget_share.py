"""lanes_past_budget_share: lanes the decode blocks stepped in the window
for requests whose budget had ended earlier in the same block, over all the
lanes they stepped (``ServingEngine.lanes_past_budget`` / ``lanes_stepped``).
Reads nothing where the counters are not in the snapshots."""


def read(ctx):
    a, b = ctx.snap.get("t0"), ctx.snap.get("t1")
    if a is None or b is None or "lanes_stepped" not in a:
        return None
    stepped = b["lanes_stepped"] - a["lanes_stepped"]
    past = b["lanes_past_budget"] - a["lanes_past_budget"]
    return past / stepped if stepped else None
