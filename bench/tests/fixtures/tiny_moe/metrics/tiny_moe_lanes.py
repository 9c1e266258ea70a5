"""tiny_moe_lanes: lanes the window's decode steps ran through the expert
layers, from the tiny_moe family's program counter."""


def read(ctx):
    a, b = ctx.snap.get("t0"), ctx.snap.get("t1")
    if a is None or b is None or "moe_lanes" not in b["program"]:
        return None
    return float(b["program"]["moe_lanes"] - a["program"]["moe_lanes"])
