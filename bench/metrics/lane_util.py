"""lane_util: tokens the decode blocks emitted in the window over the lanes
they stepped (decode steps dispatched x slots); the rest were lanes of
freed slots or of requests past their budget mid-block."""


def read(ctx):
    a, b = ctx.snap.get("t0"), ctx.snap.get("t1")
    if a is None or b is None:
        return None
    # every admission emits its first token outside a block
    block_tokens = (b["loop_tokens"] - a["loop_tokens"]) \
        - (b["submitted"] - a["submitted"])
    lanes = (b["steps"] - a["steps"]) * ctx.slots
    return block_tokens / lanes if lanes else None
