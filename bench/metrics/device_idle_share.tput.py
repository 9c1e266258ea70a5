"""device_idle_share: the share of the traced window in which no operation
ran on the device, averaged over the chips used."""


def read(ctx):
    t = ctx.trace
    if t is None or not t["devices"] or t["window_s"] <= 0:
        return None
    return 1.0 - t["busy_s"] / t["window_s"]
