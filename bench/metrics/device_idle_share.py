"""Share of the traced window in which no operation ran on the device, in
percent, averaged over the chips used (profiler trace)."""


def read(ctx):
    t = ctx.trace
    if t is None or not t["chips_busy"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
