"""Whole serving step's share of the chips' int8 peak, in percent: needed
integer operations of the answered packets (counted from the installed
tenants) per second of the window, over chips times the int8 peak."""


def read(ctx):
    if ctx.peaks is None or not ctx.work["answered_ops"]:
        return None
    return 100.0 * ctx.work["answered_ops"] / ctx.window_s / (
        ctx.chips * ctx.peaks["int8_ops"])
