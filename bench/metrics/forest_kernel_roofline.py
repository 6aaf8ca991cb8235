"""Forest traversal kernel (``kernels/forest_traversal.py``): least time
the chip needs for the lane's needed work over the summed device time of
the kernel's events, in percent."""

from benchlib.work import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "forest")
