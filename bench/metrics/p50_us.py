"""Median due-to-answered latency over every packet of the window, in
microseconds (host clock; loops that keep due times)."""

import numpy as np


def read(ctx):
    lat = ctx.res.latency_s
    if lat is None or not lat.size:
        return None
    return float(np.percentile(lat, 50)) * 1e6
