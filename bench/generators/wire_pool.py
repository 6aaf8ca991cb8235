"""Encapsulated feature packets of paper Table 1 (``benchlib.gen.wire_pool``)
from a pool of ``pool_rows`` distinct random rows, which the window cycles
through.

Mix keys: ``pool_rows``, ``feature_lo`` and ``feature_hi`` (the feature
codes' range), ``sized_for_pps``, ``warm``, ``sample_packets``.  The
reference checks ``sample_packets`` window positions drawn from the
seed."""

import numpy as np

from benchlib import gen


def build(mix, cfg, seed, seconds):
    rows = gen.wire_pool(gen.stream_rng(seed, 2), n_rows=mix["pool_rows"],
                         model_ids=gen.tenant_ids(cfg),
                         width=cfg["server"]["max_width"],
                         lo=mix["feature_lo"], hi=mix["feature_hi"],
                         frac=cfg["server"]["frac_bits"])
    setup_raw, setup_wire = gen.warm_traffic(mix, cfg, seed)
    n = gen.window_packets(mix, seconds)
    sample = np.sort(gen.stream_rng(seed, 4).choice(
        n, min(mix["sample_packets"], n), replace=False))
    return gen.Traffic("wire", rows, True, setup_raw, setup_wire, sample)
