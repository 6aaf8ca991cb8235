"""Raw 5-tuple flows (``benchlib.gen.raw_trace``), finite: the window takes
them in arrival order, and a run that runs out of them fails.

Mix keys: ``n_flows``, ``params`` (``raw_trace``'s pattern, burst and
length settings), ``sized_for_pps``, ``warm``, ``sample_flows``.  The
reference checks every packet of ``sample_flows`` flows drawn from the
seed, the flow with the most packets among them."""

from benchlib import gen


def build(mix, cfg, seed, seconds):
    rows, flow = gen.raw_trace(gen.stream_rng(seed, 2),
                               gen.window_packets(mix, seconds),
                               n_flows=mix["n_flows"],
                               model_ids=gen.tenant_ids(cfg),
                               **mix.get("params", {}))
    setup_raw, setup_wire = gen.warm_traffic(mix, cfg, seed)
    sample = gen.sample_flows(gen.stream_rng(seed, 4), flow,
                              mix["sample_flows"])
    return gen.Traffic("raw", rows, False, setup_raw, setup_wire, sample)
