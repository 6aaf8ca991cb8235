"""Paper-claim entry point: one harness per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run

Prints a ``name,us_per_call,derived`` CSV summary after the detailed logs.
These check the paper's claims (precision, Taylor order, the Fig-1 trend,
µs-scale latency) on whatever backend runs them; serving speed is measured
on the chip by ``bench/run.py``.
"""

from __future__ import annotations

import time


def main() -> None:
    from . import (bench_fig1_throughput, bench_fig3_precision,
                   bench_fig4_taylor, bench_latency)

    results = {}
    for name, mod in [
        ("fig3_nmse_vs_frac_bits", bench_fig3_precision),
        ("fig4_nmse_vs_taylor_order", bench_fig4_taylor),
        ("fig1_throughput_vs_header", bench_fig1_throughput),
        ("latency_microsecond_claim", bench_latency),
    ]:
        print(f"[bench] {name}")
        t0 = time.perf_counter()
        results[name] = mod.run(verbose=True)
        results[name]["_elapsed_us"] = (time.perf_counter() - t0) * 1e6

    print("\nname,us_per_call,derived")
    r3 = results["fig3_nmse_vs_frac_bits"]
    print(f"fig3_nmse_vs_frac_bits,{r3['_elapsed_us']:.0f},"
          f"nmse@8bits={r3['claim_nmse_at_8bits']:.5f} "
          f"claim<0.15={'PASS' if r3['claim_validated'] else 'FAIL'}")
    r4 = results["fig4_nmse_vs_taylor_order"]
    print(f"fig4_nmse_vs_taylor_order,{r4['_elapsed_us']:.0f},"
          f"nmse@order3={r4['claim_nmse_at_order3']:.5f} "
          f"claim<0.2={'PASS' if r4['claim_validated'] else 'FAIL'}")
    r1 = results["fig1_throughput_vs_header"]
    last = r1["rows"][-1]
    print(f"fig1_throughput_vs_header,{r1['_elapsed_us']:.0f},"
          f"pkts_per_s@16feat={last['packets_per_s']:.0f} "
          f"trend={'PASS' if r1['trend_validated'] else 'FAIL'}")
    rl = results["latency_microsecond_claim"]
    print(f"latency_microsecond_claim,{rl['_elapsed_us']:.0f},"
          f"per_packet_us={rl['rows'][-1]['per_packet_us']:.3f} "
          f"us_scale={'PASS' if rl['microsecond_scale'] else 'FAIL'}")


if __name__ == "__main__":
    main()
