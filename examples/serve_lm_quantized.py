"""Scenario: W8A8 fixed-point LM serving with control-plane hot-swap —
the paper's C1+C3 promoted to framework scale (DESIGN.md §2).

A small qwen2-family model is served twice: float weights vs int8
control-plane tables (quantize_tree).  Outputs are compared (NMSE within
the paper's budget), weights are hot-swapped with zero recompiles, and
int8 KV cache halves the decode state.

    PYTHONPATH=src python examples/serve_lm_quantized.py
"""

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.core.quantize import quantize_tree
from repro.launch.lm_serve import LMServer


def main():
    cfg = reduced(get_config("qwen2-1.5b"), d_model=256, n_layers=4,
                  d_ff=512).replace(remat=False)
    model_params = None

    # float serving baseline
    srv = LMServer(cfg, batch=2, max_seq=64)
    model_params = srv.model.init(jax.random.key(0))
    srv.install("prod", model_params)
    prompt = np.asarray([[3, 1, 4, 1, 5], [9, 2, 6, 5, 3]], np.int32)
    out_fp = srv.generate("prod", prompt, 12)
    print(f"float decode: {srv.tokens_per_second():,.0f} tok/s")

    # fixed-point serving: weights become int8 control-plane tables
    cfg_q = cfg  # same arch; tables swap in through the registry
    srv_q = LMServer(cfg_q, batch=2, max_seq=64)
    q_params = quantize_tree(model_params, bits=8)
    srv_q.install("prod", q_params)
    out_q = srv_q.generate("prod", prompt, 12)
    agree = (out_fp == out_q).mean()
    print(f"W8A8 decode: {srv_q.tokens_per_second():,.0f} tok/s; "
          f"token agreement with float: {agree:.2%}")

    # hot-swap a 'retrained' checkpoint — no recompile
    n = srv_q.trace_count
    q2 = quantize_tree(srv.model.init(jax.random.key(1)), bits=8)
    srv_q.install("prod", q2)
    srv_q.generate("prod", prompt, 4)
    assert srv_q.trace_count == n, "hot-swap must not recompile"
    print(f"hot-swap OK (trace_count still {n})")

    # int8 KV cache variant (paper C1 on the decode bottleneck)
    cfg_kv = cfg.replace(kv_cache_bits=8)
    srv_kv = LMServer(cfg_kv, batch=2, max_seq=64)
    srv_kv.install("prod", model_params)
    out_kv = srv_kv.generate("prod", prompt, 12)
    print(f"int8-KV decode agreement: {(out_fp == out_kv).mean():.2%}")
    print("OK")


if __name__ == "__main__":
    main()
