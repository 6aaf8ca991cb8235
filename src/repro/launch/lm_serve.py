"""Batched LM decode with control-plane weight hot-swap — the
framework-scale generalization of the packet server: KV caches, W8A8
fixed-point weights (C1), Taylor activations (C2), and the same hot-swap
semantics through :class:`~repro.core.control_plane.WeightRegistry`.

Kept apart from :mod:`repro.launch.serve` so the packet data plane never
imports the LM model code.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from ..core.control_plane import WeightRegistry
from ..models import build_model

__all__ = ["LMServer"]


class LMServer:
    """Batched LM decode loop with control-plane weight hot-swap.

    The decode step is jitted once over abstract weights; ``install()``
    swaps checkpoints (e.g. freshly retrained) with zero recompiles —
    asserted by ``trace_count`` exactly like the packet engine.
    """

    def __init__(self, cfg, *, batch: int = 8, max_seq: int = 256):
        self.cfg = cfg
        self.model = build_model(cfg)
        self.registry = WeightRegistry()
        self.batch = batch
        self.max_seq = max_seq
        self.trace_count = 0
        self.stats = {"tokens": 0, "seconds": 0.0}

        def _step(params, caches, tokens, pos):
            self.trace_count += 1
            return self.model.decode_step(params, caches, tokens, pos)

        self._step = jax.jit(_step, donate_argnums=(1,))

    def install(self, name: str, params) -> None:
        self.registry.install(name, params)

    def new_session(self):
        return self.model.init_caches(self.batch, self.max_seq)

    def generate(self, name: str, prompt_tokens: np.ndarray, n_tokens: int,
                 temperature: float = 0.0, seed: int = 0):
        """Greedy/temperature decode of ``n_tokens`` past the prompt."""
        params = self.registry.get(name)
        caches = self.new_session()
        b, prompt_len = prompt_tokens.shape
        assert b == self.batch
        key = jax.random.key(seed)
        toks = jnp.asarray(prompt_tokens, jnp.int32)
        out = []
        t0 = time.perf_counter()
        cur = toks[:, :1]
        logits = None
        for t in range(prompt_len + n_tokens - 1):
            pos = jnp.full((b,), t, jnp.int32)
            logits, caches = self._step(params, caches, cur, pos)
            if t + 1 < prompt_len:
                cur = toks[:, t + 1: t + 2]
            else:
                if temperature > 0:
                    key, sub = jax.random.split(key)
                    nxt = jax.random.categorical(
                        sub, logits[:, -1] / temperature, axis=-1)
                else:
                    nxt = jnp.argmax(logits[:, -1], axis=-1)
                cur = nxt[:, None].astype(jnp.int32)
                out.append(np.asarray(cur[:, 0]))
        dt = time.perf_counter() - t0
        self.stats["tokens"] += b * (prompt_len + n_tokens - 1)
        self.stats["seconds"] += dt
        return np.stack(out, axis=1)

    def tokens_per_second(self) -> float:
        s = self.stats
        return s["tokens"] / s["seconds"] if s["seconds"] else 0.0
