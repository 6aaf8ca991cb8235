"""Tentpole tests for the zero-copy ingress pipeline (core/ingress.py):
the generation-aware duplicate-result cache, the coalescing fixed-shape
batch queue, submission-order result delivery with per-packet error slots,
and the cache-staleness contract under concurrent ``install()``/``remove()``.
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax.numpy as jnp

from repro.core import packet as pk
from repro.core.control_plane import ControlPlane
from repro.core.inference import DataPlaneEngine
from repro.core.ingress import (IngressPipeline, PacketError, ResultCache,
                                _dedup_rows, hash_words, pack_rows)

FRAC = 8
WIDTH = 8


def _install(cp, rng, model_id, scale=0.3):
    w1 = rng.normal(size=(WIDTH, WIDTH)).astype(np.float32) * scale
    w2 = rng.normal(size=(WIDTH, 2)).astype(np.float32) * scale
    cp.install(model_id, [(w1, np.zeros(WIDTH, np.float32)),
                          (w2, np.zeros(2, np.float32))],
               ["relu"], final_activation="sigmoid")


def _pipeline(n_models=4, batch_size=64, seed=0, **kw):
    rng = np.random.default_rng(seed)
    cp = ControlPlane(max_models=n_models, max_layers=2, max_width=WIDTH,
                      frac_bits=FRAC)
    for m in range(n_models):
        _install(cp, rng, 10 + m)
    eng = DataPlaneEngine(cp, max_features=WIDTH)
    return cp, eng, IngressPipeline(eng, batch_size=batch_size, **kw)


def _wire(rng, n, model_lo=10, model_hi=14):
    mids = rng.integers(model_lo, model_hi, n).astype(np.int32)
    codes = rng.integers(-2000, 2000, (n, WIDTH)).astype(np.int32)
    return np.asarray(pk.encode_packets(jnp.asarray(mids), jnp.int32(FRAC),
                                        jnp.asarray(codes)))


# ---------------------------------------------------------------------------
# ResultCache
# ---------------------------------------------------------------------------


class TestResultCache:
    def _kv(self, rng, n, kw=3, vb=16):
        rows = rng.integers(0, 256, (n, kw * 8 - 3)).astype(np.uint8)
        words = pack_rows(rows, kw)
        vals = rng.integers(0, 256, (n, vb)).astype(np.uint8)
        mids = rng.integers(0, 8, n).astype(np.int64)
        return words, vals, mids

    def test_roundtrip_and_miss(self):
        rng = np.random.default_rng(0)
        words, vals, mids = self._kv(rng, 500)
        c = ResultCache(3, 16, capacity_pow2=11)
        hm, _ = c.lookup(words, 1)
        assert not hm.any()
        c.insert(words, vals, mids, 1)
        hm, got = c.lookup(words, 1)
        assert hm.all()
        np.testing.assert_array_equal(got, vals)
        other, _, _ = self._kv(np.random.default_rng(1), 500)
        hm2, _ = c.lookup(other, 1)
        assert not hm2.any()

    def test_generation_bump_invalidates_everything(self):
        """Entries computed under generation g must never be served at
        generation g+1 — the install()/remove() staleness contract."""
        rng = np.random.default_rng(2)
        words, vals, mids = self._kv(rng, 64)
        c = ResultCache(3, 16)
        c.insert(words, vals, mids, 5)
        hm, _ = c.lookup(words, 6)
        assert not hm.any()
        assert len(c) == 0

    def test_stale_insert_dropped(self):
        """Results of a batch dispatched before an install retire after it:
        they carry the old generation and must not enter the cache."""
        rng = np.random.default_rng(3)
        words, vals, mids = self._kv(rng, 64)
        c = ResultCache(3, 16)
        c.lookup(words, 7)          # cache now lives at generation 7
        assert c.insert(words, vals, mids, 6) == 0  # stale: dropped whole
        hm, _ = c.lookup(words, 7)
        assert not hm.any()
        assert c.stale_inserts_dropped == 64

    def test_refresh_in_place(self):
        rng = np.random.default_rng(4)
        words, vals, mids = self._kv(rng, 32)
        c = ResultCache(3, 16)
        c.insert(words, vals, mids, 1)
        vals2 = (vals + 1).astype(np.uint8)
        c.insert(words, vals2, mids, 1)
        assert len(c) == 32  # refreshed, not duplicated
        _, got = c.lookup(words, 1)
        np.testing.assert_array_equal(got, vals2)

    def test_drop_model_tombstones_only_that_model(self):
        rng = np.random.default_rng(5)
        words, vals, mids = self._kv(rng, 400)
        c = ResultCache(3, 16, capacity_pow2=10)  # small: probe chains exist
        c.insert(words, vals, mids, 1)
        dropped = c.drop_model(3)
        assert dropped == int((mids == 3).sum())
        assert not c.contains_model(3)
        hm, got = c.lookup(words, 1)
        np.testing.assert_array_equal(hm, mids != 3)
        np.testing.assert_array_equal(got, vals[mids != 3])

    def test_insert_after_tombstone_reuses_slots(self):
        rng = np.random.default_rng(6)
        words, vals, mids = self._kv(rng, 100)
        c = ResultCache(3, 16, capacity_pow2=9)
        c.insert(words, vals, mids, 1)
        c.drop_model(2)
        c.insert(words, vals, mids, 1)  # re-admit the dropped entries
        hm, _ = c.lookup(words, 1)
        assert hm.all()

    def test_load_limit_flushes_not_overflows(self):
        rng = np.random.default_rng(7)
        c = ResultCache(3, 16, capacity_pow2=7, load_limit=0.5)  # cap 128
        for gen_chunk in range(6):
            words, vals, mids = self._kv(rng, 50)
            c.insert(words, vals, mids, 1)
            assert len(c) <= 64

    def test_duplicate_rows_in_one_insert(self):
        rng = np.random.default_rng(8)
        words, vals, mids = self._kv(rng, 20)
        dup_words = np.concatenate([words, words])
        dup_vals = np.concatenate([vals, vals])
        dup_mids = np.concatenate([mids, mids])
        c = ResultCache(3, 16)
        c.insert(dup_words, dup_vals, dup_mids, 1)
        assert len(c) == 20
        hm, got = c.lookup(words, 1)
        assert hm.all()
        np.testing.assert_array_equal(got, vals)

    def test_duplicate_keys_with_assume_unique_refresh_not_double_insert(self):
        """assume_unique is an optimization hint, not a correctness
        precondition: duplicate keys slipping past a best-effort upstream
        dedup (e.g. the pending window dropped a row) must resolve as
        in-place refreshes — never claim a second slot for the same key."""
        rng = np.random.default_rng(9)
        words, vals, mids = self._kv(rng, 10)
        dup_words = np.concatenate([words, words])
        dup_vals = np.concatenate([vals, vals])
        dup_mids = np.concatenate([mids, mids])
        c = ResultCache(3, 16)
        c.insert(dup_words, dup_vals, dup_mids, 1, assume_unique=True)
        assert len(c) == 10
        hm, got = c.lookup(words, 1)
        assert hm.all()
        np.testing.assert_array_equal(got, vals)

    def test_tombstone_slots_reclaimed_under_model_churn(self):
        """The PR-3 satellite regression test: a long-running serve loop
        that keeps installing and dropping models must not degrade toward
        all-tombstone probing — drop_model() tombstones are reclaimed by
        inserts and compacted away past the threshold, so the dead-slot
        population stays bounded forever."""
        rng = np.random.default_rng(40)
        cap = 1 << 9
        c = ResultCache(3, 16, capacity_pow2=9, load_limit=0.5,
                        tombstone_limit=0.25)
        for round_ in range(40):
            words, vals, _ = self._kv(rng, 60)
            mids = np.full(60, round_ % 5, np.int64)
            c.insert(words, vals, mids, 1)
            c.drop_model(round_ % 5)
            # invariant: tombstones never exceed the compaction threshold
            # (plus one round's insertions re-claiming on top is fine)
            assert c.tombstones <= cap * 0.25
        assert c.compactions > 0  # churn actually exercised the compactor
        # the cache still works at full fidelity after heavy churn
        words, vals, mids = self._kv(rng, 50)
        c.insert(words, vals, mids, 1)
        hm, got = c.lookup(words, 1)
        assert hm.all()
        np.testing.assert_array_equal(got, vals)

    def test_compaction_preserves_live_entries(self):
        rng = np.random.default_rng(41)
        words, vals, mids = self._kv(rng, 120)
        c = ResultCache(3, 16, capacity_pow2=8, tombstone_limit=0.05)
        c.insert(words, vals, mids, 1)
        keep = (mids != 3) & (mids != 4)
        c.drop_model(3)
        c.drop_model(4)  # cumulative tombstones cross 5% → compact in place
        assert c.compactions >= 1 and c.tombstones == 0
        hm, got = c.lookup(words, 1)
        np.testing.assert_array_equal(hm, keep)
        np.testing.assert_array_equal(got, vals[keep])

    @settings(max_examples=25, deadline=None)
    @given(n=st.integers(min_value=1, max_value=200),
           seed=st.integers(min_value=0, max_value=2 ** 16),
           cap=st.integers(min_value=9, max_value=12))
    def test_property_lookup_after_insert_exact(self, n, seed, cap):
        """Whatever the fill pattern and collision structure, every inserted
        key must come back with exactly its own value, and unrelated keys
        must miss (the probe sweeps never cross-wire rows).  Table load is
        kept under ~40% — at saturation the cache legitimately refuses
        admission (probe bound), which is a different property."""
        rng = np.random.default_rng(seed)
        words, vals, mids = self._kv(rng, n)
        c = ResultCache(3, 16, capacity_pow2=cap, load_limit=1.0)
        c.insert(words, vals, mids, 1)
        hm, got = c.lookup(words, 1)
        uniq = np.unique(words, axis=0).shape[0]
        # duplicate keys collapse; all survivors must round-trip exactly
        assert hm.all() or uniq < n
        if hm.all():
            # values correspond row-for-row (duplicates share one slot, and
            # the last write of an identical key wins — values here are
            # keyed off the row index so duplicates may disagree; restrict
            # the exactness claim to unique keys)
            _, first = np.unique(words, axis=0, return_index=True)
            np.testing.assert_array_equal(got[np.sort(first)],
                                          vals[np.sort(first)])
        other = self._kv(np.random.default_rng(seed + 77777), n)[0]
        row_in = (other[:, None, :] == words[None, :, :]).all(-1).any(1)
        hm2, _ = c.lookup(other, 1)
        assert not (hm2 & ~row_in).any()

    # -- hash-tag checks: a slot's key is read only where its tag matches --

    def test_planted_tag_collision_lookup_misses_foreign_key(self):
        """A row whose hash equals a cached row's but whose key differs is a
        64-bit collision: the tag matches, the key words do not, so the
        lookup probes on and misses — it never serves the foreign value."""
        rng = np.random.default_rng(50)
        a, vals, mids = self._kv(rng, 200)
        b, _, _ = self._kv(rng, 200)
        h = hash_words(a)
        c = ResultCache(3, 16, capacity_pow2=10)
        c.insert(a, vals, mids, 1, h)
        v0 = c.key_verifies
        hm, _ = c.lookup(b, 1, h)  # b's rows planted on a's tags
        assert not hm.any()
        assert c.key_verifies - v0 >= 200  # every b row met a's tag
        hm, got = c.lookup(a, 1, h)
        assert hm.all()
        np.testing.assert_array_equal(got, vals)

    def test_planted_tag_collision_insert_claims_own_slot(self):
        """Inserting a colliding foreign key must claim a slot of its own
        and leave the cached key's value untouched (no refresh across
        keys)."""
        rng = np.random.default_rng(51)
        a, va, ma = self._kv(rng, 150)
        b, vb, mb = self._kv(rng, 150)
        h = hash_words(a)
        c = ResultCache(3, 16, capacity_pow2=10)
        c.insert(a, va, ma, 1, h, assume_unique=True)
        assert c.insert(b, vb, mb, 1, h, assume_unique=True) == 150
        assert len(c) == 300
        hm, got = c.lookup(a, 1, h)
        assert hm.all()
        np.testing.assert_array_equal(got, va)
        hm, got = c.lookup(b, 1, h)
        assert hm.all()
        np.testing.assert_array_equal(got, vb)

    def test_planted_tag_collision_arbitration_loser(self):
        """One call with rows ``a, b, a`` on one hash: all three race one
        home slot.  The last writer (the second ``a``) wins; the first
        ``a`` loses to its own key and refreshes in place; ``b`` loses to
        a foreign key with its tag and must probe on to a slot of its
        own."""
        rng = np.random.default_rng(52)
        w, v, m = self._kv(rng, 2)
        words = w[[0, 1, 0]]
        vals = v[[0, 1, 0]]
        mids = m[[0, 1, 0]]
        h = np.full(3, hash_words(w[:1])[0], np.uint64)
        c = ResultCache(3, 16, capacity_pow2=8)
        assert c.insert(words, vals, mids, 1, h, assume_unique=True) == 2
        assert len(c) == 2
        hm, got = c.lookup(w, 1, h[:2])
        assert hm.all()
        np.testing.assert_array_equal(got, v)

    def test_tags_hold_after_tombstone_reuse_and_compaction(self):
        """Tags are written wherever a slot is filled: an insert reclaiming
        a ``drop_model`` tombstone, and ``_compact``'s re-insert, which
        re-homes every live entry under its stored tag — so entries cached
        under caller-supplied hashes stay reachable through them."""
        rng = np.random.default_rng(53)
        words, vals, mids = self._kv(rng, 120)
        # planted hashes: every row shares its tag with three others
        h = hash_words(words[np.arange(120) // 4])
        c = ResultCache(3, 16, capacity_pow2=8, tombstone_limit=0.1)
        c.insert(words, vals, mids, 1, h)
        c.drop_model(3)
        assert c.tombstones > 0 and c.compactions == 0
        c.insert(words, vals, mids, 1, h)  # re-admit: reclaims tombstones
        hm, got = c.lookup(words, 1, h)
        assert hm.all()
        np.testing.assert_array_equal(got, vals)
        c.drop_model(4)
        c.drop_model(5)  # cumulative tombstones cross 10% → compact
        assert c.compactions >= 1 and c.tombstones == 0
        keep = (mids != 4) & (mids != 5)
        hm, got = c.lookup(words, 1, h)
        np.testing.assert_array_equal(hm, keep)
        np.testing.assert_array_equal(got, vals[keep])
        live = np.flatnonzero(c._state == 1)
        want = {words[i].tobytes(): h[i] for i in range(120)}
        assert [c._tag[s] for s in live] \
            == [want[c._keys[s].tobytes()] for s in live]

    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2 ** 16),
           n_ops=st.integers(min_value=1, max_value=16))
    def test_property_matches_dict_oracle_under_tag_collisions(self, seed,
                                                               n_ops):
        """Random inserts (with and without ``assume_unique``, repeats
        allowed), model drops and generation bumps, against a dict oracle.
        The hashes are folded to 3 bits, so nearly every probe meets a
        foreign key under its own tag.  With a probe budget of the whole
        table no row is refused admission, so after every step the cache
        must hold exactly the oracle's keys, each with its own value."""
        rng = np.random.default_rng(seed)
        pool, pool_vals, _ = self._kv(rng, 48)
        pool_mids = np.arange(48, dtype=np.int64) % 4
        h = hash_words(pool) & np.uint64(7)
        c = ResultCache(3, 16, capacity_pow2=8, max_probe=1 << 8,
                        load_limit=1.0, tombstone_limit=0.1)
        gen = 1
        oracle = set()
        for _ in range(n_ops):
            op = rng.integers(0, 4)
            if op <= 1:
                idx = rng.integers(0, 48, int(rng.integers(1, 24)))
                flushes = c.flushes
                c.insert(pool[idx], pool_vals[idx] ^ np.uint8(gen),
                         pool_mids[idx], gen, h[idx],
                         assume_unique=bool(op))
                if c.flushes != flushes:
                    oracle.clear()
                oracle.update(idx.tolist())
            elif op == 2:
                m = int(rng.integers(0, 4))
                assert c.drop_model(m) >= sum(
                    1 for i in oracle if pool_mids[i] == m)
                oracle = {i for i in oracle if pool_mids[i] != m}
            else:
                gen += 1
                oracle.clear()
            hm, got = c.lookup(pool, gen, h)
            want = np.isin(np.arange(48), sorted(oracle))
            np.testing.assert_array_equal(hm, want)
            np.testing.assert_array_equal(got, pool_vals[want] ^ np.uint8(gen))

    def test_probe_counters(self):
        """``probes`` counts slots examined, ``key_verifies`` the slots
        whose tag matched: cold rows read no key, cached rows read exactly
        their own, colliding rows read the foreign key they collide
        with."""
        rng = np.random.default_rng(54)
        a, vals, mids = self._kv(rng, 300)
        b, _, _ = self._kv(rng, 300)
        ha = hash_words(a)
        c = ResultCache(3, 16, capacity_pow2=10)
        c.insert(a, vals, mids, 1, ha)

        def delta(fn):
            p0, v0 = c.probes, c.key_verifies
            fn()
            return c.probes - p0, c.key_verifies - v0

        p, v = delta(lambda: c.lookup(b, 1))  # cold
        assert p >= 300 and v == 0
        p, v = delta(lambda: c.lookup(a, 1, ha))  # repeats
        assert p >= 300 and v == 300
        p, v = delta(lambda: c.lookup(b, 1, ha))  # planted collisions
        assert p >= 300 and v >= 300
        assert c.key_verifies <= c.probes

    def test_probe_counters_mirrored_in_registry(self):
        rng = np.random.default_rng(55)
        _, _, pipe = _pipeline(batch_size=32)
        wire = _wire(rng, 96)
        pipe.submit(wire)
        pipe.drain()
        pipe.submit(wire)  # served from the cache: every row reads its key
        pipe.drain()
        snap = pipe.obs.registry.snapshot()
        probes = snap["cache_probes_total"]['shard="0"']
        verifies = snap["cache_key_verifies_total"]['shard="0"']
        assert probes == pipe.cache.probes > 0
        assert verifies == pipe.cache.key_verifies >= 96


# ---------------------------------------------------------------------------
# _dedup_rows
# ---------------------------------------------------------------------------


def _dedup_reference(words, hashes):
    """Word-by-word reference of ``_dedup_rows``: stable sort on the folded
    hash, then a new group wherever a row differs from its sort neighbour
    in hash or in any word; rank counts earlier rows of the same group."""
    order = np.argsort(hashes.astype(np.uint32), kind="stable")
    group, uniq, prev = [], [], None
    for i in order:
        cur = (int(hashes[i]), tuple(words[i].tolist()))
        if cur != prev:
            uniq.append(i)
        group.append(len(uniq) - 1)
        prev = cur
    inverse = np.empty(len(order), np.int64)
    inverse[order] = group
    seen = {}
    rank = np.empty(len(order), np.int64)
    for i, g in enumerate(inverse):
        rank[i] = seen.get(g, 0)
        seen[g] = rank[i] + 1
    return np.array(uniq, np.int64), inverse, rank


class TestDedupRows:
    @pytest.mark.parametrize("fold_bits", [64, 3, 0])
    @pytest.mark.parametrize("dup_share", [0.0, 0.5])
    def test_matches_word_by_word_reference(self, fold_bits, dup_share):
        """Equal hashes on distinct rows (``fold_bits`` < 64 plants
        collisions) and real duplicates: the hash-first compare gives the
        reference's ``uniq_idx``, ``inverse`` and ``rank`` exactly."""
        rng = np.random.default_rng(60 + fold_bits)
        n = 400
        base = pack_rows(rng.integers(0, 256, (n, 21)).astype(np.uint8), 3)
        src = np.where(rng.random(n) < dup_share,
                       rng.integers(0, max(1, n // 8), n), np.arange(n))
        words = base[src]
        h = hash_words(words)
        if fold_bits < 64:
            h &= np.uint64((1 << fold_bits) - 1)
        uniq, inverse, rank = _dedup_rows(words, h, want_rank=True)
        r_uniq, r_inverse, r_rank = _dedup_reference(words, h)
        np.testing.assert_array_equal(uniq, r_uniq)
        np.testing.assert_array_equal(inverse, r_inverse)
        np.testing.assert_array_equal(rank, r_rank)
        np.testing.assert_array_equal(words[uniq][inverse], words)
        u2, i2 = _dedup_rows(words, h)
        np.testing.assert_array_equal(u2, uniq)
        np.testing.assert_array_equal(i2, inverse)


# ---------------------------------------------------------------------------
# IngressPipeline
# ---------------------------------------------------------------------------


class TestPipelineCorrectness:
    def test_matches_engine_any_arrival_pattern(self):
        """Ragged chunks, duplicates, unknown Model IDs: per-packet egress
        equals the engine run on the concatenated trace, in submission
        order."""
        rng = np.random.default_rng(11)
        cp, eng, pipe = _pipeline(batch_size=64)
        chunks = [_wire(rng, n, model_lo=10, model_hi=16)  # 14,15 unknown
                  for n in (13, 64, 7, 129, 1, 64)]
        chunks.append(chunks[0].copy())  # whole-chunk duplicate
        for ch in chunks:
            pipe.submit(ch)
        got = pipe.drain()
        allpk = np.concatenate(chunks, 0)
        want = np.asarray(eng.process(allpk))[:, : pipe.out_bytes]
        np.testing.assert_array_equal(np.stack(got), want)

    def test_zero_retraces_across_ragged_arrivals(self):
        """The acceptance property: arrival raggedness never changes the
        device batch shape, so the data plane compiles exactly once."""
        rng = np.random.default_rng(12)
        cp, eng, pipe = _pipeline(batch_size=32)
        for n in (1, 31, 32, 33, 100, 7, 64, 5):
            pipe.submit(_wire(rng, n))
            pipe.flush()
        pipe.drain()
        assert eng.trace_count == 1

    def test_duplicates_short_circuit_device(self):
        """Byte-identical packets must not multiply device work: one window
        of N distinct rows repeated k times dispatches N rows once."""
        rng = np.random.default_rng(13)
        cp, eng, pipe = _pipeline(batch_size=64)
        base = _wire(rng, 64)
        for _ in range(4):
            pipe.submit(base)
        pipe.flush()
        assert pipe.stats["ingress_dispatched_rows_total"] == 64
        assert (pipe.stats["ingress_coalesced_total"]
                + pipe.stats["ingress_cache_hits_total"]) == 3 * 64
        got = pipe.drain()
        want = np.asarray(eng.process(base))[:, : pipe.out_bytes]
        for k in range(4):
            np.testing.assert_array_equal(np.stack(got[64 * k: 64 * (k + 1)]),
                                          want)

    def test_half_duplicate_trace_short_circuits_every_repeat(self):
        """A cold pass over a trace in which half of each chunk repeats an
        earlier packet (of any earlier chunk or its own): every repeat
        resolves without device work, by in-chunk dedup, pending-window
        coalescing or a result-cache hit, so the short-circuit share is
        the trace's duplicate share."""
        rng = np.random.default_rng(16)
        cp, eng, pipe = _pipeline(batch_size=64, max_inflight=2)
        chunk, n_chunks = 64, 16
        pool = _wire(rng, chunk // 2 * n_chunks, model_lo=10, model_hi=14)
        idx = []
        for c in range(n_chunks):
            fresh = np.arange(c * chunk // 2, (c + 1) * chunk // 2)
            repeats = rng.integers(0, fresh[-1] + 1, chunk // 2)
            idx.append(rng.permutation(np.concatenate([fresh, repeats])))
        trace = pool[np.concatenate(idx)]
        for c in range(n_chunks):
            pipe.submit(trace[c * chunk:(c + 1) * chunk])
        got = pipe.drain()
        short = (pipe.stats["ingress_coalesced_total"]
                 + pipe.stats["ingress_cache_hits_total"])
        assert short == trace.shape[0] // 2
        assert short / trace.shape[0] >= 0.45
        want = np.asarray(eng.process(trace))[:, : pipe.out_bytes]
        np.testing.assert_array_equal(np.stack(got), want)

    def test_cache_serves_across_windows(self):
        rng = np.random.default_rng(14)
        cp, eng, pipe = _pipeline(batch_size=32)
        base = _wire(rng, 48)
        pipe.submit(base)
        first = pipe.drain()
        d0 = pipe.stats["ingress_dispatched_rows_total"]
        pipe.submit(base)
        second = pipe.drain()
        assert pipe.stats["ingress_dispatched_rows_total"] == d0  # pure cache serve
        np.testing.assert_array_equal(np.stack(first), np.stack(second))

    def test_partial_batch_padding_rows_are_dead(self):
        """Padding rows carry Model ID 0 (not installed) — they must not
        leak into any ticket's result."""
        rng = np.random.default_rng(15)
        cp, eng, pipe = _pipeline(batch_size=256)
        ch = _wire(rng, 3)
        pipe.submit(ch)
        got = pipe.drain()
        want = np.asarray(eng.process(ch))[:, : pipe.out_bytes]
        np.testing.assert_array_equal(np.stack(got), want)
        assert pipe.stats["ingress_padded_rows_total"] == 253

    def test_short_wire_rows_are_padded_to_shape(self):
        """Chunks narrower than the parser bound ride the same fixed wire
        shape (zero-padded) — no retrace, same semantics."""
        rng = np.random.default_rng(16)
        cp, eng, pipe = _pipeline(batch_size=16)
        mids = rng.integers(10, 14, 8).astype(np.int32)
        codes = rng.integers(-500, 500, (8, 3)).astype(np.int32)  # 3 features
        short = np.asarray(pk.encode_packets(
            jnp.asarray(mids), jnp.int32(FRAC), jnp.asarray(codes)))
        assert short.shape[1] < pipe.wire_bytes
        pipe.submit(short)
        got = pipe.drain()
        padded = np.zeros((8, pipe.wire_bytes), np.uint8)
        padded[:, : short.shape[1]] = short
        want = np.asarray(eng.process(padded))[:, : pipe.out_bytes]
        np.testing.assert_array_equal(np.stack(got), want)


class _Scripted:
    """A device result whose readiness the test decides."""

    def __init__(self, out, ready):
        self._out = out
        self._ready = ready

    def is_ready(self):
        return self._ready

    def block_until_ready(self):
        return self

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self._out, dtype)


class TestRetiresReadyCounter:
    """``ingress_retires_ready_total`` counts the retires whose batch had
    already been computed when the retire began."""

    def test_registered_at_zero_and_counts_ready_retires(self, monkeypatch):
        rng = np.random.default_rng(71)
        _, eng, pipe = _pipeline(batch_size=32, max_inflight=2)
        snap = pipe.obs.registry.snapshot()
        assert snap["ingress_retires_ready_total"]['shard="0"'] == 0
        assert pipe.stats["ingress_retires_ready_total"] == 0
        real = eng._program
        runs = []

        def scripted(args, use_mlp, use_forest):
            prog = real(args, use_mlp, use_forest)

            def run(*a):
                runs.append(1)
                return _Scripted(prog(*a), len(runs) % 3 != 2)
            return run
        monkeypatch.setattr(eng, "_program", scripted)
        wire = _wire(rng, 7 * 32)        # distinct rows: seven full batches
        want = np.asarray(eng.process(wire))[:, : pipe.out_bytes]
        ready_seen = []
        for k in range(7):
            pipe.submit(wire[32 * k: 32 * (k + 1)])
            n = pipe.stats["ingress_retires_ready_total"]
            assert n <= pipe.stats["ingress_batches_total"]
            ready_seen.append(n)
        got = pipe.drain()
        np.testing.assert_array_equal(np.stack(got), want)
        assert pipe.stats["ingress_batches_total"] == len(runs) == 7
        # batches 2 and 5 were still cooking when they retired
        assert pipe.stats["ingress_retires_ready_total"] == 5
        assert ready_seen == sorted(ready_seen)
        snap = pipe.obs.registry.snapshot()
        assert snap["ingress_retires_ready_total"]['shard="0"'] == 5

    def test_one_per_retire_of_a_finished_batch(self):
        rng = np.random.default_rng(72)
        _, _, pipe = _pipeline(batch_size=32, max_inflight=4)
        for k in range(3):
            pipe.submit(_wire(rng, 32))
            assert len(pipe._inflight) == k + 1
            pipe._inflight[-1].future.block_until_ready()
        pipe.flush()
        assert pipe.stats["ingress_batches_total"] == 3
        assert pipe.stats["ingress_retires_ready_total"] == 3
        snap = pipe.obs.registry.snapshot()
        assert snap["ingress_retires_ready_total"]['shard="0"'] == 3


class _FakeClock:
    """Deterministic injectable clock: age-based pipeline behavior is
    tested by advancing time, not by sleeping against the scheduler."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


class TestFlushAfter:
    """PR-3 satellite: the ``flush_after`` latency knob (first step of the
    ROADMAP adaptive-batch-sizing item); the injected monotonic clock makes
    every age-based case deterministic."""

    def test_default_preserves_wait_for_flush_behavior(self):
        rng = np.random.default_rng(50)
        cp, eng, pipe = _pipeline(batch_size=64)
        pipe.submit(_wire(rng, 10))
        pipe.submit(_wire(rng, 10))
        assert pipe.stats["ingress_batches_total"] == 0  # partial batch waits, as before
        pipe.drain()

    def test_zero_age_dispatches_every_submit(self):
        rng = np.random.default_rng(51)
        cp, eng, pipe = _pipeline(batch_size=64, flush_after=0.0)
        pipe.submit(_wire(rng, 10))
        assert pipe.stats["ingress_batches_total"] == 1  # padded partial batch went out
        pipe.submit(_wire(rng, 7))
        assert pipe.stats["ingress_batches_total"] == 2
        got = pipe.drain()
        assert len(got) == 17 and all(
            not isinstance(g, PacketError) for g in got)

    def test_aged_partial_batch_dispatches_on_next_submit(self):
        clock = _FakeClock()
        rng = np.random.default_rng(52)
        cp, eng, pipe = _pipeline(batch_size=64, flush_after=0.02,
                                  clock=clock)
        pipe.submit(_wire(rng, 5))
        assert pipe.stats["ingress_batches_total"] == 0  # too young
        clock.advance(0.03)
        pipe.submit(_wire(rng, 5))  # age check fires at submit end
        assert pipe.stats["ingress_batches_total"] == 1
        pipe.drain()

    def test_poll_flushes_without_new_traffic(self):
        clock = _FakeClock()
        rng = np.random.default_rng(53)
        cp, eng, pipe = _pipeline(batch_size=64, flush_after=0.02,
                                  clock=clock)
        pipe.submit(_wire(rng, 5))
        assert not pipe.poll()  # too young
        clock.advance(0.03)
        assert pipe.poll()
        assert pipe.stats["ingress_batches_total"] == 1
        pipe.drain()

    def test_age_boundary_is_inclusive_and_exact(self):
        """The injected clock makes the boundary testable: a batch exactly
        flush_after old dispatches, one tick younger does not — previously
        unverifiable without racing the scheduler."""
        clock = _FakeClock()
        rng = np.random.default_rng(55)
        cp, eng, pipe = _pipeline(batch_size=64, flush_after=0.02,
                                  clock=clock)
        pipe.submit(_wire(rng, 5))
        clock.advance(0.0199)
        assert not pipe.poll()  # strictly younger: stays staged
        clock.advance(0.0001)
        assert pipe.poll()  # age == flush_after: dispatches
        pipe.drain()

    def test_each_family_batch_ages_on_its_own_clock(self):
        """With forests installed, the MLP and forest staging batches carry
        independent t0s — only the over-age one dispatches."""
        from repro.data.packets import anomaly_dataset
        from repro.forest import train_forest
        clock = _FakeClock()
        rng = np.random.default_rng(56)
        cp, eng, pipe = _pipeline(batch_size=64, flush_after=0.02,
                                  clock=clock)
        X, y = anomaly_dataset(rng, 256, WIDTH)
        cp.install_forest(
            30, train_forest(X, y, task="classify", n_trees=2, max_depth=3,
                             max_nodes=15, seed=1))
        pipe.submit(_wire(rng, 5))  # MLP family batch opens at t=0
        clock.advance(0.015)
        mids = np.full(4, 30, np.int32)
        codes = rng.integers(-500, 500, (4, WIDTH)).astype(np.int32)
        pipe.submit(np.asarray(pk.encode_packets(
            jnp.asarray(mids), jnp.int32(FRAC), jnp.asarray(codes))))
        clock.advance(0.010)  # MLP batch is 25ms old, forest batch 10ms
        assert pipe.poll()
        assert pipe.stats["lane_batches"]["mlp"] == 1
        assert pipe.stats["lane_batches"]["forest"] == 0
        clock.advance(0.015)  # now the forest batch crosses the knob
        assert pipe.poll()
        assert pipe.stats["lane_batches"]["forest"] == 1
        pipe.drain()

    def test_results_identical_with_knob_enabled(self):
        """Early dispatch is a latency policy, never a semantics change."""
        rng = np.random.default_rng(54)
        cp, eng, pipe = _pipeline(batch_size=64, flush_after=0.0)
        chunks = [_wire(rng, n) for n in (13, 64, 7, 29)]
        for ch in chunks:
            pipe.submit(ch)
        got = pipe.drain()
        want = np.asarray(eng.process(np.concatenate(chunks, 0)))
        np.testing.assert_array_equal(np.stack(got),
                                      want[:, : pipe.out_bytes])

    def test_negative_flush_after_rejected(self):
        with pytest.raises(ValueError, match="flush_after"):
            _pipeline(flush_after=-0.1)


class TestPipelineErrorSlots:
    @pytest.mark.parametrize("surface,bad_len", [
        ("pipeline", pk.HEADER_BYTES + 4 * (WIDTH + 1)),  # a lane too long
        ("server", 3),                                    # under a header
    ], ids=["pipeline", "server"])
    def test_malformed_chunks_occupy_ordered_slots(self, surface, bad_len):
        """A chunk of the wrong wire length (too long, or shorter than the
        header) resolves as per-packet error slots at its own submission
        positions, through the pipeline and through the server, and the
        packets after it do not shift."""
        rng = np.random.default_rng(17)
        if surface == "pipeline":
            cp, eng, pipe = _pipeline(batch_size=32)
            submit, drain = pipe.submit, pipe.drain
        else:
            srv, rng = TestServerIntegration._server(ingress_batch=32,
                                                     max_inflight=2)
            eng, pipe = srv.engine, srv.ingress
            submit, drain = srv.submit_packets, srv.drain_packets
        good1, good2 = _wire(rng, 5), _wire(rng, 6)
        submit(good1)
        submit(np.zeros((3, bad_len), np.uint8))
        submit(good2)
        got = drain()
        assert len(got) == 14
        want = np.asarray(eng.process(np.concatenate([good1, good2])))
        for i in range(5):
            np.testing.assert_array_equal(got[i], want[i][: pipe.out_bytes])
        for i in range(5, 8):
            assert isinstance(got[i], PacketError)
            assert "wire length" in got[i].reason
        for i in range(8, 14):
            np.testing.assert_array_equal(got[i],
                                          want[i - 3][: pipe.out_bytes])

    def test_feature_count_overflow_is_per_packet(self):
        rng = np.random.default_rng(18)
        cp, eng, pipe = _pipeline(batch_size=16)
        ch = _wire(rng, 4).copy()
        ch[2, 2] = WIDTH + 1  # declared feature count beyond parser bound
        pipe.submit(ch)
        got = pipe.drain()
        assert isinstance(got[2], PacketError)
        assert "feature count" in got[2].reason
        keep = [0, 1, 3]
        want = np.asarray(eng.process(ch[keep]))[:, : pipe.out_bytes]
        np.testing.assert_array_equal(np.stack([got[i] for i in keep]), want)

    def test_non_2d_chunk_raises(self):
        cp, eng, pipe = _pipeline()
        with pytest.raises(ValueError):
            pipe.submit(np.zeros(16, np.uint8))


class TestCacheStalenessEndToEnd:
    """The acceptance property: zero stale cache hits under concurrent
    install()/remove()."""

    def test_install_between_windows_redispatches(self):
        rng = np.random.default_rng(19)
        cp, eng, pipe = _pipeline(batch_size=32)
        base = _wire(rng, 32, model_lo=10, model_hi=11)  # all model 10
        pipe.submit(base)
        old = np.stack(pipe.drain())
        _install(cp, rng, 10, scale=0.9)  # retrain/hot-swap model 10
        pipe.submit(base)
        new = np.stack(pipe.drain())
        want_new = np.asarray(eng.process(base))[:, : pipe.out_bytes]
        np.testing.assert_array_equal(new, want_new)
        assert not np.array_equal(old, new)  # weights really changed

    def test_install_mid_window_no_stale_serving(self):
        """First occurrence dispatched under gen g and in flight; install
        bumps to g+1; a later duplicate must re-dispatch under g+1, never
        ride the stale pending/cache entry."""
        rng = np.random.default_rng(20)
        cp, eng, pipe = _pipeline(batch_size=32, max_inflight=2)
        base = _wire(rng, 32, model_lo=10, model_hi=11)
        want_old = np.asarray(eng.process(base))[:, : pipe.out_bytes]
        pipe.submit(base)              # dispatched under the old generation
        _install(cp, rng, 10, scale=0.9)
        pipe.submit(base)              # same bytes, new generation
        got = pipe.drain()
        want_new = np.asarray(eng.process(base))[:, : pipe.out_bytes]
        np.testing.assert_array_equal(np.stack(got[:32]), want_old)
        np.testing.assert_array_equal(np.stack(got[32:]), want_new)
        assert not np.array_equal(want_old, want_new)

    def test_remove_drops_model_entries_and_unroutes(self):
        rng = np.random.default_rng(21)
        cp, eng, pipe = _pipeline(batch_size=32)
        base = _wire(rng, 16, model_lo=10, model_hi=11)
        pipe.submit(base)
        pipe.drain()
        assert pipe.cache.contains_model(10)
        cp.remove(10)
        pipe.on_model_removed(10)
        assert not pipe.cache.contains_model(10)
        pipe.submit(base)
        got = np.stack(pipe.drain())
        want = np.asarray(eng.process(base))[:, : pipe.out_bytes]
        np.testing.assert_array_equal(got, want)  # zeroed egress, not stale

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=10 ** 6),
           n=st.integers(min_value=1, max_value=96))
    def test_property_duplicates_across_generations(self, seed, n):
        """For arbitrary traffic, resubmitting the same bytes after an
        install must serve the *new* generation's outputs exactly."""
        rng = np.random.default_rng(seed)
        cp, eng, pipe = _pipeline(batch_size=16, seed=seed)
        base = _wire(rng, n)
        pipe.submit(base)
        pipe.drain()
        _install(cp, rng, 11, scale=0.7)
        pipe.submit(base)
        got = np.stack(pipe.drain())
        want = np.asarray(eng.process(base))[:, : pipe.out_bytes]
        np.testing.assert_array_equal(got, want)


class TestServerIntegration:
    @staticmethod
    def _server(**kw):
        from repro.launch.serve import PacketServer
        rng = np.random.default_rng(22)
        srv = PacketServer(max_models=4, max_layers=2, max_width=WIDTH,
                           frac_bits=FRAC, **kw)
        for m in range(4):
            _install(srv.control_plane, rng, 10 + m)
        return srv, rng

    def test_remove_via_server_drops_cache(self):
        srv, rng = self._server()
        base = _wire(rng, 8, model_lo=10, model_hi=11)
        srv.submit_packets(base)
        srv.drain_packets()
        assert srv.ingress.cache.contains_model(10)
        srv.remove(10)
        assert not srv.ingress.cache.contains_model(10)
        assert srv.stats()["cache_entries"] == 0

    @pytest.mark.parametrize("sizes,ingress_batch,max_inflight", [
        ((5, 40, 17), 32, 8),       # ragged chunks across batch edges
        ((64,) * 7, 64, 3),         # whole batches through a 3-deep window
    ], ids=["ragged", "window"])
    def test_stream_results_match_sync(self, sizes, ingress_batch,
                                       max_inflight):
        srv, rng = self._server(ingress_batch=ingress_batch,
                                max_inflight=max_inflight)
        chunks = [_wire(rng, n) for n in sizes]
        for ch in chunks:
            srv.submit_packets(ch)
        got = srv.drain_packets()
        want = np.asarray(srv.process(np.concatenate(chunks)))
        np.testing.assert_array_equal(
            np.stack(got), want[:, : srv.ingress.out_bytes])
