"""Kernel library tests on the CPU backend, cross-checked against numpy
oracles (the framework's version of the reference's query-generator
cross-check strategy, SURVEY §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from citus_tpu.catalog.distribution import (
    hash_token,
    shard_index_for_values,
)
from citus_tpu.executor.batch import Block, block_from_numpy, compact_to_numpy
from citus_tpu.ops import (
    expand_join,
    hash_token_jax,
    lookup_join,
    match_counts,
    pack_by_target,
    segment_aggregate,
    shard_index_for_values_jax,
)


class TestHashingParity:
    """Host (numpy) and device (jax) hashing must agree bit-for-bit —
    the routing contract for shuffles."""

    @pytest.mark.parametrize("dtype", [np.int32, np.int64, np.float32,
                                       np.float64])
    def test_bit_equality(self, rng, dtype):
        if np.issubdtype(dtype, np.integer):
            vals = rng.integers(-1_000_000, 1_000_000, 5000).astype(dtype)
        else:
            vals = rng.normal(size=5000).astype(dtype)
        host = hash_token(vals)
        dev = np.asarray(hash_token_jax(jnp.asarray(vals)))
        np.testing.assert_array_equal(host, dev)

    def test_shard_routing_parity(self, rng):
        vals = rng.integers(0, 10**9, 10_000).astype(np.int64)
        host = shard_index_for_values(vals, 7)
        dev = np.asarray(shard_index_for_values_jax(jnp.asarray(vals), 7))
        np.testing.assert_array_equal(host, dev)


class TestSegmentAggregate:
    def _oracle(self, keys, vals, valid):
        out = {}
        for i in range(len(valid)):
            if not valid[i]:
                continue
            k = tuple(int(a[i]) for a in keys)
            s = out.setdefault(k, [0.0, 0])
            s[0] += float(vals[i])
            s[1] += 1
        return out

    def test_matches_oracle_single_key(self, rng):
        n = 4000
        keys = [rng.integers(0, 50, n).astype(np.int64)]
        vals = rng.normal(size=n)
        valid = rng.random(n) > 0.1
        gk, res, gv, ng = segment_aggregate(
            [jnp.asarray(keys[0])],
            [(jnp.asarray(vals), "sum", None),
             (jnp.asarray(vals), "count", None),
             (jnp.asarray(vals), "min", None),
             (jnp.asarray(vals), "max", None)],
            jnp.asarray(valid))
        oracle = self._oracle(keys, vals, valid)
        assert int(ng) == len(oracle)
        got = {}
        for i in range(int(ng)):
            got[(int(gk[0][i]),)] = (float(res[0][i]), int(res[1][i]),
                                     float(res[2][i]), float(res[3][i]))
        for k, (s, c) in oracle.items():
            gs, gc, gmn, gmx = got[k]
            assert gc == c
            np.testing.assert_allclose(gs, s, rtol=1e-9)
            mask = (keys[0] == k[0]) & valid
            assert gmn == vals[mask].min()
            assert gmx == vals[mask].max()

    def test_multi_key_grouping(self, rng):
        n = 2000
        k1 = rng.integers(0, 5, n).astype(np.int32)
        k2 = rng.integers(0, 7, n).astype(np.int32)
        valid = np.ones(n, dtype=bool)
        gk, res, gv, ng = segment_aggregate(
            [jnp.asarray(k1), jnp.asarray(k2)],
            [(jnp.asarray(np.ones(n)), "sum", None)],
            jnp.asarray(valid))
        # all 35 combinations present with overwhelming probability
        assert int(ng) == 35
        total = float(jnp.where(gv, res[0], 0).sum())
        assert total == n

    def test_null_values_skipped(self):
        k = jnp.asarray(np.zeros(6, np.int32))
        v = jnp.asarray(np.array([1.0, 2, 3, 4, 5, 6]))
        vv = jnp.asarray(np.array([True, False, True, False, True, False]))
        valid = jnp.ones(6, dtype=bool)
        _, res, _, ng = segment_aggregate(
            [k], [(v, "sum", vv), (v, "count", vv)], valid)
        assert int(ng) == 1
        assert float(res[0][0]) == 1 + 3 + 5
        assert int(res[1][0]) == 3

    def test_all_invalid_rows(self):
        k = jnp.asarray(np.arange(4, dtype=np.int64))
        valid = jnp.zeros(4, dtype=bool)
        _, res, gv, ng = segment_aggregate(
            [k], [(jnp.asarray(np.ones(4)), "sum", None)], valid)
        assert int(ng) == 0
        assert not bool(gv.any())

    def test_jit_compiles_once_static_shape(self, rng):
        # shapes stay static: jit must trace once for same-capacity inputs
        traces = []

        @jax.jit
        def run(k, v, valid):
            traces.append(1)
            _, res, gv, ng = segment_aggregate([k], [(v, "sum", None)], valid)
            return res[0], gv, ng

        for _ in range(3):
            n = 1000
            k = jnp.asarray(rng.integers(0, 10, n).astype(np.int64))
            v = jnp.asarray(rng.normal(size=n))
            run(k, v, jnp.ones(n, dtype=bool))
        assert len(traces) == 1


class TestLookupJoin:
    def test_pk_fk_join_matches_dict_oracle(self, rng):
        m, n = 500, 3000
        build_k = np.arange(m, dtype=np.int64)
        rng.shuffle(build_k)
        probe_k = rng.integers(-50, m + 50, n).astype(np.int64)
        bv = np.ones(m, bool)
        pv = np.ones(n, bool)
        idx, found = lookup_join([jnp.asarray(build_k)], jnp.asarray(bv),
                                 [jnp.asarray(probe_k)], jnp.asarray(pv))
        idx, found = np.asarray(idx), np.asarray(found)
        table = {int(k): i for i, k in enumerate(build_k)}
        for i in range(n):
            if int(probe_k[i]) in table:
                assert found[i]
                assert idx[i] == table[int(probe_k[i])]
            else:
                assert not found[i]

    def test_multi_key_exact_no_collisions(self, rng):
        # two-column key where a hash-combine would risk collisions;
        # lexicographic search must be exact
        m = 300
        k1 = rng.integers(0, 20, m).astype(np.int64)
        k2 = rng.integers(0, 20, m).astype(np.int64)
        # dedupe build pairs
        pairs = {}
        for i in range(m):
            pairs[(int(k1[i]), int(k2[i]))] = i
        uk = np.array([p[0] for p in pairs], dtype=np.int64)
        uv = np.array([p[1] for p in pairs], dtype=np.int64)
        bm = len(uk)
        probe1 = rng.integers(0, 25, 1000).astype(np.int64)
        probe2 = rng.integers(0, 25, 1000).astype(np.int64)
        idx, found = lookup_join(
            [jnp.asarray(uk), jnp.asarray(uv)], jnp.ones(bm, bool),
            [jnp.asarray(probe1), jnp.asarray(probe2)], jnp.ones(1000, bool))
        idx, found = np.asarray(idx), np.asarray(found)
        for i in range(1000):
            expect = (int(probe1[i]), int(probe2[i])) in pairs
            assert bool(found[i]) == expect
            if expect:
                assert (int(uk[idx[i]]), int(uv[idx[i]])) == (
                    int(probe1[i]), int(probe2[i]))

    def test_invalid_build_rows_never_match(self, rng):
        build_k = np.array([1, 2, 3, 4], dtype=np.int64)
        bv = np.array([True, False, True, False])
        probe_k = np.array([1, 2, 3, 4], dtype=np.int64)
        idx, found = lookup_join([jnp.asarray(build_k)], jnp.asarray(bv),
                                 [jnp.asarray(probe_k)],
                                 jnp.ones(4, bool))
        np.testing.assert_array_equal(np.asarray(found),
                                      [True, False, True, False])

    def test_match_counts(self, rng):
        build_k = np.array([5, 5, 5, 7, 9], dtype=np.int64)
        probe_k = np.array([5, 7, 8, 9], dtype=np.int64)
        counts = match_counts([jnp.asarray(build_k)], jnp.ones(5, bool),
                              [jnp.asarray(probe_k)], jnp.ones(4, bool))
        np.testing.assert_array_equal(np.asarray(counts), [3, 1, 0, 1])

    def test_expand_join_many_to_many(self, rng):
        build_k = np.array([1, 1, 2, 3, 3, 3], dtype=np.int64)
        probe_k = np.array([3, 1, 4, 3], dtype=np.int64)
        bidx, pidx, ov, overflow = expand_join(
            [jnp.asarray(build_k)], jnp.ones(6, bool),
            [jnp.asarray(probe_k)], jnp.ones(4, bool), capacity=16)
        assert int(overflow) == 0
        got = set()
        for b, p, v in zip(np.asarray(bidx), np.asarray(pidx),
                           np.asarray(ov)):
            if v:
                got.add((int(b), int(p)))
        expect = {(b, p) for p in range(4) for b in range(6)
                  if build_k[b] == probe_k[p]}
        assert got == expect  # 3 matches for probe0, 2 for probe1, 3 for probe3

    def test_expand_join_overflow_detected(self):
        build_k = np.zeros(10, dtype=np.int64)
        probe_k = np.zeros(4, dtype=np.int64)
        _, _, ov, overflow = expand_join(
            [jnp.asarray(build_k)], jnp.ones(10, bool),
            [jnp.asarray(probe_k)], jnp.ones(4, bool), capacity=8)
        assert int(overflow) == 40 - 8
        assert int(np.asarray(ov).sum()) == 8


class TestPartitionPack:
    def test_pack_matches_bincount(self, rng):
        n, p, cap = 5000, 8, 1024
        target = rng.integers(0, p, n).astype(np.int32)
        valid = rng.random(n) > 0.2
        key = rng.integers(0, 10**6, n).astype(np.int64)
        packed, pvalid, overflow = pack_by_target(
            {"k": jnp.asarray(key)}, jnp.asarray(valid),
            jnp.asarray(target), p, cap)
        assert int(overflow) == 0
        pvalid = np.asarray(pvalid)
        pk = np.asarray(packed["k"])
        counts = np.bincount(target[valid], minlength=p)
        np.testing.assert_array_equal(pvalid.sum(axis=1), counts)
        # every valid row lands in its own partition with its key intact
        for t in range(p):
            got = sorted(pk[t][pvalid[t]])
            expect = sorted(key[(target == t) & valid])
            np.testing.assert_array_equal(got, expect)

    def test_overflow_counted_and_capped(self, rng):
        n, p, cap = 1000, 4, 100
        target = np.zeros(n, dtype=np.int32)  # extreme skew: all → 0
        packed, pvalid, overflow = pack_by_target(
            {"x": jnp.asarray(np.arange(n))}, jnp.ones(n, bool),
            jnp.asarray(target), p, cap)
        assert int(overflow) == n - cap
        assert int(np.asarray(pvalid)[0].sum()) == cap

    def test_round_trip_through_all_to_all_layout(self, rng):
        # pack on 2 source "devices" → exchange axis 0 → all rows preserved
        n, p, cap = 400, 2, 512
        key = rng.integers(0, 1000, n).astype(np.int64)
        target = (key % p).astype(np.int32)
        packed, pvalid, _ = pack_by_target(
            {"k": jnp.asarray(key)}, jnp.ones(n, bool),
            jnp.asarray(target), p, cap)
        # simulated exchange: partition t of this device goes to device t
        for t in range(p):
            rows = np.asarray(packed["k"][t])[np.asarray(pvalid[t])]
            assert (rows % p == t).all()


class TestBlock:
    def test_pytree_round_trip_under_jit(self, rng):
        b = block_from_numpy({"x": rng.normal(size=100)})

        @jax.jit
        def double(block: Block) -> Block:
            return block.with_column("x", block.column("x") * 2)

        out = double(b)
        np.testing.assert_allclose(np.asarray(out.column("x")),
                                   np.asarray(b.column("x")) * 2)

    def test_padding_and_compact(self, rng):
        vals = {"x": np.arange(10, dtype=np.int64)}
        b = block_from_numpy(vals, capacity=16)
        assert b.capacity == 16
        assert int(b.row_count()) == 10
        out, _ = compact_to_numpy(b.with_filter(b.column("x") % 2 == 0))
        np.testing.assert_array_equal(out["x"], [0, 2, 4, 6, 8])

    def test_nulls_from_storage_validity(self, rng):
        vals = {"x": np.arange(4, dtype=np.int64)}
        b = block_from_numpy(vals, validity={"x": np.array(
            [True, False, True, True])})
        np.testing.assert_array_equal(
            np.asarray(b.null_mask("x")), [False, True, False, False])

    def test_compute_dtype_downcast(self):
        b = block_from_numpy({"x": np.arange(3, dtype=np.float64)},
                             compute_dtype=np.float32)
        assert b.column("x").dtype == jnp.float32


class TestSortedUniqueLookup:
    """Sort-and-scan lookup (ops.join.sorted_unique_lookup) vs the
    single-gather dense_unique_lookup and a dict oracle: the contract
    is dense_unique_lookup's, without a base or an extent."""

    def _lookup(self, bk, bmatch, pk):
        from citus_tpu.ops.join import sorted_unique_lookup

        return tuple(np.asarray(x) for x in sorted_unique_lookup(
            jnp.asarray(bk), jnp.asarray(bmatch), jnp.asarray(pk)))

    def _inputs(self, rng, dtype, base=1000, extent=1000, m=600, n=5000):
        bk = (base + rng.permutation(extent)[:m]).astype(dtype)
        bmatch = rng.random(m) > 0.1
        # a non-matchable build row may carry a matchable row's key
        # (padding rows do): it must match nothing and count no duplicate
        dead = np.flatnonzero(~bmatch)
        bk[dead[::2]] = rng.choice(bk[bmatch], len(dead[::2]))
        pk = rng.integers(base - 100, base + extent + 100, n).astype(dtype)
        return bk, bmatch, pk

    # (base, extent, build rows, probe rows).  The last two are the
    # retired bucketed probe's inputs: its parity shape, and an extent of
    # several thousand slots that is no multiple of anything, most of it
    # taken, under probe keys on both sides of [base, base + extent)
    @pytest.mark.parametrize("shape", [(1000, 1000, 600, 5000),
                                       (0, 512, 300, 2000),
                                       (7, 5003, 4100, 3000)],
                             ids=lambda s: "-".join(map(str, s)))
    @pytest.mark.parametrize("dtype", [np.int32, np.int64])
    def test_matches_dense_lookup_and_oracle(self, rng, dtype, shape):
        from citus_tpu.ops.join import dense_unique_lookup

        base, extent, m, n = shape
        bk, bmatch, pk = self._inputs(rng, dtype, base, extent, m, n)
        assert (pk < base).any() and (pk >= base + extent).any()
        assert not bmatch.all()
        bidx, counts, oob = self._lookup(bk, bmatch, pk)
        assert int(oob) == 0
        assert bidx.shape == counts.shape == pk.shape
        assert bidx.min() >= 0 and bidx.max() < len(bk)
        live = np.flatnonzero(bmatch)
        dbidx, dcounts, _ = (np.asarray(x) for x in dense_unique_lookup(
            jnp.asarray(bk[live]), jnp.ones(len(live), bool),
            jnp.asarray(pk), base, extent))
        np.testing.assert_array_equal(counts, dcounts)
        np.testing.assert_array_equal(bidx[counts > 0],
                                      live[dbidx[dcounts > 0]])
        # dict oracle, row by row: bidx is in ORIGINAL probe order and
        # indexes the ORIGINAL build arrays
        table = {int(k): i for i, k in enumerate(bk) if bmatch[i]}
        for i in range(len(pk)):
            hit = int(pk[i]) in table
            assert bool(counts[i]) == hit
            if hit:
                assert int(bidx[i]) == table[int(pk[i])]

    @pytest.mark.parametrize("dtype", [np.int32, np.int64])
    def test_keys_at_the_ends_of_the_type(self, dtype):
        # the scan's "no build row yet" must not be taken for the
        # smallest key, nor the largest key for a sentinel
        lo, hi = np.iinfo(dtype).min, np.iinfo(dtype).max
        bk = np.array([hi, 5, lo + 1], dtype)
        pk = np.array([lo, lo + 1, 5, hi, hi - 1, lo], dtype)
        bidx, counts, oob = self._lookup(bk, np.ones(3, bool), pk)
        assert counts.tolist() == [0, 1, 1, 1, 0, 0]
        assert bidx[counts > 0].tolist() == [2, 1, 0]
        assert int(oob) == 0
        bidx, counts, _ = self._lookup(
            np.array([lo], dtype), np.ones(1, bool), pk)
        assert counts.tolist() == [1, 0, 0, 0, 0, 1]

    @pytest.mark.parametrize("m,n,live", [(0, 7, 0), (5, 0, 5), (5, 7, 0)])
    def test_empty_sides(self, m, n, live):
        # zero-length arrays, and a build side with no matchable row
        bk = np.arange(m, dtype=np.int64)
        bidx, counts, oob = self._lookup(
            bk, np.arange(m) < live, np.arange(n, dtype=np.int64))
        assert bidx.shape == counts.shape == (n,)
        assert int(counts.sum()) == 0 and int(oob) == 0
        assert (bidx == 0).all()

    @pytest.mark.parametrize("dtype", [np.int32, np.int64])
    def test_duplicate_build_keys_counted_as_oob(self, dtype):
        # stale uniqueness claim: duplicates among MATCHABLE rows must
        # surface through oob (the host retries on the general
        # expansion path — never a silent arbitrary winner); a
        # non-matchable twin is no duplicate
        bk = np.array([1, 2, 2, 3, 900, 2, 3], dtype)
        bmatch = np.array([True, True, True, True, True, True, False])
        pk = np.arange(1, 5).astype(dtype)
        _, _, oob = self._lookup(bk, bmatch, pk)
        assert int(oob) == 2
        _, counts, oob = self._lookup(
            bk, np.array([True, True, False, True, True, False, False]),
            pk)
        assert int(oob) == 0 and counts.tolist() == [1, 1, 1, 0]


class TestBucketedGridAggregate:
    """Bucketed dense-grid aggregation (ops.groupby) vs a numpy oracle:
    sums/counts/min/max and garbage-lane hygiene.  The tile and the
    chunk are patched small so tiny slot spaces still span many
    buckets and tiny inputs many chunks."""

    TILE = 64
    CHUNK = 32

    def _run(self, monkeypatch, slot, valid, values, total, **kw):
        import citus_tpu.ops.groupby as G

        monkeypatch.setattr(G, "GROUP_TILE_SLOTS", self.TILE)
        monkeypatch.setattr(G, "GROUP_CHUNK_ROWS", self.CHUNK)
        res, rows = G.bucketed_grid_aggregate(
            jnp.asarray(slot.astype(np.int32)), jnp.asarray(valid),
            values, total, **kw)
        return [np.asarray(r) for r in res], np.asarray(rows)

    def _inputs(self, rng, n=4000, total=500):
        slot = rng.integers(0, total, n).astype(np.int32)
        valid = rng.random(n) > 0.1
        contrib = rng.random(n) > 0.2
        vf = rng.normal(size=n).astype(np.float32)
        vi = rng.integers(-1000, 1000, n).astype(np.int64)
        return slot, valid, contrib, vf, vi

    def test_matches_oracle_all_kinds(self, rng, monkeypatch):
        n, total = 4000, 500  # not a tile multiple: padded tail
        slot, valid, contrib, vf, vi = self._inputs(rng, n, total)
        c = jnp.asarray(valid & contrib)
        imax = np.iinfo(np.int64).max
        values = [
            (jnp.where(c, jnp.asarray(vf), 0.0), "sum"),
            (jnp.where(c, jnp.asarray(vi), 0), "sum"),
            (jnp.asarray((valid & contrib).astype(np.int32)), "count"),
            (jnp.where(c, jnp.asarray(vi), imax), "min"),
            (jnp.where(c, jnp.asarray(vi), -imax - 1), "max"),
        ]
        res, rows = self._run(monkeypatch, slot, valid, values, total)
        osum = np.zeros(total)
        oisum = np.zeros(total, np.int64)
        ocnt = np.zeros(total, np.int64)
        omin = np.full(total, imax)
        omax = np.full(total, -imax - 1)
        orows = np.zeros(total, np.int64)
        for i in range(n):
            if not valid[i]:
                continue
            orows[slot[i]] += 1
            if contrib[i]:
                osum[slot[i]] += vf[i]
                oisum[slot[i]] += vi[i]
                ocnt[slot[i]] += 1
                omin[slot[i]] = min(omin[slot[i]], vi[i])
                omax[slot[i]] = max(omax[slot[i]], vi[i])
        np.testing.assert_array_equal(rows, orows)
        np.testing.assert_allclose(res[0], osum, rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(res[1], oisum)
        np.testing.assert_array_equal(res[2], ocnt)
        live = ocnt > 0
        np.testing.assert_array_equal(res[3][live], omin[live])
        np.testing.assert_array_equal(res[4][live], omax[live])

    def test_one_hot_bucket_needs_no_capacity(self, rng, monkeypatch):
        # every row lands in bucket 0: the pack is sized by the rows
        # (ceil(300 / 32) + 4 chunks), so nothing is dropped, reported
        # or retried whatever one bucket holds
        n, total = 300, 4 * 64
        slot = np.zeros(n, np.int32)
        valid = np.ones(n, bool)
        values = [(jnp.asarray(np.ones(n, np.int32)), "count")]
        res, rows = self._run(monkeypatch, slot, valid, values, total)
        assert rows[0] == n and int(rows.sum()) == n
        assert res[0][0] == n and int(res[0].sum()) == n

    def test_all_invalid_rows(self, rng, monkeypatch):
        n, total = 64, 128
        values = [(jnp.asarray(np.ones(n, np.int32)), "count")]
        res, rows = self._run(
            monkeypatch, np.zeros(n, np.int32), np.zeros(n, bool),
            values, total)
        assert int(rows.sum()) == 0
        assert int(res[0].sum()) == 0

    def test_matches_flat_segment_path(self, rng, monkeypatch):
        # the segment_sum fallback (wide dtypes / CPU one-hot bound)
        # and the one-hot path must agree exactly for int32 counts
        import citus_tpu.ops.groupby as G

        slot, valid, _c, _vf, vi = self._inputs(rng, 2000, 300)
        values = [(jnp.where(jnp.asarray(valid), jnp.asarray(vi), 0),
                   "sum")]  # int64 → segment path
        res, rows = self._run(monkeypatch, slot, valid, values, 300)
        want = np.zeros(300, np.int64)
        for i in range(2000):
            if valid[i]:
                want[slot[i]] += vi[i]
        np.testing.assert_array_equal(res[0], want)
