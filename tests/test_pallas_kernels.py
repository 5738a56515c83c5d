"""Pallas aggregation kernel: correctness in interpreter mode (CPU CI).

Real-hardware timing lives in bench_kernels.py; this only pins semantics
(padding, trash-slot handling, K chunking boundaries) against the numpy
oracle."""

import numpy as np
import pytest

from citus_tpu.ops.pallas_kernels import (
    dense_grid_aggregate_pallas,
    segment_sum_reference,
)


@pytest.mark.parametrize("n,total", [
    (100, 5),          # tiny, sub-tile
    (3000, 16),        # multi-tile rows
    (5000, 513),       # K crosses a chunk boundary
    (2048, 1024),      # exact tiles
])
def test_matches_numpy_oracle(rng, n, total):
    slot = rng.integers(0, total + 1, n).astype(np.int32)  # incl. trash
    vals = rng.uniform(-50, 50, (n, 3)).astype(np.float32)
    got = np.asarray(dense_grid_aggregate_pallas(
        slot, vals, total, interpret=True))
    want = segment_sum_reference(slot, vals, total)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-3)


def test_empty_and_single_slot(rng):
    vals = rng.uniform(0, 1, (64, 2)).astype(np.float32)
    slot = np.zeros(64, np.int32)
    got = np.asarray(dense_grid_aggregate_pallas(
        slot, vals, 1, interpret=True))
    np.testing.assert_allclose(got[0], vals.sum(axis=0), rtol=1e-5)
    # all rows in the trash slot → zeros
    slot_trash = np.full(64, 3, np.int32)
    got = np.asarray(dense_grid_aggregate_pallas(
        slot_trash, vals, 3, interpret=True))
    assert np.abs(got).sum() == 0


class TestBucketedGroupbySums:
    """Bucket-tiled MXU segment-sum (bucketed_groupby_sums_pallas) vs
    numpy oracle in interpreter mode: bucket batching, cap padding,
    sub-chunk tiles, and parity with the XLA formulation inside
    bucketed_grid_aggregate."""

    @pytest.mark.parametrize("nb,cap,tile,a", [
        (1, 100, 64, 3),      # single bucket, tile below one K chunk
        (7, 333, 128, 1),     # ragged cap, multi-bucket
        (4, 1100, 512, 5),    # cap crosses a row-tile boundary
        (2, 2048, 4096, 6),   # full-size tile, exact rows
    ])
    def test_matches_numpy_oracle(self, rng, nb, cap, tile, a):
        from citus_tpu.ops.pallas_kernels import (
            bucketed_groupby_sums_pallas,
            groupby_sums_reference,
        )

        loc = rng.integers(0, tile, (nb, cap)).astype(np.int32)
        stack = rng.uniform(-20, 20, (nb, cap, a)).astype(np.float32)
        got = np.asarray(bucketed_groupby_sums_pallas(
            loc, stack, tile, interpret=True))
        want = groupby_sums_reference(loc, stack, tile)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-2)

    def test_op_level_parity_with_xla(self, rng):
        # bucketed_grid_aggregate(kernel='pallas', interpret=True) must
        # match the XLA formulation bit-for-bit on counts and closely
        # on f32 sums (same accumulation dtype, different order)
        import jax.numpy as jnp

        import citus_tpu.ops.groupby as G

        n, total = 3000, 300
        slot = jnp.asarray(rng.integers(0, total, n).astype(np.int32))
        valid = jnp.asarray(rng.random(n) > 0.1)
        v = jnp.asarray(rng.normal(size=n).astype(np.float32))
        values = [(jnp.where(valid, v, 0.0), "sum"),
                  (jnp.asarray(np.ones(n, np.int32)), "count")]
        orig_tile = G.GROUP_TILE_SLOTS
        try:
            G.GROUP_TILE_SLOTS = 64
            rx = G.bucketed_grid_aggregate(slot, valid, values, total,
                                           kernel="xla")
            rp = G.bucketed_grid_aggregate(slot, valid, values, total,
                                           kernel="pallas",
                                           interpret=True)
        finally:
            G.GROUP_TILE_SLOTS = orig_tile
        np.testing.assert_allclose(np.asarray(rx[0][0]),
                                   np.asarray(rp[0][0]),
                                   rtol=1e-4, atol=1e-3)
        np.testing.assert_array_equal(np.asarray(rx[0][1]),
                                      np.asarray(rp[0][1]))
        np.testing.assert_array_equal(np.asarray(rx[1]),
                                      np.asarray(rp[1]))
