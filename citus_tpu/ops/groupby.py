"""Bucketed dense-grid aggregation for high-cardinality GROUP BY.

The sort-path aggregation (ops/aggregate.segment_aggregate) pays a
stable argsort over the input capacity per execution — O(n log n) and
sort-bound on TPU (PERF_NOTES: ~30% of warm Q3 is the 1.5M-row group
sort).  The dense-grid path (executor/compiler._exec_dense_aggregate)
is sort-free but capped at DENSE_GROUP_LIMIT slots: the one-hot MXU
matmul it rides was measured 2-10x faster than segment_sum only while
the slot space stays <= ~4096 wide.

This module removes the cap the radix-partition way (Theseus, arXiv
2508.05029; the GPU hash-aggregation pipeline, arXiv 2606.24647):

  1. rows carry a PACKED dense slot id (the planner's `key_ranges`
     machinery — every group key's value range statically known, one
     int64 slot per composite key, null slot reserved per key),
  2. rows partition by slot high bits (`hashing.tile_buckets`) through
     the same counting-sort pack the repartition shuffle uses
     (`partition.pack_by_target`) into `[n_buckets, bucket_cap]`
     buffers — value-range partitioning over an already-dense slot
     space needs no avalanche mixing,
  3. each bucket reduces over its <= GROUP_TILE_SLOTS-wide dense tile:
     sums/counts through the measured-fastest one-hot `dot_general`
     formulation (batched over buckets; a Pallas variant is A/B'd by
     `bench_kernels.py groupby`),
     min/max through per-tile scatter (segment) reductions — tiles are
     small and bucket-major packing makes the scatters local,
  4. the [total]-slot grid emits exactly like the dense grid today:
     group keys reconstruct from the slot id, `rows_per_slot > 0`
     marks live groups.

Static shapes throughout: a hot bucket overflows its per-bucket
capacity and the host regrows + retries (`Capacities.agg_bucket`, the
same count-then-emit protocol every static buffer uses); realized max
fill feeds capacity feedback.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# slots per bucket tile: the dense-grid one-hot matmul's measured win
# region tops out at ~4096 slots (PERF_NOTES segment-aggregation table:
# 2-10x faster than segment_sum at k <= 4096, slower past 8192), so
# each bucket reduces over exactly one fast-path-sized tile
GROUP_TILE_SLOTS = 4096

# packed-slot-space ceiling for the bucketed grid: the [total] result
# grid (and its psum combine) must stay HBM-reasonable — 2^24 slots is
# 128 MB per int64 aggregate column, comparable to the sort path's
# input-sized outputs under the occupancy gate below
GROUP_BUCKET_MAX_SLOTS = 1 << 24


def group_bucket_count(total: int) -> int:
    """Number of dense tiles covering [0, total)."""
    return max(1, -(-total // GROUP_TILE_SLOTS))


def group_bucket_eligible(total: int, rows: int) -> bool:
    """Planner cost threshold for the bucketed grid: the packed slot
    space must be small enough to materialize as a result grid AND the
    input dense enough to amortize reducing every tile (a sparse
    group-by over a huge key space would stream mostly-empty tiles —
    the sort path stays cheaper there)."""
    return total <= GROUP_BUCKET_MAX_SLOTS and rows * 4 >= total


def _onehot_bucket_sums(loc2d: jnp.ndarray, stack: jnp.ndarray,
                        tile: int) -> jnp.ndarray:
    """Batched one-hot x values matmul: [nb, cap] local slots and
    [nb, cap, A] values -> [nb, tile, A] per-tile sums.  Garbage lanes
    carry zeroed values (pack_by_target zeroes them), so their slot-0
    contribution is exactly zero — no mask operand needed.  XLA fuses
    the one-hot construction into the contraction loop on TPU (the
    measured formulation behind DENSE_ONEHOT_MAX_SLOTS)."""
    ids = jnp.arange(tile, dtype=jnp.int32)
    onehot = (loc2d[:, :, None] == ids[None, None, :]).astype(jnp.float32)
    return jax.lax.dot_general(
        onehot, stack.astype(jnp.float32),
        dimension_numbers=(((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)


def _onehot_ok(n_buckets: int, bucket_cap: int, tile: int) -> bool:
    """XLA:CPU materializes the one-hot operand before the batched dot
    (no fusion into the Eigen contraction), so past a size bound the
    formulation would allocate n_buckets*cap*tile floats; route those
    shapes through segment_sum instead (same results).  TPU fuses —
    the bound only bites the CPU test/bench mesh."""
    if jax.default_backend() != "cpu":
        return True
    return n_buckets * bucket_cap * tile <= (1 << 24)


def bucketed_grid_aggregate(slot: jnp.ndarray, valid: jnp.ndarray,
                            values: list[tuple[jnp.ndarray, str]],
                            total: int, bucket_cap: int,
                            kernel: str = "xla",
                            interpret: bool = False):
    """Aggregate rows onto a [total]-slot dense grid, bucket-tiled.

    Args:
      slot:   [n] int32 dense packed slot per row, in [0, total) for
              valid rows (callers clip; out-of-range accounting happens
              upstream via the dense_oob protocol).
      valid:  [n] bool — rows to aggregate; invalid rows are dropped by
              the pack.
      values: (array [n], kind) per aggregate, kind in sum|count|min|max.
              sum/count arrays must hold 0 on non-contributing rows and
              min/max arrays the reduction identity (the caller owns
              NULL masking, exactly as with the flat dense grid).
      total:  static slot-space size.
      bucket_cap: static per-bucket row slots; a hot bucket overflows
              and the host regrows + retries.
      kernel: 'xla' (batched take-free one-hot dot_general) or 'pallas'
              (ops.pallas_kernels.bucketed_groupby_sums_pallas for the
              f32/int32 sum stacks; min/max and wide dtypes stay on the
              XLA segment ops either way).  Degrades to 'xla' where
              pallas cannot compile.

    Returns (results, rows_per_slot, overflow, bucket_max_fill):
      results:       [total] array per input value, same order,
      rows_per_slot: [total] int32 — valid input rows per slot,
      overflow:      int64 — rows dropped by full buckets (host retries
                     with grown capacity; results are incomplete),
      bucket_max_fill: int64 — realized max bucket fill (feedback).
    """
    from .hashing import tile_buckets
    from .partition import pack_by_target

    tile = GROUP_TILE_SLOTS
    n_buckets = group_bucket_count(total)
    ext_pad = n_buckets * tile

    bucket, local = tile_buckets(slot, tile)
    cols = {f"v{i}": arr for i, (arr, _kind) in enumerate(values)}
    cols["local"] = local
    packed, pvalid, overflow = pack_by_target(cols, valid, bucket,
                                              n_buckets, bucket_cap)
    bucket_max_fill = pvalid.sum(axis=1).max().astype(jnp.int64)
    loc2d = packed["local"]  # garbage lanes: slot 0, values zeroed
    # flat slots for the scatter-based reductions: garbage lanes park at
    # the trash slot ext_pad so the pack's ZEROED garbage values can
    # never masquerade as a min/max contribution
    biota = jnp.arange(n_buckets, dtype=jnp.int32)[:, None]
    flat_slot = jnp.where(pvalid, biota * tile + loc2d,
                          ext_pad).reshape(-1)

    if kernel == "pallas" and not interpret:
        if jax.default_backend() == "cpu":
            # on the CPU backend a compiled pallas_call is
            # interpret-only, so the XLA formulation (identical
            # results) runs instead
            kernel = "xla"

    def _sums(colkeys: list[str], out_dtype):
        """Per-tile sums of same-dtype packed stacks [nb, cap] each."""
        stack = jnp.stack([packed[ck] for ck in colkeys], axis=2)
        if kernel == "pallas":
            from .pallas_kernels import bucketed_groupby_sums_pallas

            red = bucketed_groupby_sums_pallas(
                loc2d, stack.astype(jnp.float32), tile,
                interpret=interpret)
        elif _onehot_ok(n_buckets, bucket_cap, tile):
            red = _onehot_bucket_sums(loc2d, stack, tile)
        else:
            flat = stack.reshape(n_buckets * bucket_cap, len(colkeys))
            return jax.ops.segment_sum(
                flat, flat_slot,
                num_segments=ext_pad + 1)[:ext_pad].astype(out_dtype)
        return red.reshape(ext_pad, len(colkeys)).astype(out_dtype)

    # ROWS marks the rows_per_slot lane: pvalid IS the packed all-ones
    # int32 column (the pack zeroes garbage lanes), so it rides the
    # int32 sum stack for free instead of paying a second one-hot pass
    ROWS = "rows"
    packed[ROWS] = pvalid.astype(jnp.int32)
    results: list = [None] * len(values)
    rows_per_slot = None
    by_kind: dict[tuple, list[tuple[object, str]]] = {}
    for i, (arr, kind) in enumerate(values):
        if kind == "count":
            # 0/1 contributions: exact through the f32 matmul while a
            # bucket holds < 2^24 rows (partial sums stay ≤ bucket_cap)
            by_kind.setdefault(("matsum", jnp.int32), []) \
                .append((i, f"v{i}"))
        elif kind == "sum":
            # f32 sums accumulate in f32 either way; every integer sum
            # stays on the exact segment path — f32 accumulation loses
            # bits once VALUES (not just row counts) pass 2^24, a bound
            # no cheap static check can guarantee for data columns.
            key = (("matsum", arr.dtype) if arr.dtype == jnp.float32
                   else ("segsum", arr.dtype))
            by_kind.setdefault(key, []).append((i, f"v{i}"))
        elif kind in ("min", "max"):
            by_kind.setdefault((kind, arr.dtype), []).append((i, f"v{i}"))
        else:
            raise ValueError(f"unsupported aggregate kind {kind!r}")
    by_kind.setdefault(("matsum", jnp.int32), []).append((ROWS, ROWS))

    for (op, dt), items in by_kind.items():
        if op == "matsum" and bucket_cap >= (1 << 24):
            op = "segsum"  # counts past f32 exactness: exact scatter
        colkeys = [ck for _slot, ck in items]
        if op == "matsum":
            red = _sums(colkeys, dt)
        else:
            seg = (jax.ops.segment_min if op == "min"
                   else jax.ops.segment_max if op == "max"
                   else jax.ops.segment_sum)
            flat = jnp.stack(
                [packed[ck] for ck in colkeys],
                axis=2).reshape(n_buckets * bucket_cap, len(colkeys))
            red = seg(flat, flat_slot, num_segments=ext_pad + 1)[:ext_pad]
        for j, (slot_i, _ck) in enumerate(items):
            if slot_i is ROWS:
                rows_per_slot = red[:total, j].astype(jnp.int32)
            else:
                results[slot_i] = red[:total, j]

    return results, rows_per_slot, overflow.astype(jnp.int64), \
        bucket_max_fill
