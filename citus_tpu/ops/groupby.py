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
  2. rows sort by slot (one sort that carries the value columns), so
     each bucket — one tile of the slot space: value-range
     partitioning, since an already-dense slot space needs no
     avalanche mixing — is a contiguous run of sorted rows, and the
     runs are cut into CHUNKS of a fixed size C, each chunk wholly
     inside one bucket (`_pack_chunks`): a `[NC, C]` buffer a column,
  3. each chunk reduces over its bucket's <= GROUP_TILE_SLOTS-wide
     dense tile: sums/counts through the measured-fastest one-hot
     `dot_general` formulation (batched over chunks; a Pallas variant
     is A/B'd by `bench_kernels.py groupby`), then the chunks of one
     bucket are added up (chunk ids are sorted by bucket);
     min/max and the exact integer sums through scatter (segment)
     reductions over the flat slots,
  4. the [total]-slot grid emits exactly like the dense grid today:
     group keys reconstruct from the slot id, `rows_per_slot > 0`
     marks live groups.

Static shapes throughout, and none of them the data's: a bucket with
count[b] rows takes ceil(count[b] / C) chunks, so NC = ceil(n / C) +
n_buckets chunks hold ANY distribution of the key over n input slots
(`group_pack_shape`).  The pack is n + n_buckets * C slots whether the
key is uniform or one value holds every row; there is no per-bucket
capacity, nothing overflows and nothing is retried.  (The repartition
shuffle keeps `partition.pack_by_target`: an all_to_all needs equal
buckets, a group-by does not.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..stats.tracing import stage_scope

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

# rows one chunk of the pack holds.  One size for every input: of
# 1,024, 4,096 and the uniform expectation n / n_buckets, 4,096 is the
# fastest or within 0.15 ms of it at every shape `bench_kernels.py
# groupby` times on the v5e, uniform or skewed (PERF.md §6, PR 36), and
# a chunk's count stays far under 2^24, where a float32 partial is
# exact.  At the tile's own size the [NC, A, tile] partials are what
# the pack is: n + n_buckets * tile values a column.
GROUP_CHUNK_ROWS = 4096


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


def group_pack_shape(n: int, n_buckets: int) -> tuple[int, int]:
    """(NC, C) of the chunked pack over `n` input slots.  Bucket b with
    count[b] rows takes ceil(count[b] / C) chunks and
    sum_b ceil(count[b] / C) <= n / C + n_buckets, so NC chunks suffice
    for ANY distribution of the key: n + n_buckets * C slots (n rounded
    up to whole chunks), and nothing to overflow.  The shape reads the
    input capacity and the tile count alone, never the data."""
    chunk = GROUP_CHUNK_ROWS
    return -(-n // chunk) + n_buckets, chunk


def _chunk_layout(skey: jnp.ndarray, n_buckets: int, tile: int,
                  nc: int, chunk: int):
    """Where each of `nc` chunks lies in `skey`, the slots sorted
    (invalid rows last, at the trash slot n_buckets * tile) → (bucket
    of each chunk [nc], its first sorted position [nc], its live lanes
    [nc], 0 for the chunks no bucket needs)."""
    # bucket b's run is sorted positions [starts[b], starts[b + 1])
    starts = jnp.searchsorted(
        skey, jnp.arange(n_buckets + 1, dtype=jnp.int32) * tile,
        side="left").astype(jnp.int32)
    per = (starts[1:] - starts[:-1] + chunk - 1) // chunk
    ends = jnp.cumsum(per, dtype=jnp.int32)
    # chunk j belongs to the bucket whose running chunk count passes j;
    # the chunks past the last bucket's read as its overhang and hold
    # no live lane
    j = jnp.arange(nc, dtype=jnp.int32)
    cb = jnp.minimum(jnp.searchsorted(ends, j, side="right"),
                     n_buckets - 1).astype(jnp.int32)
    base = starts[cb] + (j - (ends - per)[cb]) * chunk
    return cb, base, jnp.clip(starts[cb + 1] - base, 0, chunk)


def _pack_chunks(slot: jnp.ndarray, valid: jnp.ndarray,
                 columns: dict[str, jnp.ndarray], n_buckets: int,
                 tile: int, nc: int, chunk: int):
    """Rows → [nc, chunk] per column, every chunk wholly inside one
    bucket (one `tile`-slot range of the slot space).

    One sort by slot carries the columns along, so each bucket's rows
    are a contiguous run of sorted positions and a chunk is `chunk`
    consecutive positions of one run: it is CUT out of the sorted
    column (one slice a chunk), not gathered element by element.  The
    repartition's `pack_by_target` needs equal buckets for its
    all_to_all and sizes every bucket by the fullest; a group-by does
    not, and this pack's size follows the rows.

    Returns (packed columns, flat slot [nc, chunk], lane validity
    [nc, chunk], bucket of each chunk [nc]).  Garbage lanes (a bucket's
    last chunk past its run, and the chunks no bucket needs) hold
    zeroed values and the trash slot n_buckets * tile."""
    with stage_scope("pack"):
        n = slot.shape[0]
        trash = n_buckets * tile
        names = list(columns)
        # invalid rows take the trash slot and sort last
        key = jnp.where(valid, slot, trash).astype(jnp.int32)
        skey, *scols = jax.lax.sort(
            [key] + [columns[c] for c in names], num_keys=1,
            is_stable=False)
        cb, base, live = _chunk_layout(skey, n_buckets, tile, nc, chunk)
        lane_ok = jnp.arange(chunk, dtype=jnp.int32)[None, :] \
            < live[:, None]
        base = jnp.minimum(base, n)

        def cut(col, fill):
            # `chunk` slots of padding so that a slice starting at the
            # last row (or at n) never clamps backwards
            padded = jnp.pad(col, (0, chunk))
            cuts = jax.vmap(lambda b: jax.lax.dynamic_slice(
                padded, (b,), (chunk,)))(base)
            return jnp.where(lane_ok, cuts, jnp.asarray(fill, col.dtype))

        packed = {c: cut(col, 0) for c, col in zip(names, scols)}
        return packed, cut(skey, trash), lane_ok, cb


def _onehot_bucket_sums(loc2d: jnp.ndarray, stack: jnp.ndarray,
                        tile: int) -> jnp.ndarray:
    """Batched one-hot x values matmul: [nc, chunk] tile-local slots
    and [nc, chunk, A] values -> [nc, A, tile] sums a chunk (the tile
    last: a [slots, A] array pads its two or three columns to 128
    lanes on the chip).  Garbage lanes carry zeroed values (the pack
    zeroes them), so their slot-0 contribution is exactly zero — no
    mask operand needed.  XLA fuses the one-hot construction into the
    contraction loop on TPU (the measured formulation behind
    DENSE_ONEHOT_MAX_SLOTS)."""
    ids = jnp.arange(tile, dtype=jnp.int32)
    onehot = (loc2d[:, :, None] == ids[None, None, :]).astype(jnp.float32)
    return jax.lax.dot_general(
        stack.astype(jnp.float32), onehot,
        dimension_numbers=(((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)


def _onehot_ok(slots: int, tile: int) -> bool:
    """XLA:CPU materializes the one-hot operand before the batched dot
    (no fusion into the Eigen contraction), so past a size bound the
    formulation would allocate slots*tile floats; route those shapes
    through segment_sum instead (same results).  TPU fuses — the bound
    only bites the CPU test/bench mesh."""
    if jax.default_backend() != "cpu":
        return True
    return slots * tile <= (1 << 24)


def bucketed_grid_aggregate(slot: jnp.ndarray, valid: jnp.ndarray,
                            values: list[tuple[jnp.ndarray, str]],
                            total: int, kernel: str = "xla",
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
      kernel: 'xla' (batched take-free one-hot dot_general) or 'pallas'
              (ops.pallas_kernels.bucketed_groupby_sums_pallas for the
              f32/int32 sum stacks, a chunk where it took a bucket;
              min/max and wide dtypes stay on the XLA segment ops
              either way).  Degrades to 'xla' where pallas cannot
              compile.

    Returns (results, rows_per_slot):
      results:       [total] array per input value, same order,
      rows_per_slot: [total] int32 — valid input rows per slot.
    """
    tile = GROUP_TILE_SLOTS
    n_buckets = group_bucket_count(total)
    ext_pad = n_buckets * tile
    nc, chunk = group_pack_shape(slot.shape[0], n_buckets)

    cols = {f"v{i}": arr for i, (arr, _kind) in enumerate(values)}
    packed, flat2d, pvalid, cb = _pack_chunks(
        slot, valid, cols, n_buckets, tile, nc, chunk)
    # garbage lanes: local slot 0 with zeroed values for the one-hot
    # sums; for the scatter-based reductions they park at the trash
    # slot ext_pad, so the pack's ZEROED garbage values can never
    # masquerade as a min/max contribution
    loc2d = jnp.where(pvalid, flat2d - cb[:, None] * tile, 0)
    flat_slot = flat2d.reshape(-1)

    if kernel == "pallas" and not interpret:
        if jax.default_backend() == "cpu":
            # on the CPU backend a compiled pallas_call is
            # interpret-only, so the XLA formulation (identical
            # results) runs instead
            kernel = "xla"

    def _sums(colkeys: list[str], out_dtype):
        """Per-tile sums of same-dtype packed stacks [nc, chunk] each,
        a column a row of the result [A, ext_pad]: a partial a chunk,
        then the chunks of one bucket added up."""
        a = len(colkeys)
        stack = jnp.stack([packed[ck] for ck in colkeys], axis=2)
        if kernel == "pallas":
            from .pallas_kernels import bucketed_groupby_sums_pallas

            red = bucketed_groupby_sums_pallas(
                loc2d, stack.astype(jnp.float32), tile,
                interpret=interpret).swapaxes(1, 2)
        elif _onehot_ok(nc * chunk, tile):
            red = _onehot_bucket_sums(loc2d, stack, tile)
        else:
            flat = stack.reshape(nc * chunk, a)
            return jax.ops.segment_sum(
                flat, flat_slot,
                num_segments=ext_pad + 1)[:ext_pad].astype(out_dtype).T
        # a chunk's f32 partial counts at most `chunk` < 2^24 rows, so
        # the cast to int32 is exact whatever a bucket holds; chunk ids
        # are sorted by bucket
        red = jax.ops.segment_sum(
            red.astype(out_dtype).reshape(nc, a * tile), cb,
            num_segments=n_buckets, indices_are_sorted=True)
        return red.reshape(n_buckets, a, tile).swapaxes(0, 1) \
            .reshape(a, ext_pad)

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
            # 0/1 contributions: exact through the f32 matmul, a chunk
            # at a time
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
        colkeys = [ck for _slot, ck in items]
        if op == "matsum":
            red = _sums(colkeys, dt)
        else:
            seg = (jax.ops.segment_min if op == "min"
                   else jax.ops.segment_max if op == "max"
                   else jax.ops.segment_sum)
            flat = jnp.stack(
                [packed[ck] for ck in colkeys],
                axis=2).reshape(nc * chunk, len(colkeys))
            red = seg(flat, flat_slot,
                      num_segments=ext_pad + 1)[:ext_pad].T
        for j, (slot_i, _ck) in enumerate(items):
            if slot_i is ROWS:
                rows_per_slot = red[j, :total].astype(jnp.int32)
            else:
                results[slot_i] = red[j, :total]

    return results, rows_per_slot
