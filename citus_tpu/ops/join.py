"""Equi-join kernels: dense-directory lookup + sorted binary-search join.

TPU-native replacement for the reference's hash build/probe executed per
shard on workers (co-located pushdown joins,
/root/reference/src/backend/distributed/planner/query_pushdown_planning.c;
repartition merge tasks, multi_physical_planner.c BuildMapMergeJob): no
pointer-chasing hash tables — the build side is arranged once (sort or
counting-sort) and probes resolve to a contiguous run of matches.

Two probe paths, chosen at trace time:

* **Dense directory** (the TPU fast path): when the build key's value
  range [base, base+extent) is known from table statistics (manifest
  min/max — exact for committed data), a counting-sort directory
  `starts[extent+1]` maps each key value straight to its sorted run.
  Probing is TWO O(1) gathers instead of 2·log2(M) serial gather steps —
  on a v5e this turns a 6.5 s binary-search phase into ~100 ms.  Build
  rows outside the declared range (stale stats / uncommitted overlay
  rows) are counted into a separate `dense_oob` overflow output; the host
  retries with the directory disabled, so stale statistics cost one
  recompile, never wrong answers.

* **Lexicographic binary search** (general path): multi-column or
  unbounded keys fall back to an exact vectorized binary search.  The
  lower and upper bounds run in ONE fused loop whose two gather chains
  are independent, letting the TPU overlap their memory traffic.

Pair emission is sort-free: probe start offsets scatter into the output
slot space and a `cummax` scan fills each probe's run (replacing a
log-time searchsorted over every output slot).  Static output capacity +
overflow counts remain the answer to data-dependent cardinalities
(SURVEY §7 hard part #1: capacity padding + count-then-emit).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..stats.tracing import stage_scope


# dense-directory planning limits: the starts[] table costs O(extent)
# build work and 4·extent bytes of HBM, so it must stay proportional to
# the build side (sparse 64-bit keys fall back to binary search)
DENSE_MAX_SLOTS = 1 << 26

# bucketed probe path: directory slots per bucket tile.  An int32 tile of
# 2^15 slots is 128 KB — VMEM-resident with pipelining headroom on a
# 16 MB/core budget, and small enough that a probe stream sorted by
# bucket turns the random directory gather into sequential tile traffic.
PROBE_TILE_SLOTS = 1 << 15
# below this extent the whole directory is cache-sized and the single
# random gather is already bandwidth-friendly; the bucketed path's
# pack (one int32 argsort over the probe side) would cost more than the
# locality it buys.  Threshold = the measured knee where dense_unique_
# lookup's probe throughput collapses (~16 MB of directory, PERF_NOTES
# round-5 table: random gathers over 60M entries run ~300× below
# roofline while small directories ride the caches).
PROBE_BUCKET_MIN_EXTENT = 1 << 22


def probe_bucket_count(extent: int) -> int:
    """Number of VMEM-sized directory tiles covering [0, extent)."""
    return max(1, -(-extent // PROBE_TILE_SLOTS))


def probe_bucket_eligible(extent: int, probe_rows: int) -> bool:
    """Planner cost threshold for the bucketed probe path: the directory
    must be past the cache knee AND the probe stream must be dense enough
    to amortize streaming every tile once (a sparse probe over a huge
    directory still favors the single gather — most tiles would stream
    in for a handful of probes)."""
    return extent >= PROBE_BUCKET_MIN_EXTENT and probe_rows * 4 >= extent


def dense_directory_ok(extent: int, build_size: int) -> bool:
    """Shared eligibility predicate for the dense probe directory
    (PlanCompiler passes the padded build capacity; EXPLAIN approximates
    with the planner's row estimate)."""
    return (0 < extent <= DENSE_MAX_SLOTS
            and extent <= max(8 * max(build_size, 1), 1 << 20))


def _lex_less(a: list[jnp.ndarray], b: list[jnp.ndarray]) -> jnp.ndarray:
    """a < b lexicographically; arrays broadcast elementwise."""
    out = jnp.zeros(jnp.broadcast_shapes(a[0].shape, b[0].shape), jnp.bool_)
    tie = jnp.ones_like(out)
    for x, y in zip(a, b):
        out = out | (tie & (x < y))
        tie = tie & (x == y)
    return out


def _lex_eq(a: list[jnp.ndarray], b: list[jnp.ndarray]) -> jnp.ndarray:
    out = jnp.ones(jnp.broadcast_shapes(a[0].shape, b[0].shape), jnp.bool_)
    for x, y in zip(a, b):
        out = out & (x == y)
    return out


def _lex_leq(a: list[jnp.ndarray], b: list[jnp.ndarray]) -> jnp.ndarray:
    return ~_lex_less(b, a)


def sort_build_side(build_keys: list[jnp.ndarray], build_valid: jnp.ndarray,
                    ) -> tuple[list[jnp.ndarray], jnp.ndarray, jnp.ndarray]:
    """Sort build rows by key, invalid rows last.

    Returns (sorted_keys, order, n_valid).  Invalid rows keep their key
    values but sort after all valid rows, and lookups clamp to n_valid.
    """
    invalid = (~build_valid).astype(jnp.int32)
    order = jnp.lexsort(tuple(reversed(build_keys)) + (invalid,))
    order = order.astype(jnp.int32)
    sorted_keys = [k[order] for k in build_keys]
    n_valid = build_valid.sum().astype(jnp.int32)
    return sorted_keys, order, n_valid


def _search(sorted_keys: list[jnp.ndarray], n_valid: jnp.ndarray,
            probe_keys: list[jnp.ndarray], cmp) -> jnp.ndarray:
    """Vectorized binary search: first index in [0, n_valid] where
    cmp(build_key, probe_key) is False.  cmp must be monotone (True then
    False over the sorted build).  ceil(log2(M))+1 fixed iterations."""
    m = sorted_keys[0].shape[0]
    n = probe_keys[0].shape[0]
    steps = max(1, math.ceil(math.log2(m + 1)))
    lo = jnp.zeros(n, dtype=jnp.int32)
    hi = jnp.broadcast_to(n_valid.astype(jnp.int32), (n,))

    def body(_, carry):
        lo, hi = carry
        active = lo < hi  # converged lanes must stay put (fixed trip count)
        mid = (lo + hi) // 2
        mid_c = jnp.clip(mid, 0, m - 1)
        mid_keys = [k[mid_c] for k in sorted_keys]
        take = cmp(mid_keys, probe_keys)
        lo = jnp.where(active & take, mid + 1, lo)
        hi = jnp.where(active & ~take, mid, hi)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, steps, body, (lo, hi))
    return lo


def _dual_search(sorted_keys: list[jnp.ndarray], n_valid: jnp.ndarray,
                 probe_keys: list[jnp.ndarray],
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """lower_bound and upper_bound in ONE fused loop.

    The two binary searches are data-independent; interleaving them in a
    single fori_loop lets XLA issue both mid-gathers per iteration
    concurrently (the gathers are the serial bottleneck — each step's
    addresses depend on the previous step's loads)."""
    m = sorted_keys[0].shape[0]
    n = probe_keys[0].shape[0]
    steps = max(1, math.ceil(math.log2(m + 1)))
    zero = jnp.zeros(n, dtype=jnp.int32)
    top = jnp.broadcast_to(n_valid.astype(jnp.int32), (n,))

    def body(_, carry):
        lo1, hi1, lo2, hi2 = carry
        act1 = lo1 < hi1
        act2 = lo2 < hi2
        mid1 = (lo1 + hi1) // 2
        mid2 = (lo2 + hi2) // 2
        k1 = [k[jnp.clip(mid1, 0, m - 1)] for k in sorted_keys]
        k2 = [k[jnp.clip(mid2, 0, m - 1)] for k in sorted_keys]
        take1 = _lex_less(k1, probe_keys)   # lower: build < probe
        take2 = _lex_leq(k2, probe_keys)    # upper: build <= probe
        lo1 = jnp.where(act1 & take1, mid1 + 1, lo1)
        hi1 = jnp.where(act1 & ~take1, mid1, hi1)
        lo2 = jnp.where(act2 & take2, mid2 + 1, lo2)
        hi2 = jnp.where(act2 & ~take2, mid2, hi2)
        return lo1, hi1, lo2, hi2

    lo1, _, lo2, _ = jax.lax.fori_loop(
        0, steps, body, (zero, top, zero, top))
    return lo1, lo2


def lower_bound(sorted_keys: list[jnp.ndarray], n_valid: jnp.ndarray,
                probe_keys: list[jnp.ndarray]) -> jnp.ndarray:
    """First index with key >= probe (lexicographic, exact)."""
    return _search(sorted_keys, n_valid, probe_keys, _lex_less)


def _upper_bound(sorted_keys, n_valid, probe_keys):
    """First index with key > probe — a direct search with <=, exact for
    any key dtype and any extreme values (no '+1 bump' tricks)."""
    return _search(sorted_keys, n_valid, probe_keys, _lex_leq)


def _dense_slots(build_key: jnp.ndarray, build_matchable: jnp.ndarray,
                 base: int, extent: int):
    """Shared dense-directory build prologue: (slot [m] with out-of-range
    rows parked at `extent`, per_slot counts [extent], oob_count).  Both
    dense paths (counting-sort bounds and the sort-free unique lookup)
    derive their stale-stats oob accounting from here so the retry
    contract cannot diverge between them."""
    idx = build_key.astype(jnp.int64) - jnp.int64(base)
    inb = build_matchable & (idx >= 0) & (idx < extent)
    oob = (build_matchable & ~inb).sum().astype(jnp.int64)
    slot = jnp.where(inb, idx, extent).astype(jnp.int32)
    per_slot = jax.ops.segment_sum(
        inb.astype(jnp.int32), slot, num_segments=extent + 1)[:extent]
    return slot, per_slot, oob


def _probe_slots(probe_key: jnp.ndarray, base: int, extent: int):
    """(pin [n], pc [n]): in-range mask + clipped slot per probe row."""
    pidx = probe_key.astype(jnp.int64) - jnp.int64(base)
    pin = (pidx >= 0) & (pidx < extent)
    pc = jnp.clip(pidx, 0, extent - 1).astype(jnp.int32)
    return pin, pc


def _dense_bounds(build_key: jnp.ndarray, build_matchable: jnp.ndarray,
                  probe_key: jnp.ndarray, base: int, extent: int,
                  ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                             jnp.ndarray]:
    """Counting-sort directory over the key range [base, base+extent).

    Returns (order, lo, hi, oob_count): `order` arranges matchable
    in-range build rows first, sorted by key; lo/hi bound each probe's
    run in that order.  Matchable build rows OUTSIDE the declared range
    cannot be matched — their count comes back as `oob_count` so the
    caller can surface a retry-without-directory (stale-stats guard).
    """
    slot, counts, oob = _dense_slots(build_key, build_matchable, base,
                                     extent)
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              jnp.cumsum(counts, dtype=jnp.int32)])
    order = jnp.argsort(slot, stable=True).astype(jnp.int32)

    pin, pc = _probe_slots(probe_key, base, extent)
    lo = jnp.where(pin, starts[pc], 0)
    hi = jnp.where(pin, starts[pc + 1], 0)
    return order, lo, hi, oob


def dense_unique_lookup(build_key: jnp.ndarray,
                        build_matchable: jnp.ndarray,
                        probe_key: jnp.ndarray, base: int, extent: int,
                        ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sort-free dense lookup for a UNIQUE-keyed build side (the fused
    PK-join path): one unique-index scatter builds `directory[slot] →
    build row`, one gather probes it — no argsort over the build
    capacity (the counting-sort directory in _dense_bounds pays an
    O(m log m) argsort per execution, which dominated multi-join
    queries at SF1 on real TPUs).

    Returns (bidx [N], counts [N], oob_count).  Probing costs ONE gather
    per probe row: random HBM gathers are the measured wall of this path
    (~80M probes/s on v5e — 2 gathers over a 60M-entry directory put
    TPC-H Q3's SF10 probe stage at 1 s alone), so per-probe match counts
    come from the directory hit itself (0/1) rather than a second
    per_slot gather.  Duplicate build keys — the stale-uniqueness case —
    are detected BUILD-side: scatter-then-gather-back over the m build
    rows; overwritten rows read back a different index.  dups feed oob
    so the caller's retry-on-general-path protocol still always fires."""
    m = build_key.shape[0]
    idx = build_key.astype(jnp.int64) - jnp.int64(base)
    inb = build_matchable & (idx >= 0) & (idx < extent)
    oob = (build_matchable & ~inb).sum().astype(jnp.int64)
    slot = jnp.where(inb, idx, extent).astype(jnp.int32)
    iota_m = jnp.arange(m, dtype=jnp.int32)
    directory = jnp.full(extent, m, jnp.int32).at[slot].set(
        iota_m, mode="drop")
    dup = (inb & (jnp.minimum(directory[jnp.minimum(slot, extent - 1)], m)
                  != iota_m)).sum().astype(jnp.int64)
    pin, pc = _probe_slots(probe_key, base, extent)
    raw = directory[pc]
    found = pin & (raw != m)
    bidx = jnp.minimum(raw, m - 1)
    counts = found.astype(jnp.int32)
    return bidx, counts, oob + dup


def bucketed_unique_lookup(build_key: jnp.ndarray,
                           build_matchable: jnp.ndarray,
                           probe_key: jnp.ndarray, base: int, extent: int,
                           bucket_cap: int, kernel: str = "xla",
                           interpret: bool = False,
                           ) -> tuple[jnp.ndarray, jnp.ndarray,
                                      jnp.ndarray, jnp.ndarray,
                                      jnp.ndarray]:
    """Hash-bucketed, VMEM-tiled variant of dense_unique_lookup.

    The single-gather probe is latency-bound: random HBM touches over a
    multi-hundred-MB directory run ~300× below the memory roofline
    (~80M probes/s measured on v5e at SF10 sizes — PERF_NOTES).  This
    path restores locality the radix-join way (Theseus, arXiv
    2508.05029; shared-nothing multicore joins, arXiv 1804.09324;
    reference repartition machinery, multi_physical_planner.c
    BuildMapMergeJob): partition the probe stream by directory tile
    until each tile fits fast memory, then probe tile-by-tile so the
    directory streams through VMEM exactly once.

      1. build the dense directory as usual (one scatter; duplicate
         build keys detected build-side exactly like dense_unique_lookup
         so the stale-uniqueness retry contract cannot diverge),
      2. pack probe rows by bucket = slot // PROBE_TILE_SLOTS with the
         same counting-sort gather the repartition shuffle uses
         (pack_by_target) into a [n_buckets, bucket_cap] buffer,
      3. probe bucket-by-bucket — each bucket's tile is VMEM-sized and
         its probes are contiguous (kernel='xla': a batched row-local
         take_along_axis; kernel='pallas': the tile-resident kernel in
         ops/pallas_kernels.py),
      4. scatter hits back to original probe positions (unique-index).

    Returns (bidx [N], counts [N], oob_count, bucket_overflow,
    bucket_max_fill): oob_count follows the dense_unique_lookup contract
    (out-of-range + duplicate build rows → the host retries on the
    general path); bucket_overflow counts probe rows dropped because
    their bucket exceeded bucket_cap — results are incomplete and the
    host retries with grown per-bucket capacity (the same
    count-then-emit protocol every static buffer uses).  bucket_max_fill
    is the realized per-bucket maximum (capacity-feedback input)."""
    tile = PROBE_TILE_SLOTS
    m = build_key.shape[0]
    n = probe_key.shape[0]
    n_buckets = max(1, -(-extent // tile))
    ext_pad = n_buckets * tile

    with stage_scope("probe"):
        # directory build + duplicate detection: identical accounting to
        # dense_unique_lookup (padding slots [extent, ext_pad) stay empty)
        idx = build_key.astype(jnp.int64) - jnp.int64(base)
        inb = build_matchable & (idx >= 0) & (idx < extent)
        oob = (build_matchable & ~inb).sum().astype(jnp.int64)
        slot = jnp.where(inb, idx, ext_pad).astype(jnp.int32)
        iota_m = jnp.arange(m, dtype=jnp.int32)
        directory = jnp.full(ext_pad, m, jnp.int32).at[slot].set(
            iota_m, mode="drop")
        dup = (inb & (jnp.minimum(directory[jnp.minimum(slot, ext_pad - 1)], m)
                      != iota_m)).sum().astype(jnp.int64)

    pin, pc = _probe_slots(probe_key, base, extent)
    from .hashing import tile_buckets
    from .partition import pack_by_target

    bucket, local = tile_buckets(pc, tile)

    packed, pvalid, overflow = pack_by_target(
        {"local": local, "pos": jnp.arange(n, dtype=jnp.int32)},
        pin, bucket, n_buckets, bucket_cap)
    # realized skew (max bucket fill) feeds capacity tightening; on an
    # overflowed run the retry regrows before feedback ever fires
    bucket_max_fill = pvalid.sum(axis=1).max().astype(jnp.int64)

    with stage_scope("probe"):
        dir2d = directory.reshape(n_buckets, tile)
        loc2d = jnp.where(pvalid, packed["local"], 0)
        if kernel == "pallas" and not interpret:
            if jax.default_backend() == "cpu":
                # config asked for the kernel on the CPU backend, where a
                # compiled pallas_call is interpret-only: the XLA
                # formulation gives the same results
                kernel = "xla"
        if kernel == "pallas":
            from .pallas_kernels import bucketed_probe_pallas

            raw2d = bucketed_probe_pallas(dir2d, loc2d, interpret=interpret)
        else:
            raw2d = jnp.take_along_axis(dir2d, loc2d, axis=1)

    with stage_scope("scatter_back"):
        pos = jnp.where(pvalid, packed["pos"], n).reshape(-1)
        raw = jnp.full(n, m, jnp.int32).at[pos].set(
            raw2d.reshape(-1), mode="drop")
        found = pin & (raw != m)
        bidx = jnp.minimum(raw, m - 1)
        counts = found.astype(jnp.int32)
    return bidx, counts, oob + dup, overflow.astype(jnp.int64), \
        bucket_max_fill


def _bounds(build_keys, build_matchable, probe_keys,
            dense: tuple[int, int] | None,
            ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(order, lo, hi, dense_oob) via directory or binary search."""
    if dense is not None and len(build_keys) == 1:
        return _dense_bounds(build_keys[0], build_matchable, probe_keys[0],
                             dense[0], dense[1])
    sorted_keys, order, n_valid = sort_build_side(build_keys,
                                                  build_matchable)
    lo, hi = _dual_search(sorted_keys, n_valid, probe_keys)
    return order, lo, hi, jnp.zeros((), jnp.int64)


def match_counts(build_keys: list[jnp.ndarray], build_valid: jnp.ndarray,
                 probe_keys: list[jnp.ndarray], probe_valid: jnp.ndarray,
                 ) -> jnp.ndarray:
    """Number of build matches per probe row (count phase of count-then-emit)."""
    _, lo, hi, _ = _bounds(build_keys, build_valid, probe_keys, None)
    return jnp.where(probe_valid, hi - lo, 0)


def lookup_join(build_keys: list[jnp.ndarray], build_valid: jnp.ndarray,
                probe_keys: list[jnp.ndarray], probe_valid: jnp.ndarray,
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One-match-per-probe equi-join (build side unique on key — PK side).

    Returns (build_row_idx [N] into the ORIGINAL build arrays, found [N]).
    If the build side has duplicate keys, the first (in sorted order) wins —
    callers that need all matches use expand_join.
    """
    sorted_keys, order, n_valid = sort_build_side(build_keys, build_valid)
    pos = lower_bound(sorted_keys, n_valid, probe_keys)
    m = sorted_keys[0].shape[0]
    pos_c = jnp.clip(pos, 0, m - 1)
    hit_keys = [k[pos_c] for k in sorted_keys]
    found = (probe_valid & (pos < n_valid) & _lex_eq(hit_keys, probe_keys))
    build_idx = order[pos_c]
    return build_idx, found


def expand_join(build_keys: list[jnp.ndarray], build_valid: jnp.ndarray,
                probe_keys: list[jnp.ndarray], probe_valid: jnp.ndarray,
                capacity: int, dense: tuple[int, int] | None = None,
                ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """General many-to-many equi-join with static output capacity.

    Emits (build_idx [C], probe_idx [C], out_valid [C], overflow_count):
    every (build, probe) key-match pair, padded to `capacity`.  If total
    matches exceed capacity, overflow_count > 0 and the host retries with
    a larger capacity (CapacityOverflowError protocol).  `dense` is the
    optional (base, extent) of the build key's value range; see
    _dense_bounds.  overflow also reflects dense out-of-range build rows.
    """
    build_idx, probe_idx, out_valid, _missing, overflow, dense_oob = \
        expand_join_pairs(build_keys, build_valid, probe_keys, probe_valid,
                          probe_valid, capacity, probe_outer=False,
                          dense=dense)
    return build_idx, probe_idx, out_valid, overflow + dense_oob


def expand_join_pairs(build_keys, build_matchable, probe_keys, probe_valid,
                      probe_matchable, capacity: int, probe_outer: bool,
                      dense: tuple[int, int] | None = None):
    """Pair emission core.

    probe_valid = rows that exist; probe_matchable = rows whose keys may
    match (valid AND no NULL key — SQL: NULL joins nothing, but a LEFT
    join still emits the row null-extended).  With probe_outer, valid
    probe rows with zero matches emit one pair with build_missing=True.

    Returns (build_idx, probe_idx, out_valid, build_missing,
    capacity_overflow, dense_oob) — the two overflow kinds stay separate
    so the host can distinguish "grow buffers" from "stats were stale,
    drop the directory".
    """
    order, lo, hi, dense_oob = _bounds(build_keys, build_matchable,
                                       probe_keys, dense)
    m = build_keys[0].shape[0]
    n = probe_keys[0].shape[0]
    counts = jnp.where(probe_matchable, hi - lo, 0).astype(jnp.int32)
    if probe_outer:
        emit = jnp.where(probe_valid & (counts == 0), 1, counts)
    else:
        emit = counts
    total = emit.sum(dtype=jnp.int64)
    # exclusive prefix in int64 (cross joins can exceed int32), clamped to
    # capacity for the int32 slot arithmetic — slots past the clamp are
    # invalid anyway (slot < total fails or offset goes negative)
    starts64 = jnp.cumsum(emit.astype(jnp.int64)) - emit.astype(jnp.int64)
    starts = jnp.minimum(starts64, capacity).astype(jnp.int32)

    # probe id per output slot: each emitting probe scatters its index at
    # its start slot; a running max fills the run (sort-free emission —
    # replaces a log2(N) searchsorted chain over every output slot)
    marker = jnp.full(capacity, -1, jnp.int32).at[
        jnp.where(emit > 0, starts, capacity)].max(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    probe_idx = jnp.maximum(jax.lax.cummax(marker), 0)

    slots = jnp.arange(capacity, dtype=jnp.int32)
    offset = slots - starts[probe_idx]
    out_valid = ((slots.astype(jnp.int64) < total)
                 & (offset >= 0) & (offset < emit[probe_idx]))
    sorted_pos = jnp.clip(lo[probe_idx] + offset, 0, m - 1)
    build_idx = order[sorted_pos]
    build_missing = out_valid & (counts[probe_idx] == 0)
    build_idx = jnp.where(build_missing, 0, build_idx)
    overflow = jnp.maximum(total - capacity, 0)
    return build_idx, probe_idx, out_valid, build_missing, overflow, dense_oob


def expand_join_outer(build_keys: list[jnp.ndarray], build_valid: jnp.ndarray,
                      build_matchable: jnp.ndarray,
                      probe_keys: list[jnp.ndarray],
                      probe_valid: jnp.ndarray,
                      probe_matchable: jnp.ndarray, capacity: int,
                      probe_outer: bool, build_outer: bool,
                      replicated_build: bool = False,
                      axis_name: str | None = None,
                      dense: tuple[int, int] | None = None):
    """Outer-join pair emission (LEFT/RIGHT/FULL null extension).

    Returns (build_idx [C], probe_idx [C], out_valid [C],
    build_missing [C], unmatched_build [M], overflow, dense_oob):

    * probe_outer (LEFT): valid probe rows with zero matches emit one pair
      flagged build_missing — the consumer NULLs the build columns.
    * build_outer (RIGHT/FULL): unmatched_build marks valid build rows no
      surviving pair references; the consumer appends them as a second
      segment with probe columns NULL.  With replicated_build the matched
      flags combine across devices (psum over `axis_name`) and the extra
      segment emits on device 0 only, so a broadcast build side doesn't
      duplicate its unmatched rows once per device.
    """
    build_idx, probe_idx, out_valid, build_missing, overflow, dense_oob = \
        expand_join_pairs(build_keys, build_matchable, probe_keys,
                          probe_valid, probe_matchable, capacity,
                          probe_outer, dense=dense)
    m = build_keys[0].shape[0]
    if build_outer:
        hit = out_valid & ~build_missing
        matched = jnp.zeros(m, jnp.int32).at[
            jnp.where(hit, build_idx, 0)].max(hit.astype(jnp.int32))
        if replicated_build:
            matched = jax.lax.psum(matched, axis_name) > 0
        else:
            matched = matched > 0
        unmatched_build = build_valid & ~matched
        if replicated_build:
            unmatched_build = unmatched_build & (
                jax.lax.axis_index(axis_name) == 0)
    else:
        unmatched_build = jnp.zeros(m, jnp.bool_)
    return (build_idx, probe_idx, out_valid, build_missing,
            unmatched_build, overflow, dense_oob)
