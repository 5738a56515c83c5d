"""Equi-join kernels: dense-directory lookup, sort-and-scan lookup,
sorted binary-search join.

TPU-native replacement for the reference's hash build/probe executed per
shard on workers (co-located pushdown joins,
/root/reference/src/backend/distributed/planner/query_pushdown_planning.c;
repartition merge tasks, multi_physical_planner.c BuildMapMergeJob): no
pointer-chasing hash tables — the build side is arranged once (sort or
counting-sort) and probes resolve to a contiguous run of matches.

Probe paths, chosen at trace time:

* **Dense directory**: when the build key's value range
  [base, base+extent) is known from table statistics (manifest min/max —
  exact for committed data), a counting-sort directory `starts[extent+1]`
  maps each key value straight to its sorted run.  Probing is TWO O(1)
  gathers instead of 2·log2(M) serial gather steps: a gather costs
  6.7–8.3 ns an element on a v5e whatever the directory's size, and the
  binary search's 38 took 418 ms for 1.5 M probe rows in tpch4.q3 (my
  chip run, PR 28; ledger, PR 27).  Build rows outside the declared
  range (stale stats / uncommitted overlay rows) are counted into a
  separate `dense_oob` overflow output; the host retries with the
  directory disabled, so stale statistics cost one recompile, never
  wrong answers.

* **Sort and scan** (`sorted_unique_lookup`): a fused lookup against a
  unique single-column key over an extent of SORTED_LOOKUP_MIN_EXTENT
  slots or more gathers nothing — the chip sorts a row in about 2 ns
  and scans one in under 1.

* **Lexicographic binary search** (general path): multi-column or
  unbounded keys fall back to an exact vectorized binary search.  The
  lower and upper bounds run in ONE fused loop whose two gather chains
  are independent, letting the TPU overlap their memory traffic.  A
  multi-column key whose every range the planner knows is packed into
  one int64 first (`pack_keys`), so the build side sorts as one
  operand and the search compares one key: a stable lexsort of five
  operands costs the chip's compiler minutes at 10^5 rows.

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
from .aggregate import sort_packed


# dense-directory planning limits: the starts[] table costs O(extent)
# build work and 4·extent bytes of HBM, so it must stay proportional to
# the build side (sparse 64-bit keys fall back to binary search)
DENSE_MAX_SLOTS = 1 << 26

# From this key extent up (slots) a fused lookup sorts and scans
# (sorted_unique_lookup) instead of gathering from a directory.  The chip
# showed no knee to put it at: a gather or scatter costs 6.7–8.3 ns an
# element whether the directory holds 2^14 slots (64 KB) or 6.0 M
# (24 MB), and the sorted arm 3.6–6.2 ns a row of both sides, rising
# with their number and flat in the extent; it won every pairing
# measured — 1.17 against 2.68 ms at 2^18 slots (build 65 k, probe
# 262 k), 46.2 against 61.9 at TPC-H SF1's 6.0 M (1.5 M, 6.0 M), 9.9
# against 21.9 with a 65 k-row probe side there, 6.9 against 10.5 at
# 2^17 slots under 1.5 M probe rows — and that with stable sorts; as it
# stands it takes 29.6 ms at SF1's shape (my chip run, PR 28; `python
# bench_kernels.py lookup`, `lookup knee`, `lookup q3`; PERF.md §6).  So
# the constant is the smallest extent at which both arms were timed at
# a join's own proportions; under it, where the sides are small and
# either arm takes well under a millisecond, the one gather stays
SORTED_LOOKUP_MIN_EXTENT = 1 << 18


def sorted_lookup_eligible(extent: int) -> bool:
    """The pick between the two arms of a fused single-key lookup join,
    from the build key's extent: True sorts and scans, False gathers
    from the dense directory.  The two sides' rows are no part of it:
    at a fixed extent no shape measured turned the pick
    (SORTED_LOOKUP_MIN_EXTENT).  Asked by the planner once a join;
    EXPLAIN, the compiler, the plan fingerprint and the counter read
    its answer from the plan (`lookup_sorted`)."""
    return extent >= SORTED_LOOKUP_MIN_EXTENT


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


def pack_keys(keys: list[jnp.ndarray], ranges) -> tuple[jnp.ndarray,
                                                        jnp.ndarray]:
    """Several join keys → ONE int64 over the product of their value
    ranges, each key packed as its value less its base, first key major
    (the group-by's packing, `PlanCompiler._pack_group_keys`, without
    its NULL slot: a NULL key joins nothing, so no NULL lane is read).
    `ranges` holds (base, extent) per key.  Returns (packed, in_range):
    a row whose key lies outside its range packs to a clipped slot that
    may alias another key, so the caller must not match it."""
    packed = jnp.zeros(keys[0].shape, jnp.int64)
    inb = jnp.ones(keys[0].shape, jnp.bool_)
    for k, (base, extent) in zip(keys, ranges):
        raw = k.astype(jnp.int64) - jnp.int64(base)
        inb = inb & (raw >= 0) & (raw < extent)
        packed = packed * extent + jnp.clip(raw, 0, extent - 1)
    return packed, inb


def packed_sort_fits(pack, m: int) -> bool:
    """Whether a packed key over the ranges `pack` and the position of
    one of `m` build rows fit one int64 (`ops.aggregate.sort_packed`);
    where they do not the join keeps its lexsort."""
    top = math.prod(e for _b, e in pack)
    return (top + 1) << max(1, (m - 1).bit_length()) < 1 << 63


def build_sort_operands(n_keys: int, pack) -> int:
    """The operands of a join's build-side sort: the keys and the
    validity of a lexsort, or with packed ranges one (key, position)
    composite."""
    return n_keys + 1 if pack is None else 1


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
    per probe row: gathers are the cost of this path (10.2 ns a probe
    row with a build side a quarter of the probe side, at any extent
    from 2^18 to 6.0 M slots; my chip run, PR 28), so per-probe match
    counts come from the directory hit itself (0/1) rather than a second
    per_slot gather.  Duplicate build keys — the stale-uniqueness case —
    are detected BUILD-side: scatter-then-gather-back over the m build
    rows; overwritten rows read back a different index.  dups feed oob
    so the caller's retry-on-general-path protocol still always fires."""
    m = build_key.shape[0]
    with stage_scope("dense"):
        idx = build_key.astype(jnp.int64) - jnp.int64(base)
        inb = build_matchable & (idx >= 0) & (idx < extent)
        oob = (build_matchable & ~inb).sum().astype(jnp.int64)
        slot = jnp.where(inb, idx, extent).astype(jnp.int32)
        iota_m = jnp.arange(m, dtype=jnp.int32)
        directory = jnp.full(extent, m, jnp.int32).at[slot].set(
            iota_m, mode="drop")
        dup = (inb & (jnp.minimum(directory[jnp.minimum(slot, extent - 1)],
                                  m) != iota_m)).sum().astype(jnp.int64)
        pin, pc = _probe_slots(probe_key, base, extent)
        raw = directory[pc]
        found = pin & (raw != m)
        bidx = jnp.minimum(raw, m - 1)
        counts = found.astype(jnp.int32)
    return bidx, counts, oob + dup


def sorted_unique_lookup(build_key: jnp.ndarray,
                         build_matchable: jnp.ndarray,
                         probe_key: jnp.ndarray,
                         ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Gather-free lookup for a UNIQUE-keyed build side: sort the two
    sides' keys together, carry each build row's index forward to the
    probe rows that follow it, and put the result back in probe order.

    Returns (bidx [N], counts [N], oob_count) under dense_unique_lookup's
    contract: bidx indexes the ORIGINAL build arrays in ORIGINAL probe
    order, counts is 0/1, a non-matchable build row matches nothing, and
    duplicate matchable build keys (stale uniqueness) are counted into
    oob so the caller's retry on the general path still fires.  There is
    no directory, so no base, no extent and nothing out of range.

      1. the build side alone by (key, p), m rows: p is a row's 1-based
         position, negated when the row is not matchable, so a key's
         matchable row is the last of its run.  Neighbours in that order
         give every row the DIFFERENCE of its p to its predecessor's,
         and the matchable duplicates;
      2. one sort of the m+n keys, second operand `x`: a build row's
         difference shifted below zero (build rows precede the probe
         rows of their key), a probe row's position;
      3. two scans in sorted order: the running sum of the differences
         is the p of the last build row — whatever order the sort gave
         a run's build rows, past the run the sum has telescoped to its
         last row's p — and the running max of the build rows' keys is
         that run's key.  A probe row found its match when that p is
         positive (matchable) and that key is its own.  Nothing is
         gathered by a scan's result;
      4. back to probe order by a second sort on `x` and a static
         slice.  (One unique-index scatter in its place made the whole
         lookup 74.9 ms where this made it 46.2, at build 1.5 M and
         probe 6.0 M rows and with stable sorts; my chip run, PR 28.)"""
    m = build_key.shape[0]
    n = probe_key.shape[0]
    if m == 0 or n == 0:
        return (jnp.zeros(n, jnp.int32), jnp.zeros(n, jnp.int32),
                jnp.zeros((), jnp.int64))
    with stage_scope("sort"):
        p = jnp.arange(1, m + 1, dtype=jnp.int32)
        # (no sort here needs to be stable — ties are duplicates, which
        # go to oob — and a stable one sorts a third operand, an iota)
        bkey, p = jax.lax.sort(
            (build_key, jnp.where(build_matchable, p, -p)), num_keys=2,
            is_stable=False)
        dup = ((p[1:] > 0) & (p[:-1] > 0)
               & (bkey[1:] == bkey[:-1])).sum().astype(jnp.int64)
        step = p - jnp.concatenate([jnp.zeros(1, jnp.int32), p[:-1]])
        shift = 2 * m + 1  # |step| <= 2m
        skey, sx = jax.lax.sort(
            (jnp.concatenate([bkey, probe_key.astype(bkey.dtype)]),
             jnp.concatenate([step - shift,
                              jnp.arange(n, dtype=jnp.int32)])),
            num_keys=2, is_stable=False)
    with stage_scope("carry"):
        is_build = sx < 0
        last_p = jnp.cumsum(jnp.where(is_build, sx + shift, 0),
                            dtype=jnp.int32)
        mark = skey
        if mark.dtype.itemsize > 4:
            # 64-bit scans are emulated on the chip: scan the number of
            # the key's run instead, which is as monotone as the key
            mark = jnp.cumsum(jnp.concatenate(
                [jnp.zeros(1, jnp.bool_), skey[1:] != skey[:-1]]),
                dtype=jnp.int32)
        last_key = jax.lax.cummax(
            jnp.where(is_build, mark, jnp.iinfo(mark.dtype).min))
        hit = jnp.where(~is_build & (last_p > 0) & (last_key == mark),
                        last_p, 0)
    with stage_scope("sort"):
        hit = jax.lax.sort((sx, hit), num_keys=1, is_stable=False)[1][m:]
    return jnp.maximum(hit - 1, 0), (hit > 0).astype(jnp.int32), dup


def _bounds(build_keys, build_matchable, probe_keys,
            dense: tuple[int, int] | None, pack=None,
            ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(order, lo, hi, dense_oob) via directory or binary search.

    `pack`, (base, extent) per key of a multi-key join whose ranges the
    planner knows, searches one packed key (`pack_keys`) over a build
    side sorted as one (key, position) operand (`sort_packed`; the
    caller checked `packed_sort_fits`).  A matchable build row
    outside the ranges (stale statistics) is counted into dense_oob, so
    the host retries without packing, and a probe row outside them
    matches nothing: no pair is wrong."""
    if dense is not None and len(build_keys) == 1:
        return _dense_bounds(build_keys[0], build_matchable, probe_keys[0],
                             dense[0], dense[1])
    if pack is not None and len(build_keys) > 1:
        top = math.prod(e for _b, e in pack)
        bkey, b_in = pack_keys(build_keys, pack)
        pkey, p_in = pack_keys(probe_keys, pack)
        oob = (build_matchable & ~b_in).sum().astype(jnp.int64)
        b_ok = build_matchable & b_in
        sorted_key, order = sort_packed(bkey, b_ok, top)
        n_valid = b_ok.sum().astype(jnp.int32)
        if top < (1 << 31) - 1:
            # 64-bit compares and gathers are emulated on the chip
            sorted_key, pkey = (sorted_key.astype(jnp.int32),
                                pkey.astype(jnp.int32))
        lo, hi = _dual_search([sorted_key], n_valid, [pkey])
        return order, lo, jnp.where(p_in, hi, lo), oob
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
                      dense: tuple[int, int] | None = None, pack=None):
    """Pair emission core.

    probe_valid = rows that exist; probe_matchable = rows whose keys may
    match (valid AND no NULL key — SQL: NULL joins nothing, but a LEFT
    join still emits the row null-extended).  With probe_outer, valid
    probe rows with zero matches emit one pair with build_missing=True.

    Returns (build_idx, probe_idx, out_valid, build_missing,
    capacity_overflow, dense_oob) — the two overflow kinds stay separate
    so the host can distinguish "grow buffers" from "stats were stale,
    drop the directory".  `pack` as `_bounds` takes it.
    """
    with stage_scope("expand"):
        order, lo, hi, dense_oob = _bounds(build_keys, build_matchable,
                                           probe_keys, dense, pack)
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
        return (build_idx, probe_idx, out_valid, build_missing, overflow,
                dense_oob)


def expand_join_outer(build_keys: list[jnp.ndarray], build_valid: jnp.ndarray,
                      build_matchable: jnp.ndarray,
                      probe_keys: list[jnp.ndarray],
                      probe_valid: jnp.ndarray,
                      probe_matchable: jnp.ndarray, capacity: int,
                      probe_outer: bool, build_outer: bool,
                      replicated_build: bool = False,
                      axis_name: str | None = None,
                      dense: tuple[int, int] | None = None, pack=None):
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
                          probe_outer, dense=dense, pack=pack)
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
