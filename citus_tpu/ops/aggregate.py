"""Sort-based grouped aggregation with static output capacity.

The TPU-native replacement for the reference's two-level aggregation
(worker partial aggregate + coordinator combine, Citus's
src/backend/distributed/planner/multi_logical_optimizer.c:1419
MasterExtendedOpNode / WorkerExtendedOpNode): instead of a dynamic hash
table, rows are sorted by group key (XLA-friendly, deterministic) and
reduced over the sorted runs.  The caller names how many group slots it
keeps (`out`, the aggregate's planned or fed-back capacity; the input's
size where it has none): groups past it are counted in `n_groups` and
dropped, and the caller retries larger.  `group_valid` marks which
output slots hold real groups.

Every operation runs at the narrowest width its result needs.  Per row,
at the input's N: the sort, the value columns and their null masks in
sorted order, the boundary compare, the segmented scans and the count's
prefix sum.  Per group, at `out`: the group starts and ends, each
group's first row, key values and reductions.

Reduction strategy (the part that matters on TPU): `jax.ops.segment_*`
lowers to scatter-add/min/max, which the TPU executes element-at-a-time —
a 9M-row segment_sum measures >1 s on a v5e.  Because the rows are
SORTED by group, every reduction is over a contiguous run instead:

* integer sum / count — prefix-sum difference: `cumsum` once, subtract
  the values at each group's boundaries (exact in int64).
* float sum / min / max — a segmented associative scan (value,
  boundary-flag) pairs that resets at group boundaries; the scan value
  at a group's last row is its reduction.  A float sum is taken in the
  value's own dtype, each run from its own start, so nothing cancels
  (a float64 prefix difference would: and its emulated cumsum costs the
  chip's compiler 120-290 s a program, PERF.md §6, PR 40).
* group starts — the first `out + 1` boundary rows' positions, by ONE
  sort of the positions (`survivor_positions`): no scatter, whose
  unique-index form and the `cumsum` that fed it cost the v5e 4.7-6.6
  ns a row against the sort's 0.4-1.5 (`bench_kernels.py compact`).
* the sorted key and validity — the sort's own output where the caller
  sorted a packed key (`sort_packed`), and valid rows first under every
  order: neither is gathered.

This same primitive serves: GROUP BY (partial + final), DISTINCT, and the
merge step after an all_to_all repartition.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..stats.tracing import stage_scope

SUPPORTED_AGGS = ("sum", "count", "min", "max")


def _sort_order(keys: list[jnp.ndarray], valid: jnp.ndarray) -> jnp.ndarray:
    """Stable order: valid rows first, grouped by key columns."""
    invalid = (~valid).astype(jnp.int32)
    # lexsort: LAST key is primary
    return jnp.lexsort(tuple(reversed(keys)) + (invalid,)).astype(jnp.int32)


def sort_packed(key: jnp.ndarray, valid: jnp.ndarray, top: int):
    """(sorted_key, order) of rows by a packed int64 key below `top`,
    invalid rows (keyed `top`) last and ties in row order — what a
    stable argsort gives — from ONE single-operand sort of (key, row
    position) packed into an int64: a stable or multi-operand sort costs
    the chip's compiler minutes at 10^5 rows and more (PERF.md §6).
    None where the two do not fit 63 bits."""
    n = valid.shape[0]
    bits = max(1, (n - 1).bit_length())
    if (top + 1) << bits >= 1 << 63:
        return None
    key = jnp.where(valid, key, jnp.int64(top))
    comp = jax.lax.sort((key << bits) | jnp.arange(n, dtype=jnp.int64),
                        is_stable=False)
    return (comp >> bits,
            (comp & jnp.int64((1 << bits) - 1)).astype(jnp.int32))


def survivor_positions(valid, k: int):
    """→ (the positions of the first k True rows of `valid`, in row
    order; the count of True rows), by ONE sort: survivors keep their
    position as key, the rest take the sentinel n, so the first k of the
    sorted keys are the survivors (slots past the count hold n).  Keys
    are distinct, so the sort need not be stable.

    A sort, not a scatter of each survivor's rank: on the v5e XLA's
    scatter and the `cumsum` that feeds it cost 4.7–6.6 ns a row of the
    uncompacted size (36.7 ms for 6.0 M rows) where the one-operand
    sort costs 0.4–1.5 (5.0 ms), at every size from 30 k rows up
    (`python bench_kernels.py compact`; PERF.md §6)."""
    n = valid.shape[0]
    key = jnp.where(valid, jnp.arange(n, dtype=jnp.int32), n)
    return (jax.lax.sort(key, is_stable=False)[:k],
            valid.sum(dtype=jnp.int32))


@dataclass(frozen=True)
class AggSpec:
    """One aggregate over one input array."""

    kind: str            # sum | count | min | max
    # count counts rows where contributing value is non-null (input_valid)


def _segmented_scan(x: jnp.ndarray, boundary: jnp.ndarray, op):
    """Inclusive segmented scan: resets at every boundary row.

    Hillis-Steele step-doubling inside ONE fori_loop body (log2(n)
    iterations of same-shape where/roll ops).  `lax.associative_scan`
    computes the same thing but UNROLLS its odd/even recursion into
    ~2·log2(n) concat/slice layers, which the TPU compiler cannot digest
    at engine scale — a 6M-row segmented max hangs XLA:TPU compilation
    for >5 minutes, while this loop compiles in seconds and runs at the
    same O(n log n) work."""
    n = x.shape[0]
    if n <= 1:
        return x
    idx = jnp.arange(n, dtype=jnp.int32)

    def body(i, carry):
        v, f = carry
        step = jnp.int32(1) << i
        pv = jnp.roll(v, step)
        pf = jnp.roll(f, step)
        has_prev = idx >= step
        nv = jnp.where(has_prev & ~f, op(v, pv), v)
        nf = jnp.where(has_prev, f | pf, f)
        return nv, nf

    n_steps = (n - 1).bit_length()
    v, _f = jax.lax.fori_loop(0, n_steps, body, (x, boundary))
    return v


def segment_aggregate(keys: list[jnp.ndarray],
                      values: list[tuple[jnp.ndarray, str, jnp.ndarray | None]],
                      valid: jnp.ndarray,
                      out_keys: list[jnp.ndarray] | None = None,
                      order: jnp.ndarray | None = None,
                      sorted_key: jnp.ndarray | None = None,
                      out: int | None = None,
                      gathered: list[int] | None = None,
                      ) -> tuple[list[jnp.ndarray], list[jnp.ndarray], jnp.ndarray, jnp.ndarray]:
    """Group rows by `keys` and reduce.

    Args:
      keys:   key columns, each [N].  With a packed composite key this
              is ONE int64 array (single-operand argsort — far faster
              on TPU than a multi-operand lexsort).
      values: (array [N], kind, value_valid [N] | None) per aggregate;
              value_valid masks per-column NULLs (count(col), sum skips null).
      valid:  row validity [N].
      out_keys: when set, group-key VALUES are extracted from these
              arrays (the original columns) while ordering/boundary
              detection runs on `keys` (the packed form — injective
              over in-range rows, so the groupings agree).
      order:  the rows' sorted order, when the caller sorted them (valid
              rows first, grouped by `keys`); the sort is skipped.
      sorted_key: with `order`, the one packed key in that order, as
              the caller's sort returned it (`sort_packed`): not gathered.
      out:    the group slots to produce, at most N (N where None):
              groups past them are counted in n_groups and dropped.
      gathered: when given, the index size of every gather this call
              makes is appended to it, each distinct gather once.

    Returns (group_keys, agg_results, group_valid, n_groups):
      group_keys:  each [out], key value of each group slot,
      agg_results: each [out],
      group_valid: [out] bool, slots < n_groups,
      n_groups:    scalar int32, every group of the input.
    """
    n = valid.shape[0]
    out = n if out is None else min(out, n)
    taken = {}

    def take(arr, idx):
        # one gather a distinct (array, index): a value's null mask
        # serves its aggregate and the companion count.  The entry
        # holds both operands, so no id is reused while it stands
        key = (id(arr), id(idx))
        if key not in taken:
            if gathered is not None:
                gathered.append(int(idx.shape[0]))
            taken[key] = (arr, idx, arr[idx])
        return taken[key][2]

    with stage_scope("sort"):
        if order is None and out_keys is not None:
            # packed mode: the single int64 key already encodes invalid rows
            # as the int64-max sentinel, so this is a TRUE single-operand
            # argsort (adding the validity operand back would re-create the
            # two-operand lexsort the packing exists to avoid)
            order = jnp.argsort(keys[0], stable=True).astype(jnp.int32)
        elif order is None:
            order = _sort_order(keys, valid)
        keys_s = ([sorted_key] if sorted_key is not None
                  else [take(k, order) for k in keys])
        # every order above puts the valid rows first
        valid_s = jnp.arange(n, dtype=jnp.int32) < valid.sum(
            dtype=jnp.int32)

    with stage_scope("reduce"):
        # boundary: first row of each (valid) group
        def _shift_ne(a):
            return jnp.concatenate([jnp.ones((1,), jnp.bool_),
                                    a[1:] != a[:-1]])

        diff = jnp.zeros(n, dtype=jnp.bool_)
        for k in keys_s:
            diff = diff | _shift_ne(k)
        boundary = diff & valid_s

        # group g's run is [starts[g], ends[g]) in sorted space: the
        # first out + 1 boundaries' positions; slots past the group
        # count hold n, so the last real group runs to n (trailing
        # invalid rows carry identity contributions)
        pos, n_groups = survivor_positions(boundary, out + 1)
        if pos.shape[0] == out:          # out == n: the last end is n
            pos = jnp.concatenate([pos, jnp.full((1,), n, jnp.int32)])
        starts, ends = pos[:out], pos[1:]
        last = jnp.clip(ends - 1, 0, n - 1)

        def run_sum(x, acc_dtype):
            # each run's sum: a prefix-sum difference at its two ends
            prefix = jnp.concatenate([jnp.zeros(1, acc_dtype),
                                      jnp.cumsum(x.astype(acc_dtype))])
            return take(prefix, ends) - take(prefix, starts)

        first_c = jnp.minimum(starts, n - 1)
        if out_keys is None:
            group_keys = [take(k, first_c) for k in keys_s]
        else:
            first_idx = take(order, first_c)
            group_keys = [take(k, first_idx) for k in out_keys]

        results = []
        for arr, kind, value_valid in values:
            contrib_valid = valid_s if value_valid is None else (
                valid_s & take(value_valid, order))
            arr_s = None if kind == "count" else take(arr, order)
            if kind == "count":
                res = run_sum(contrib_valid.astype(jnp.int32),
                              jnp.int32).astype(jnp.int64)
            elif kind == "sum":
                z = jnp.zeros((), dtype=arr_s.dtype)
                x = jnp.where(contrib_valid, arr_s, z)
                if jnp.issubdtype(arr_s.dtype, jnp.floating):
                    res = take(_segmented_scan(x, boundary, jnp.add), last)
                else:
                    res = run_sum(x, jnp.int64)
            elif kind in ("min", "max"):
                ident = _identity_for(arr_s.dtype, kind)
                x = jnp.where(contrib_valid, arr_s, ident)
                op = jnp.minimum if kind == "min" else jnp.maximum
                res = take(_segmented_scan(x, boundary, op), last)
            else:
                raise ValueError(f"unsupported aggregate kind {kind!r}")
            results.append(res)

        group_valid = jnp.arange(out) < n_groups
        group_keys = [jnp.where(group_valid, k,
                                jnp.zeros((), dtype=k.dtype))
                      for k in group_keys]
        results = [jnp.where(group_valid, r, jnp.zeros((), dtype=r.dtype))
                   for r in results]
    return group_keys, results, group_valid, n_groups


def _identity_for(dtype, kind: str):
    if jnp.issubdtype(dtype, jnp.floating):
        inf = jnp.asarray(jnp.inf, dtype=dtype)
        return inf if kind == "min" else -inf
    info = jnp.iinfo(dtype)
    return jnp.asarray(info.max if kind == "min" else info.min, dtype=dtype)


def distinct(keys: list[jnp.ndarray], valid: jnp.ndarray):
    """DISTINCT = grouping with no aggregates."""
    gk, _, gv, n = segment_aggregate(keys, [], valid)
    return gk, gv, n
