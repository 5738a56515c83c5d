"""Sort-based grouped aggregation with static output capacity.

The TPU-native replacement for the reference's two-level aggregation
(worker partial aggregate + coordinator combine,
/root/reference/src/backend/distributed/planner/multi_logical_optimizer.c:1419
MasterExtendedOpNode / WorkerExtendedOpNode): instead of a dynamic hash
table, rows are sorted by group key (XLA-friendly, deterministic) and
reduced over the sorted runs.  Output capacity == input capacity, so
there is NO overflow case: in the worst degenerate case every row is its
own group.  `group_valid` marks which output slots hold real groups.

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
* group keys / first positions — one scatter-SET with provably unique
  indices (each group has exactly one boundary row), which the TPU
  handles vectorized, unlike combining scatters.

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


@dataclass(frozen=True)
class AggSpec:
    """One aggregate over one input array."""

    kind: str            # sum | count | min | max
    # count counts rows where contributing value is non-null (input_valid)


def _run_sum(x: jnp.ndarray, starts: jnp.ndarray, ends: jnp.ndarray,
             acc_dtype) -> jnp.ndarray:
    """Sum of each [starts[g], ends[g]) run via prefix-sum difference."""
    prefix = jnp.concatenate([jnp.zeros(1, acc_dtype),
                              jnp.cumsum(x.astype(acc_dtype))])
    return prefix[ends] - prefix[starts]


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

    Returns (group_keys, agg_results, group_valid, n_groups):
      group_keys:  each [N], key value of each group slot,
      agg_results: each [N],
      group_valid: [N] bool, slots < n_groups,
      n_groups:    scalar int32.
    """
    n = valid.shape[0]
    with stage_scope("sort"):
        if order is None and out_keys is not None:
            # packed mode: the single int64 key already encodes invalid rows
            # as the int64-max sentinel, so this is a TRUE single-operand
            # argsort (adding the validity operand back would re-create the
            # two-operand lexsort the packing exists to avoid)
            order = jnp.argsort(keys[0], stable=True).astype(jnp.int32)
        elif order is None:
            order = _sort_order(keys, valid)
        keys_s = [k[order] for k in keys]
        valid_s = valid[order]

    with stage_scope("reduce"):
        # boundary: first row of each (valid) group
        def _shift_ne(a):
            return jnp.concatenate([jnp.ones((1,), jnp.bool_),
                                    a[1:] != a[:-1]])

        diff = jnp.zeros(n, dtype=jnp.bool_)
        for k in keys_s:
            diff = diff | _shift_ne(k)
        boundary = diff & valid_s
        seg_id = jnp.cumsum(boundary.astype(jnp.int32)) - 1
        n_groups = boundary.sum().astype(jnp.int32)
        # invalid rows (sorted last) land in the last group's run with
        # identity contributions; the clip only guards the all-invalid case
        seg_id = jnp.clip(seg_id, 0, None)

        # group g's run is [starts[g], ends[g]) in sorted space.  One
        # boundary per group ⇒ the scatter indices are unique ⇒ scatter-set
        # (no combining — fast on TPU, unlike scatter-add/min)
        gpos = jnp.full(n + 1, n, jnp.int32).at[
            jnp.where(boundary, seg_id, n + 1)].set(
            jnp.arange(n, dtype=jnp.int32), mode="drop")
        starts = gpos[:n]
        ends = gpos[1:]  # last real group runs to n (trailing invalid rows
        #                  carry identity contributions, as before)

        group_keys = []
        first_c = jnp.minimum(starts, n - 1)
        if out_keys is None:
            group_keys = [k[first_c] for k in keys_s]
        else:
            first_idx = order[first_c]
            group_keys = [k[first_idx] for k in out_keys]

        results = []
        for arr, kind, value_valid in values:
            arr_s = arr[order]
            contrib_valid = valid_s if value_valid is None else (
                valid_s & value_valid[order])
            if kind == "count":
                res = _run_sum(contrib_valid.astype(jnp.int32), starts, ends,
                               jnp.int32).astype(jnp.int64)
            elif kind == "sum":
                z = jnp.zeros((), dtype=arr_s.dtype)
                x = jnp.where(contrib_valid, arr_s, z)
                if jnp.issubdtype(arr_s.dtype, jnp.floating):
                    sv = _segmented_scan(x, boundary, jnp.add)
                    res = sv[jnp.clip(ends - 1, 0, n - 1)]
                else:
                    res = _run_sum(x, starts, ends, jnp.int64)
            elif kind in ("min", "max"):
                ident = _identity_for(arr_s.dtype, kind)
                x = jnp.where(contrib_valid, arr_s, ident)
                op = jnp.minimum if kind == "min" else jnp.maximum
                sv = _segmented_scan(x, boundary, op)
                res = sv[jnp.clip(ends - 1, 0, n - 1)]
            else:
                raise ValueError(f"unsupported aggregate kind {kind!r}")
            results.append(res)

        group_valid = jnp.arange(n) < n_groups
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
