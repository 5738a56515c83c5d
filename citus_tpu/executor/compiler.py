"""Compile a QueryPlan into ONE shard_map program + host glue.

This is the structural replacement for the reference's adaptive executor +
repartition-join machinery (executor/adaptive_executor.c:962,
repartition_join_execution.c:59, intermediate_results.c): where Citus runs
a Job DAG of SQL tasks over libpq connections with intermediate-result
files, the whole distributed query here traces into a single XLA program
executed over the mesh:

    map task  (worker_partition_query_result)  → pack_by_target
    fetch task (fetch_intermediate_results)    → jax.lax.all_to_all
    merge/join task                            → expand_join per device
    worker partial agg / coordinator combine   → segment_aggregate + psum /
                                                 all_to_all final aggregate

Static capacities replace dynamic result sizes; each stage reports an
overflow count, and `execute_with_retry` doubles capacities and recompiles
when any stage overflowed (count-then-emit at host granularity,
SURVEY §7 hard part #1).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
try:  # jax >= 0.5 exports shard_map at top level
    from jax import shard_map as _shard_map
except ImportError:  # jax 0.4.x: experimental namespace
    from jax.experimental.shard_map import shard_map as _shard_map


def shard_map(body, mesh, in_specs, out_specs, check_vma=False):
    """Version-compat shim: the replication-check kwarg was renamed
    check_rep → check_vma across jax releases."""
    try:
        return _shard_map(body, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=check_vma)
    except TypeError:
        return _shard_map(body, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_rep=check_vma)

from ..catalog.distribution import HASH_TOKEN_COUNT, INT32_MIN
from ..errors import ExecutionError, PlanningError
from ..ops import pack_by_target, segment_aggregate
from ..ops.aggregate import sort_packed, survivor_positions
from ..ops.join import expand_join_outer, expand_join_pairs
from ..ops.hashing import hash_token_jax
from ..planner.plan import (
    AggregateNode,
    JoinNode,
    PlanNode,
    ProjectNode,
    QueryPlan,
    ScanNode,
    WindowNode,
)
from ..distributed.mesh import SHARD_AXIS
from ..stats.tracing import STAGE_NAMES, stage_scope
from .batch import Block, deferred_tally
from .exprs import (
    ColumnSource,
    evaluate,
    predicate_mask,
    set_device_float64,
    set_device_params,
)

NULL_PREFIX = "__null__"


def _round_cap(n: int) -> int:
    return max(128, int(math.ceil(n / 128.0)) * 128)


def _compaction(valid, k: int):
    """A compaction's row index: (the positions of the first k True rows
    of `valid`, padding slots reading row 0; their row mask; the True
    rows past k, which count as capacity overflow)."""
    por, n_valid = survivor_positions(valid, k)
    out_valid = jnp.arange(k, dtype=jnp.int32) < n_valid
    # padding slots read row 0, never the sentinel
    por = jnp.where(out_valid, por, 0)
    return por, out_valid, jnp.maximum(n_valid - k, 0).astype(jnp.int64)


def _scan_ids(plan: QueryPlan) -> list[int]:
    from .feed import walk_plan

    return [id(n) for n in walk_plan(plan.root) if isinstance(n, ScanNode)]


def collect_device_params(plan: QueryPlan) -> list:
    """BParam nodes reachable by the traced program, sorted by index.

    Walks every expression the device program evaluates (scan filters,
    projections, join keys/residuals, window specs, aggregates, and the
    device-topk ORDER BY keys).  Host-only expressions (host_select,
    HAVING) evaluate from the bound values and need no program input."""
    from ..planner import expr as ir

    from .feed import walk_plan

    found: dict[int, object] = {}

    def visit(e):
        if e is None:
            return
        for n in ir.walk(e):
            if isinstance(n, ir.BParam):
                found[n.idx] = n

    for node in walk_plan(plan.root):
        if isinstance(node, ScanNode):
            visit(node.filter)
        elif isinstance(node, ProjectNode):
            for e, _cid in node.exprs:
                visit(e)
        elif isinstance(node, JoinNode):
            for e in list(node.left_keys) + list(node.right_keys):
                visit(e)
            visit(node.residual)
            visit(node.left_match_filter)
            visit(node.right_match_filter)
        elif isinstance(node, WindowNode):
            for w, _cid in node.functions:
                visit(w)
            for p in node.partition_by:
                visit(p)
            visit(node.having)
        elif isinstance(node, AggregateNode):
            for g, _cid in node.group_keys:
                visit(g)
            for a, _cid in node.aggs:
                visit(a)
    if plan.device_topk is not None:
        for e, _d, _nf in plan.host_order_by:
            visit(e)
    return [found[i] for i in sorted(found)]


def param_feed_arrays(plan: QueryPlan, compute_dtype) -> list:
    """One [1] host array per device param, in collect order (appended
    after the scan feeds; replicated across the mesh)."""
    out = []
    for p in collect_device_params(plan):
        dt = np.dtype(p.dtype.numpy_dtype)
        if dt == np.float64 and compute_dtype is not None:
            dt = np.dtype(compute_dtype)
        out.append(np.asarray([p.value], dtype=dt))
    return out


def flatten_feed_arrays(plan: QueryPlan, feeds, compute_dtype=None) -> list:
    """Feed arrays in the exact order PlanCompiler.build consumes them —
    lets a plan-cache hit skip rebuilding the compiler entirely."""
    out = []
    for node_id in _scan_ids(plan):
        feed = feeds[node_id]
        for cid in sorted(feed.arrays):
            out.append(feed.arrays[cid])
        for cid in sorted(feed.nulls):
            out.append(feed.nulls[cid])
        out.append(feed.valid)
    out.extend(param_feed_arrays(plan, compute_dtype))
    return out


def _to_bits64(a):
    """Lossless device-side widening to int64 for the packed transfer.

    64-bit bitcasts are not implemented by the TPU X64 rewriter, so f64
    splits into two 32-bit bitcast words recombined arithmetically."""
    if a.dtype == jnp.float64:
        parts = jax.lax.bitcast_convert_type(a, jnp.uint32)  # [..., 2]
        lo = parts[..., 0].astype(jnp.uint64)
        hi = parts[..., 1].astype(jnp.uint64)
        return ((hi << jnp.uint64(32)) | lo).astype(jnp.int64)
    if a.dtype == jnp.float32:
        # sign-extended int32 bits; host truncation recovers them exactly
        return jax.lax.bitcast_convert_type(a, jnp.int32).astype(jnp.int64)
    return a.astype(jnp.int64)


def _from_bits64(arr: np.ndarray, dtype: np.dtype) -> np.ndarray:
    if dtype == np.float64:
        return arr.view(np.float64)
    if dtype == np.float32:
        return arr.astype(np.int32).view(np.float32)
    if dtype == np.bool_:
        return arr != 0
    return arr.astype(dtype)


def unpack_outputs(packed: np.ndarray, out_meta):
    """Packed [n_out, n_dev, cap] int64 → (cols, nulls, valid) numpy."""
    cols: dict[str, np.ndarray] = {}
    nulls: dict[str, np.ndarray] = {}
    valid = None
    for i, (kind, cid, dt) in enumerate(out_meta):
        arr = _from_bits64(packed[i], dt)
        if kind == "col":
            cols[cid] = arr
        elif kind == "null":
            nulls[cid] = arr
        else:
            valid = arr
    return cols, nulls, valid


# PlanCompiler.tallies of a program that recorded nothing: one count a
# counter that Executor.count_picks bumps from them
NO_TALLIES = (0,) * 13


@dataclass
class FeedSpec:
    """Host-side data feed for one scan: arrays indexed like the plan."""

    node: ScanNode
    sharded: bool               # False ⇒ replicated (reference table)
    arrays: dict[str, np.ndarray]       # cid → [n_dev, cap] or [cap]
    nulls: dict[str, np.ndarray]
    valid: np.ndarray                   # [n_dev, cap] or [cap]
    capacity: int
    # rows each device owns (pre-padding; None for replicated feeds) —
    # the EXPLAIN ANALYZE Mesh: line's per-device rows-in source
    dev_rows: list[int] | None = None


@dataclass
class Capacities:
    """Per-node static buffer sizes (trace-time constants)."""

    repartition: dict[int, int]
    join_out: dict[int, int]
    # aggregate output slots (present only when the planner estimated the
    # group count); segment_aggregate outputs slice down to this, shrinking
    # shuffle buffers AND device→host result transfer
    agg_out: dict[int, int] = None
    # True after a dense_oob retry: statistics-planned dense structures
    # (join key directories, dense aggregation grids) proved stale at
    # runtime; recompile on the general sort/search paths
    dense_off: bool = False
    # post-filter compaction slots per selective scan: surviving rows
    # pack into this many slots so downstream joins/aggregates size by
    # the filtered estimate, not the full table
    scan_out: dict[int, int] = None
    # per-(source, target) bucket slots for the INSERT..SELECT output
    # shuffle (QueryPlan.output_repart); None when the plan has none
    output_repart: int | None = None
    # probe slots of a fused inner lookup whose probe rows inside the
    # build keys' range compact to this many before the probe (set by
    # capacity feedback alone, where the range keeps few enough rows)
    join_range: dict[int, int] = None

    def __post_init__(self):
        if self.agg_out is None:
            self.agg_out = {}
        if self.scan_out is None:
            self.scan_out = {}
        if self.join_range is None:
            self.join_range = {}

    def grown(self, overflow: int) -> "Capacities":
        """Retry sizing: at least double, and at least enough for the
        observed overflow (expand_join reports exact total-minus-capacity,
        so one retry usually suffices even for 100× join fan-out)."""

        def g(v: int) -> int:
            return _round_cap(max(v * 2, v + int(overflow)))

        return Capacities({k: g(v) for k, v in self.repartition.items()},
                          {k: g(v) for k, v in self.join_out.items()},
                          {k: g(v) for k, v in self.agg_out.items()},
                          self.dense_off,
                          {k: g(v) for k, v in self.scan_out.items()},
                          g(self.output_repart)
                          if self.output_repart else None,
                          {k: g(v) for k, v in self.join_range.items()})


class PlanCompiler:
    """One instance per (plan, feeds, capacities) — produces a jitted fn."""

    def __init__(self, plan: QueryPlan, mesh: Mesh,
                 feeds: dict[int, FeedSpec], caps: Capacities,
                 compute_dtype=np.float32):
        self.plan = plan
        self.mesh = mesh
        self.feeds = feeds
        self.caps = caps
        self.n_dev = plan.n_devices
        self.compute_dtype = compute_dtype

    # ------------------------------------------------------------------
    def build(self):
        """Returns (jitted_fn, ordered_feed_arrays, out_meta).

        Feeds flatten in deterministic plan-walk order (NOT id() order) so
        a cached executable can be re-fed by flatten_feed_arrays for a
        structurally identical plan compiled in another execution.

        The jitted fn returns (packed, overflow): every output column /
        null mask / validity bitcast to int64 and stacked into ONE
        [n_out, n_dev, cap] array, so fetching results costs two
        device→host transfers total instead of one per column — on
        remote-attached TPUs each transfer pays a full round trip.
        out_meta describes how to unpack (see unpack_outputs)."""
        from .cache import plan_order

        # adaptive-capacity feedback (the static-shape answer to the
        # reference's adaptive executor streaming ACTUAL result sizes,
        # adaptive_executor.c:962): every capacity-consuming stage
        # records its true row count into the overflow transfer, and the
        # host tightens over-estimated buffers + recompiles once, so
        # warm executions run at near-actual sizes even when the
        # planner's estimate was 10× off (e.g. Q3's correlated
        # date-range join selectivity, statically unestimable)
        self._walk_order = plan_order(self.plan)
        self._stage_actual = {}
        self._stage_width = {}
        self.stage_keys = []

        feed_arrays = []
        in_specs = []
        feed_index = {}
        for node_id in _scan_ids(self.plan):
            feed = self.feeds[node_id]
            names = []
            for cid in sorted(feed.arrays):
                feed_arrays.append(feed.arrays[cid])
                in_specs.append(P(SHARD_AXIS) if feed.sharded else P())
                names.append(("col", cid))
            for cid in sorted(feed.nulls):
                feed_arrays.append(feed.nulls[cid])
                in_specs.append(P(SHARD_AXIS) if feed.sharded else P())
                names.append(("null", cid))
            feed_arrays.append(feed.valid)
            in_specs.append(P(SHARD_AXIS) if feed.sharded else P())
            names.append(("valid", ""))
            feed_index[node_id] = names
        self._feed_index = feed_index
        self._feed_sharded = {nid: self.feeds[nid].sharded
                              for nid in feed_index}
        # prepared-statement params ride as replicated [1] inputs AFTER
        # the feeds: the executable is generic over their values (see
        # planner/expr.py BParam)
        self._param_idx = [p.idx for p in collect_device_params(self.plan)]
        n_params = len(self._param_idx)
        feed_arrays.extend(param_feed_arrays(self.plan, self.compute_dtype))
        in_specs.extend([P()] * n_params)

        out_cids = sorted(self.plan.root.out_columns)
        out_specs = ({c: P(SHARD_AXIS) for c in out_cids},
                     {c: P(SHARD_AXIS) for c in out_cids},
                     P(SHARD_AXIS), P(SHARD_AXIS))

        def body(*flat_feeds):
            # trace-time device float policy: SQL float64 evaluates in the
            # session compute dtype on device (thread-local — tracing runs
            # on the calling thread)
            set_device_float64(self.compute_dtype)
            with deferred_tally() as tally:
                packed = traced(flat_feeds)
            # assigned, not accumulated, like _shuffle_bytes: published
            # as PlanCompiler.tallies after build
            self._tallies = (tally.columns, tally.gathers,
                             self._lookup_probe_slots,
                             self._agg_bucket_slots, self._window_rows,
                             tally.slots * self.n_dev, tally.topdown,
                             tally.recomposed, self._window_sorts,
                             self._window_presorted,
                             self._join_sort_operands,
                             self._agg_sort_gather_slots,
                             self._lookup_range_compactions)
            return packed

        def traced(flat_feeds):
            if n_params:
                param_args = flat_feeds[-n_params:]
                flat_feeds = flat_feeds[:-n_params]
                set_device_params({idx: arr[0] for idx, arr in
                                   zip(self._param_idx, param_args)})
            try:
                with stage_scope("feed_unpack"):
                    blocks = self._unpack_feeds(flat_feeds)
                self._overflow = jnp.zeros((), dtype=jnp.int64)
                self._dense_oob = jnp.zeros((), dtype=jnp.int64)
                self._stage_actual = {}
                # (fullest bucket, rows sent) of each recorded exchange,
                # in trace order: what a skewed key does to the one
                # static capacity every bucket of a shuffle shares
                self._exchange_rows = []
                # static all_to_all volume this program moves across
                # the mesh — assigned (not accumulated across traces:
                # eval_shape and the jit both trace this body) and
                # published as PlanCompiler.shuffle_bytes after build
                self._shuffle_bytes = 0
                # probe slots of the fused lookups, over the mesh: the
                # same rule
                self._lookup_probe_slots = 0
                # packed slots of the bucketed group-bys, over the
                # mesh: the same rule
                self._agg_bucket_slots = 0
                # slots the window operators run over, over the mesh:
                # the same rule
                self._window_rows = 0
                # the windows' lexsorts and the ORDER BY specs read in
                # the groups' own order, and the operands of the
                # multi-key joins' build-side sorts: the same rule
                self._window_sorts = 0
                self._window_presorted = 0
                self._join_sort_operands = 0
                # the index slots of the sort-path group-bys' gathers,
                # over the mesh: the same rule
                self._agg_sort_gather_slots = 0
                # the fused lookups whose probe side compacts to the
                # build keys' range: the same rule
                self._lookup_range_compactions = 0
                out = self._exec(self.plan.root, blocks)
                if self.plan.output_repart is not None:
                    # INSERT..SELECT device routing: shuffle the final
                    # block to the TARGET table's sharding so the host
                    # writes per-device slices without re-hashing
                    shard_count, placement, bounds, key_expr = \
                        self.plan.output_repart
                    out = self._repartition(
                        out, [key_expr], shard_count, placement,
                        self.caps.output_repart,
                        keep_null_rows=True,  # host raises on NULL dist
                        bounds=bounds or None)
                if self.plan.root.dist.kind == "replicated":
                    # every device holds identical rows; emit from
                    # device 0 only
                    out = out.with_filter(
                        jnp.broadcast_to(
                            jax.lax.axis_index(SHARD_AXIS) == 0,
                            out.valid.shape))
                topk = self.plan.device_topk
                if topk is not None and out.valid.shape[0] > topk:
                    with stage_scope("topk"):
                        out = self._device_topk(out, topk)
            finally:
                # traced scalars must not leak into host-side evaluation
                # on this thread after the trace completes
                set_device_params(None)
            # overflow block per device: [capacity_overflow, dense_oob,
            # *stage_actuals, *exchange_rows] — the host grows buffers
            # for the first, drops stale dense structures for the
            # second, tightens over-sized buffers from the third
            # (feedback) and counts the last (repartition_rows_total,
            # repartition_hot_bucket_rows_total)
            skeys = sorted(self._stage_actual,
                           key=lambda k: (self._walk_order.get(
                               k[0], 1 << 30), k[1]))
            self.stage_keys = [
                (self._walk_order.get(nid, -1), kind,
                 self._stage_width[(nid, kind)]) for nid, kind in skeys]
            with stage_scope("output_pack"):
                cols = {cid: jnp.broadcast_to(out.columns[cid],
                                              out.valid.shape)[None, :]
                        for cid in out_cids}
                nulls = {cid: jnp.broadcast_to(out.null_mask(cid),
                                               out.valid.shape)[None, :]
                         for cid in out_cids}
                return (cols, nulls, out.valid[None, :],
                        jnp.stack([self._overflow, self._dense_oob]
                                  + [self._stage_actual[k]
                                     for k in skeys]
                                  + [c.astype(jnp.int64) for pair in
                                     self._exchange_rows for c in pair]))

        mapped = shard_map(body, mesh=self.mesh,
                           in_specs=tuple(in_specs), out_specs=out_specs,
                           check_vma=False)
        # abstract-eval to learn output dtypes, then build the pack plan
        shapes = jax.eval_shape(mapped, *feed_arrays)
        # traced, not estimated: the repartition stages that actually
        # exist in this program (the psum-directory pushdown compiles
        # shuffles away entirely — a caps-table estimate would lie)
        self.shuffle_bytes = int(self._shuffle_bytes)
        # (columns this program carries as a row index across a
        # compaction or a lookup, gathers it issues for them later,
        # probe slots of its fused lookups, packed slots of its
        # bucketed group-bys, slots of its window operators, the index
        # slots of those later gathers over the mesh, the index chains
        # it composed from the top, the middle indexes gathered
        # through twice, its windows' lexsorts, the window ORDER BY
        # specs it read in the groups' order, the operands of its
        # multi-key joins' build-side sorts, the index slots of its
        # sort-path group-bys' gathers and the fused lookups whose probe
        # side it compacts to the build keys' range):
        # deferred_columns_total / deferred_gathers_total /
        # lookup_probe_slots_total / agg_bucket_slots_total /
        # window_rows_total /
        # deferred_gather_slots_total / deferred_topdown_chains_total /
        # deferred_topdown_recomposed_total / window_sorts_total /
        # window_presorted_total / join_sort_operands_total /
        # agg_sort_gather_slots_total / lookup_range_compactions_total
        self.tallies = self._tallies
        s_cols, s_nulls, s_valid, _ = shapes
        out_meta = []
        for cid in out_cids:
            out_meta.append(("col", cid, np.dtype(s_cols[cid].dtype)))
        for cid in out_cids:
            out_meta.append(("null", cid, np.dtype(s_nulls[cid].dtype)))
        out_meta.append(("valid", "", np.dtype(s_valid.dtype)))

        def packed_fn(*flat_feeds):
            cols, nulls, valid, overflow = mapped(*flat_feeds)
            with stage_scope("output_pack"):
                rows = []
                for kind, cid, _dt in out_meta:
                    arr = (cols[cid] if kind == "col"
                           else nulls[cid] if kind == "null" else valid)
                    rows.append(_to_bits64(arr))
                return jnp.stack(rows), overflow

        # the cached executable closes over this compiler (via body); drop
        # the FeedSpec device arrays so the plan cache pins only code +
        # metadata, not every input table's HBM buffers
        self.feeds = None
        # stage_keys was populated by the eval_shape trace above; entries
        # are (walk_index, kind, width) — walk indices, not node ids, so
        # a plan-cache hit from a different plan instance can map them
        return jax.jit(packed_fn), feed_arrays, out_meta, self.stage_keys

    # ------------------------------------------------------------------
    def _unpack_feeds(self, flat_feeds) -> dict[int, Block]:
        blocks = {}
        i = 0
        flat = list(flat_feeds)
        for node_id, names in self._feed_index.items():
            sharded = self._feed_sharded[node_id]
            cols, nulls, valid = {}, {}, None
            for kind, cid in names:
                arr = flat[i]
                i += 1
                if sharded:
                    arr = arr[0]  # shard_map gives [1, cap] per device
                if kind == "col":
                    cols[cid] = arr
                elif kind == "null":
                    nulls[cid] = arr
                else:
                    valid = arr
            blocks[node_id] = Block(cols, valid, nulls)
        return blocks

    # ------------------------------------------------------------------
    # -- window functions -----------------------------------------------
    def _exec_window(self, node, feeds) -> Block:
        """Partition-sorted segmented scans (the WindowAgg analogue).

        Shuffle co-locates partitions (all_to_all by partition-key hash,
        like the repartition join's map+fetch), then per distinct ORDER
        BY spec: one lexsort + running segmented scans.  Results scatter
        back to pre-sort row positions (unique indices — vectorized on
        TPU), so the input block passes through unchanged with the
        window columns appended."""
        blk = self._exec(node.input, feeds)
        with stage_scope("window"):
            if node.having is not None:
                # over groups: HAVING first, the windows see its survivors
                blk = blk.with_filter(
                    predicate_mask(node.having, _src(blk), jnp))
            return self._window_over(node, blk)

    def _window_over(self, node, blk: Block) -> Block:
        from ..ops.aggregate import _segmented_scan

        if node.combine == "repartition":
            cap = self.caps.repartition[id(node)]
            # routing keys with explicit NULL flags (zeroed value + flag),
            # exactly like the aggregate combine shuffle: rows of a NULL
            # partition must land on ONE device
            karr = []
            bsrc = _src(blk)
            for p in node.partition_by:
                v, nm = evaluate(p, bsrc, jnp)
                v = jnp.broadcast_to(v, blk.valid.shape)
                if jnp.issubdtype(v.dtype, jnp.floating):
                    v = jax.lax.bitcast_convert_type(
                        v, jnp.int32 if v.dtype == jnp.float32
                        else jnp.int64)
                v = v.astype(jnp.int64)
                if nm is not None:
                    nmb = jnp.broadcast_to(nm, blk.valid.shape)
                    v = jnp.where(nmb, 0, v)
                    karr.append(v)
                    karr.append(nmb.astype(jnp.int64))
                else:
                    karr.append(v)
            if not karr:
                # one global partition: constant routing key
                karr = [jnp.zeros(blk.valid.shape, jnp.int64)]
            blk = self._repartition(blk, None, self.n_dev,
                                    tuple(range(self.n_dev)), cap,
                                    key_arrays=karr, valid=blk.valid,
                                    record_nid=id(node))
        n = blk.valid.shape[0]
        # static slots this window runs over, over the mesh: published
        # with the tallies (window_rows_total)
        self._window_rows += n * self.n_dev
        src = _src(blk)

        # partition keys (NULLs form their own partition, like GROUP BY):
        # zero the value lane under NULL — the raw lane holds whatever
        # the expression computed over garbage and would split the NULL
        # partition
        pkeys = []
        for p in node.partition_by:
            v, nm = evaluate(p, src, jnp)
            v = jnp.broadcast_to(v, (n,))
            if nm is not None:
                nmb = jnp.broadcast_to(nm, (n,))
                v = jnp.where(nmb, jnp.zeros((), v.dtype), v)
                pkeys.append(v)
                pkeys.append(nmb.astype(jnp.int32))
            else:
                pkeys.append(v)

        # group functions by their ORDER BY spec: one sort per spec
        by_order: dict[tuple, list] = {}
        for w, cid in node.functions:
            by_order.setdefault(w.order_by, []).append((w, cid))

        out_cols = dict(blk.columns)
        out_nulls = dict(blk.nulls)
        iota = jnp.arange(n, dtype=jnp.int32)
        for order_spec, fns in by_order.items():
            # after a dense_oob retry the group keys are not packed, and
            # a NULL order key no longer sorts last: sort
            if node.presorted and not (order_spec and self.caps.dense_off):
                self._window_presorted += 1
                order, valid_s = None, blk.valid
                # peers compare by (zeroed value, null flag), as in
                # _window_sort: a NULL group's lane holds filler that
                # may equal the value before it
                okeys = []
                for e, _d in order_spec:
                    v, nm = evaluate(e, src, jnp)
                    v = jnp.broadcast_to(v, (n,))
                    if nm is not None:
                        nmb = jnp.broadcast_to(nm, (n,))
                        v = jnp.where(nmb, jnp.zeros((), v.dtype), v)
                        okeys.append(nmb)
                    okeys.append(v)
                part_boundary, peer_boundary = self._window_runs(
                    blk.valid, pkeys, okeys, iota)
                # the rank a row's position gives: rows HAVING left out
                # and empty grid slots sit inside a partition, so the
                # valid rows before it
                at = jnp.cumsum(blk.valid.astype(jnp.int32)) - 1
            else:
                self._window_sorts += 1
                order, valid_s, part_boundary, peer_boundary = \
                    self._window_sort(blk, src, pkeys, order_spec)
                at = iota    # the sort put the invalid rows last
            with stage_scope("scan"):
                # partition/peer start positions via running max over
                # the rows' positions
                part_start = jax.lax.cummax(
                    jnp.where(part_boundary, at, jnp.int32(0)))
                peer_start = jax.lax.cummax(
                    jnp.where(peer_boundary, at, jnp.int32(0)))
                # position of the LAST row of each peer group (running
                # aggregates include peers)
                peer_end = _seg_last(peer_boundary, iota)

                for w, cid in fns:
                    res_s, null_s = self._window_value(
                        w, blk, src, order, valid_s, part_boundary,
                        peer_boundary, part_start, peer_start, peer_end,
                        at, _segmented_scan)
                    if order is not None:
                        res_s = jnp.zeros(n, res_s.dtype).at[order].set(
                            res_s)
                        if null_s is not None:
                            null_s = jnp.zeros(n, jnp.bool_).at[order] \
                                .set(null_s)
                    out_cols[cid] = res_s
                    if null_s is not None:
                        out_nulls[cid] = null_s
        return Block(out_cols, blk.valid, out_nulls)

    @staticmethod
    def _window_runs(valid, pkeys, okeys, iota):
        """Partition and peer starts of an input whose partitions are
        already contiguous runs, each in its ORDER BY order
        (WindowNode.presorted): a valid row that differs from the
        previous VALID row in some partition key, or else in some order
        key.  Rows HAVING left out, and empty grid slots, may sit inside
        a run; they open none and contribute nothing."""
        with stage_scope("sort"):
            n = valid.shape[0]
            prev = jax.lax.cummax(jnp.where(valid, iota, jnp.int32(-1)))
            prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32),
                                    prev[:-1]])
            at = jnp.maximum(prev, 0)
            start = valid & (prev < 0)
            for k in pkeys:
                start = start | (valid & (k != k[at]))
            peer = start
            for k in okeys:
                peer = peer | (valid & (k != k[at]))
            return start, peer

    @staticmethod
    def _window_sort(blk: Block, src, pkeys, order_spec):
        """One ORDER BY spec's lexsort (validity > partition keys >
        order keys) and the partition and peer boundaries in its order:
        (order, valid_s, part_boundary, peer_boundary)."""
        n = blk.valid.shape[0]
        with stage_scope("sort"):
            okeys = []       # sort operands for the order keys
            peer_keys = []   # equality keys defining rank peers
            for e, desc in order_spec:
                v, nm = evaluate(e, src, jnp)
                v = jnp.broadcast_to(v, (n,))
                nmb = (jnp.zeros(n, jnp.bool_) if nm is None
                       else jnp.broadcast_to(nm, (n,)))
                null_rank = (nmb if not desc else ~nmb).astype(jnp.int8)
                # zero the lane under NULL FIRST: peers compare by
                # (zeroed value, null flag) so all NULL rows tie
                v = jnp.where(nmb, jnp.zeros((), v.dtype), v)
                peer_keys.append(v)
                peer_keys.append(nmb.astype(jnp.int8))
                if desc:
                    v = (-v if jnp.issubdtype(v.dtype, jnp.floating)
                         else ~v)
                okeys.append((null_rank, v))
            operands = []
            for null_rank, v in reversed(okeys):
                operands.append(v)
                operands.append(null_rank)
            # lexsort, primary LAST: validity > partition keys > order keys
            order = jnp.lexsort(tuple(operands)
                                + tuple(reversed(pkeys))
                                + ((~blk.valid).astype(jnp.int32),)
                                ).astype(jnp.int32)
            valid_s = blk.valid[order]

            def shift_ne(a):
                return jnp.concatenate([jnp.ones((1,), jnp.bool_),
                                        a[1:] != a[:-1]])

            pb = jnp.zeros(n, jnp.bool_)
            for k in pkeys:
                pb = pb | shift_ne(k[order])
            if not pkeys:
                pb = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                                      jnp.zeros(n - 1, jnp.bool_)])
            part_boundary = pb | shift_ne(valid_s)  # invalid tail split off
            peer_boundary = part_boundary
            for k in peer_keys:
                peer_boundary = peer_boundary | shift_ne(k[order])
        return order, valid_s, part_boundary, peer_boundary

    def _window_value(self, w, blk, src, order, valid_s, part_boundary,
                      peer_boundary, part_start, peer_start, peer_end,
                      iota, seg_scan):
        """One window function over the sorted view → (values, nulls)."""
        n = valid_s.shape[0]
        if w.kind == "row_number":
            return (iota - part_start + 1).astype(jnp.int64), None
        if w.kind == "rank":
            return (peer_start - part_start + 1).astype(jnp.int64), None
        if w.kind == "dense_rank":
            c = jnp.cumsum(peer_boundary.astype(jnp.int32))
            at_start = jax.lax.cummax(
                jnp.where(part_boundary, c, jnp.int32(0)))
            return (c - at_start + 1).astype(jnp.int64), None

        # aggregate kinds: running (with ORDER BY, peers included) or
        # whole-partition (without)
        whole = not w.order_by
        if w.kind == "count_star":
            v = jnp.ones(n, jnp.int64)
            contrib = valid_s
        else:
            def sorted_(a):  # the sorted view (order None: presorted)
                a = jnp.broadcast_to(a, (n,))
                return a if order is None else a[order]

            raw, nm = evaluate(w.arg, src, jnp)
            v = sorted_(raw)
            contrib = valid_s if nm is None else (valid_s & ~sorted_(nm))
        kind = w.kind
        if kind in ("count", "count_star"):
            x = contrib.astype(jnp.int64)
            scan = seg_scan(x, part_boundary, jnp.add)
            res = scan[peer_end] if not whole else None
            if whole:
                res = self._partition_total(scan, part_boundary, n)
            return res, None
        if kind in ("sum", "avg"):
            acc = (self.compute_dtype
                   if jnp.issubdtype(v.dtype, jnp.floating)
                   else jnp.int64)
            x = jnp.where(contrib, v.astype(acc), jnp.zeros((), acc))
            scan = seg_scan(x, part_boundary, jnp.add)
            cnt = seg_scan(contrib.astype(jnp.int64), part_boundary,
                           jnp.add)
            if whole:
                scan = self._partition_total(scan, part_boundary, n)
                cnt = self._partition_total(cnt, part_boundary, n)
            else:
                scan = scan[peer_end]
                cnt = cnt[peer_end]
            if kind == "avg":
                res = scan.astype(self.compute_dtype) / \
                    jnp.maximum(cnt, 1).astype(self.compute_dtype)
            else:
                res = scan
            return res, cnt == 0
        if kind in ("min", "max"):
            ident = _big(v.dtype) if kind == "min" else _small(v.dtype)
            x = jnp.where(contrib, v, ident)
            op = jnp.minimum if kind == "min" else jnp.maximum
            scan = seg_scan(x, part_boundary, op)
            cnt = seg_scan(contrib.astype(jnp.int64), part_boundary,
                           jnp.add)
            if whole:
                scan = self._partition_total(scan, part_boundary, n)
                cnt = self._partition_total(cnt, part_boundary, n)
            else:
                scan = scan[peer_end]
                cnt = cnt[peer_end]
            return scan, cnt == 0
        raise ExecutionError(f"bad window kind {w.kind}")

    @staticmethod
    def _partition_total(scan, part_boundary, n):
        """Broadcast each partition's LAST scan value to all its rows."""
        iota = jnp.arange(n, dtype=jnp.int32)
        return scan[_seg_last(part_boundary, iota)]

    def _record(self, nid: int, kind: str, count, width: int) -> None:
        """Track one capacity-consuming stage's ACTUAL row count (traced
        scalar) and its buffer width (static).  Multiple records for the
        same (node, kind) — e.g. repart_both's two shuffles, or the two
        sort-path aggregation levels — merge by max: the shared buffer
        must cover the larger."""
        STAGE_NAMES[kind]  # a capacity stage is a stage: one vocabulary
        key = (nid, kind)
        c = count.astype(jnp.int64)
        if key in self._stage_actual:
            self._stage_actual[key] = jnp.maximum(self._stage_actual[key],
                                                  c)
        else:
            self._stage_actual[key] = c
        self._stage_width[key] = max(int(width),
                                     self._stage_width.get(key, 0))

    def _exec(self, node: PlanNode, feeds: dict[int, Block]) -> Block:
        if isinstance(node, ScanNode):
            blk = feeds[id(node)]
            if node.filter is not None:
                with stage_scope("scan_out"):
                    mask = predicate_mask(node.filter,
                                          _src(blk), jnp)
                    blk = blk.with_filter(mask)
                    self._record(id(node), "scan_out", blk.valid.sum(),
                                 blk.valid.shape[0])
                    k = self.caps.scan_out.get(id(node))
                    if k is not None and k < blk.valid.shape[0]:
                        blk = self._compact(blk, k)
            return blk
        if isinstance(node, ProjectNode):
            blk = self._exec(node.input, feeds)
            with stage_scope("project"):
                return self._project(blk, node.exprs)
        if isinstance(node, JoinNode):
            return self._exec_join(node, feeds)
        if isinstance(node, WindowNode):
            return self._exec_window(node, feeds)
        if isinstance(node, AggregateNode):
            return self._exec_aggregate(node, feeds)
        raise ExecutionError(f"unknown plan node {type(node).__name__}")

    def _compact(self, blk: Block, k: int) -> Block:
        """Pack surviving rows into k slots (selection-vector compaction).

        A selective filter leaves the block mostly padding; every
        downstream sort/shuffle/join still pays for the full capacity.
        Compaction costs one sort of the positions at the OLD size
        (`survivor_positions`) and gathers nothing itself: the columns
        follow as that row index (`Block.take`) and each costs one
        gather at the NEW size where it is first read — or, if it
        crosses the next compaction unread, a share of one index
        composition at that one's size.  Everything after it shrinks
        to the filtered-estimate size.  More survivors than k counts
        as capacity overflow (host retries with doubled slots)."""
        with stage_scope("compact"):
            por, out_valid, past = _compaction(blk.valid, k)
            self._overflow = self._overflow + past
            return blk.take(por, out_valid)

    def _project(self, blk: Block, exprs) -> Block:
        cols, nulls = {}, {}
        for e, cid in exprs:
            v, nmask = evaluate(e, _src(blk), jnp)
            v = jnp.broadcast_to(v, blk.valid.shape)
            cols[cid] = v
            if nmask is not None:
                nulls[cid] = jnp.broadcast_to(nmask, blk.valid.shape)
        return Block(cols, blk.valid, nulls)

    # -- ORDER BY + LIMIT pushdown --------------------------------------
    def _device_topk(self, blk: Block, k: int) -> Block:
        """Per-device top-k by the plan's ORDER BY keys.

        Shrinks the result transfer from the full padded buffer to
        n_dev·k rows; the host's exact merge sort over those rows is
        unchanged, so the device pass only needs the same total-order
        DIRECTION as the host comparator: DESC negates floats and
        bit-complements ints (~x is a monotone-decreasing bijection with
        no overflow corner), NULL placement follows PG defaults."""
        operands = []
        keys = []
        for e, desc, nulls_first in self.plan.host_order_by:
            v, nmask = evaluate(e, _src(blk), jnp)
            v = jnp.broadcast_to(v, blk.valid.shape)
            nm = (jnp.zeros(blk.valid.shape, jnp.bool_) if nmask is None
                  else jnp.broadcast_to(nmask, blk.valid.shape))
            nulls_last = (not nulls_first if nulls_first is not None
                          else not desc)
            null_rank = (nm if nulls_last else ~nm).astype(jnp.int8)
            ranks = [null_rank]
            if jnp.issubdtype(v.dtype, jnp.floating):
                # the host comparator (np.unique factorize) ranks NaN as
                # the LARGEST value; -NaN is still NaN and would sort
                # last under DESC, so NaN placement gets its own rank key
                nanm = jnp.isnan(v)
                ranks.append((~nanm if desc else nanm).astype(jnp.int8))
                v = jnp.where(nanm, jnp.zeros((), v.dtype), v)
                if desc:
                    v = -v
            elif desc:
                v = ~v  # monotone-decreasing bijection, no overflow corner
            keys.append((ranks, v))
        # jnp.lexsort: LAST operand is the primary key.  Precedence
        # (most→least): validity, key0 nulls, key0 nan-rank, key0 value, …
        for ranks, v in reversed(keys):
            operands.append(v)
            operands.extend(reversed(ranks))
        invalid = ~blk.valid
        order = jnp.lexsort(tuple(operands) + (invalid,))[:k] \
            .astype(jnp.int32)
        return blk.take(order, blk.valid[order])

    # -- joins ----------------------------------------------------------
    def _eval_keys(self, blk: Block, keys,
                   key_int32: tuple = ()) -> tuple[list, jnp.ndarray]:
        arrays = []
        valid = blk.valid
        if not keys:
            # keyless (cartesian) join: constant key matches every row pair
            return [jnp.zeros(blk.valid.shape, dtype=jnp.int64)], valid
        for i, e in enumerate(keys):
            v, nmask = evaluate(e, _src(blk), jnp)
            if not jnp.issubdtype(v.dtype, jnp.integer):
                if e.dtype.value in ("float32", "float64"):
                    raise PlanningError(
                        "float join keys are not supported; cast to int")
                v = v.astype(jnp.int64)
            # int64 is software-emulated on TPU (every gather/compare
            # splits into u32 pairs) — narrow to int32 whenever the
            # planner proved both sides' value ranges fit.  Like the
            # dense directory, the proof comes from statistics: a runtime
            # value outside int32 (stale stats / overlay rows) raises
            # dense_oob so the host recompiles wide instead of silently
            # wrapping keys.  dense_off retries disable narrowing too.
            narrow = (i < len(key_int32) and key_int32[i]
                      and not self.caps.dense_off)
            if narrow and v.dtype != jnp.int32:
                wide = (v < jnp.int64(-(1 << 31))) | \
                       (v > jnp.int64((1 << 31) - 1))
                if nmask is not None:
                    wide = wide & ~nmask
                self._dense_oob = self._dense_oob + \
                    (wide & blk.valid).sum().astype(jnp.int64)
            kd = jnp.int32 if narrow else jnp.int64
            arrays.append(jnp.broadcast_to(v.astype(kd), blk.valid.shape))
            if nmask is not None:
                valid = valid & ~nmask  # SQL: NULL never joins
        return arrays, valid

    def _pack_for(self, node: JoinNode, keys: list) -> tuple | None:
        """The packed key ranges of a multi-key join whose every range
        the planner knows (JoinNode.key_pack; not after a dense_oob
        retry, nor where the packed key and a build row's position do
        not fit one int64), or None; tallies the operands its
        build-side sort takes (join_sort_operands_total).  Asked once
        where the join sorts its build side."""
        from ..ops.join import build_sort_operands, packed_sort_fits

        if len(keys) < 2:
            return None
        pack = None if self.caps.dense_off else node.key_pack
        if pack is not None and not packed_sort_fits(
                pack, int(keys[0].shape[0])):
            pack = None
        self._join_sort_operands += build_sort_operands(len(keys), pack)
        return pack

    def _dense_for(self, extents: tuple, keys: list) -> tuple | None:
        """(base, extent) for a single-key build side, or None."""
        from ..ops.join import dense_directory_ok

        if self.caps.dense_off or len(keys) != 1:
            return None
        if not extents or extents[0] is None:
            return None
        base, extent = extents[0]
        if not dense_directory_ok(extent, keys[0].shape[0]):
            return None
        return (int(base), int(extent))

    def _repartition(self, blk: Block, keys, shard_count: int,
                     placement: tuple[int, ...], capacity: int,
                     key_arrays: list | None = None,
                     valid: jnp.ndarray | None = None,
                     keep_null_rows: bool = False,
                     bounds: tuple[int, ...] | None = None,
                     record_nid: int | None = None) -> Block:
        """pack → all_to_all → flatten: the map+fetch phases fused.

        When repartitioning toward a TABLE's sharding (repart_left/right),
        the single key must hash exactly like host ingest routing —
        hash_token_jax.  Multi-key shuffles (repart_both second key set,
        aggregate combine) only need internal consistency and use the
        64-bit combine folded to token space.
        """
        with stage_scope("repartition"):
            if key_arrays is None:
                key_arrays, valid = self._eval_keys(blk, keys)
                if keep_null_rows:
                    # outer-preserved side: NULL-key rows ride the shuffle
                    # (routed by their zeroed storage value — deterministic;
                    # they match nothing but must still emit null-extended)
                    valid = blk.valid
            if len(key_arrays) == 1:
                token = hash_token_jax(key_arrays[0])
            else:
                from ..ops.hashing import combine_hash64

                h = combine_hash64(key_arrays)
                token = ((h & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
                         .astype(jnp.int64) + INT32_MIN).astype(jnp.int32)
            if bounds is not None:
                # range-aware routing: shard bounds are arbitrary after splits
                mins = jnp.asarray(np.asarray(bounds, dtype=np.int64))
                shard = (jnp.searchsorted(mins, token.astype(jnp.int64),
                                          side="right") - 1).clip(
                    0, shard_count - 1).astype(jnp.int32)
            else:
                increment = HASH_TOKEN_COUNT // shard_count
                shard = jnp.minimum(
                    (token.astype(jnp.int64) - INT32_MIN) // increment,
                    shard_count - 1).astype(jnp.int32)
            placement_arr = jnp.asarray(np.asarray(placement, dtype=np.int32))
            target = placement_arr[shard]
            if record_nid is not None:
                # the binding constraint on this buffer is the largest
                # (source device → target device) bucket
                sent = jnp.zeros(self.n_dev, jnp.int32).at[target].add(
                    valid.astype(jnp.int32), mode="drop")
                hot = sent.max()
                self._record(record_nid, "repartition", hot, capacity)
                self._exchange_rows.append((hot, sent.sum()))

            all_cols = dict(blk.columns)
            for cid, nmask in blk.nulls.items():
                all_cols[NULL_PREFIX + cid] = nmask
            packed, pvalid, overflow = pack_by_target(
                all_cols, valid, target, self.n_dev, capacity)
            self._overflow = self._overflow + overflow.astype(jnp.int64)

            with stage_scope("exchange"):
                exchanged = {}
                for cid, arr in packed.items():
                    exchanged[cid] = jax.lax.all_to_all(
                        arr, SHARD_AXIS, split_axis=0, concat_axis=0,
                        tiled=True)
                new_valid = jax.lax.all_to_all(
                    pvalid, SHARD_AXIS, split_axis=0, concat_axis=0,
                    tiled=True)
            # mesh-wide exchange volume of this stage (each device moves its
            # whole [n_dev, cap] pack) — static shapes make it knowable at
            # trace time, surfaced via the Mesh: EXPLAIN line and
            # shuffle_bytes_total
            self._shuffle_bytes += self.n_dev * int(
                sum(int(a.size) * a.dtype.itemsize for a in packed.values())
                + int(pvalid.size) * pvalid.dtype.itemsize)
            with stage_scope("unpack"):
                flat_n = self.n_dev * capacity
                cols, nulls = {}, {}
                for cid, arr in exchanged.items():
                    flat = arr.reshape(flat_n)
                    if cid.startswith(NULL_PREFIX):
                        nulls[cid[len(NULL_PREFIX):]] = flat
                    else:
                        cols[cid] = flat
                return Block(cols, new_valid.reshape(flat_n), nulls)

    def _join_inputs(self, node: JoinNode, feeds):
        """Execute both sides + repartition stages + key evaluation.

        Returns (lblk, rblk, lkeys, lmatch, rkeys, rmatch) — shared by
        pair-emission execution and the aggregate-pushdown path."""
        lblk = self._exec(node.left, feeds)
        rblk = self._exec(node.right, feeds)

        # probe side preserved: left/full null-extend; anti KEEPS null-key
        # probe rows (they match nothing, so NOT EXISTS holds for them)
        keep_l = node.join_type in ("left", "full", "anti")
        keep_r = node.join_type in ("right", "full")  # build side preserved
        if node.strategy in ("local", "broadcast"):
            pass
        elif node.strategy == "cartesian_gather":
            # sharded × sharded keyless product: replicate the build side
            # on every device with one all_gather over ICI, then the
            # normal keyless pair emission crosses it with the local
            # probe shard
            def _ag(x):
                return jax.lax.all_gather(x, SHARD_AXIS, tiled=True)

            rblk = Block({cid: _ag(a) for cid, a in rblk.columns.items()},
                         _ag(rblk.valid),
                         {cid: _ag(m) for cid, m in rblk.nulls.items()})
        elif node.strategy == "repart_right":
            # hash ONLY the key aligned with the partner's distribution
            # column — extra equi-keys don't participate in routing
            cap = self.caps.repartition[id(node)]
            rblk = self._repartition(rblk,
                                     [node.right_keys[node.repart_key_idx]],
                                     node.left.dist.shard_count,
                                     node.left.dist.placement, cap,
                                     keep_null_rows=keep_r,
                                     bounds=node.left.dist.bounds or None,
                                     record_nid=id(node))
        elif node.strategy == "repart_left":
            cap = self.caps.repartition[id(node)]
            lblk = self._repartition(lblk,
                                     [node.left_keys[node.repart_key_idx]],
                                     node.right.dist.shard_count,
                                     node.right.dist.placement, cap,
                                     keep_null_rows=keep_l,
                                     bounds=node.right.dist.bounds or None,
                                     record_nid=id(node))
        elif node.strategy == "repart_both":
            cap = self.caps.repartition[id(node)]
            identity = tuple(range(self.n_dev))
            lblk = self._repartition(lblk, node.left_keys, self.n_dev,
                                     identity, cap, keep_null_rows=keep_l,
                                     record_nid=id(node))
            rblk = self._repartition(rblk, node.right_keys, self.n_dev,
                                     identity, cap, keep_null_rows=keep_r,
                                     record_nid=id(node))
        else:
            raise ExecutionError(f"bad join strategy {node.strategy}")

        with stage_scope("join_out"):
            key_int32 = getattr(node, "key_int32", ())
            lkeys, lmatch = self._eval_keys(lblk, node.left_keys, key_int32)
            rkeys, rmatch = self._eval_keys(rblk, node.right_keys, key_int32)
            # ON single-side gates: restrict MATCHING without dropping rows
            if node.left_match_filter is not None:
                lmatch = lmatch & predicate_mask(node.left_match_filter,
                                                 _src(lblk), jnp)
            if node.right_match_filter is not None:
                rmatch = rmatch & predicate_mask(node.right_match_filter,
                                                 _src(rblk), jnp)
        return lblk, rblk, lkeys, lmatch, rkeys, rmatch

    def _exec_lookup_join(self, node: JoinNode, lblk, rblk, lkeys, lmatch,
                          rkeys, rmatch) -> Block:
        """Fused PK-side lookup join: one output row per probe row.

        No pair-expansion buffers, no emission scan — probe columns pass
        through untouched and build columns follow the lookup's index
        (`Block.take`: gathered where they are first read, so a column
        only the aggregate reads crosses a later compaction as an
        index).  A
        probe with >1 match means the planner's uniqueness claim was
        stale: the surplus is reported as dense_oob so the host retries
        on the general expansion path (never silently dropped pairs)."""
        from ..ops.join import (_bounds, dense_unique_lookup,
                                sorted_unique_lookup)

        if node.join_type == "inner" and \
                getattr(node, "build_side", "right") == "left":
            bblk, bkeys, bmatch = lblk, lkeys, lmatch
            pblk, pkeys, pmatch = rblk, rkeys, rmatch
            extents = getattr(node, "left_key_extents", ())
        else:  # inner build=right, or LEFT join (build is always right)
            bblk, bkeys, bmatch = rblk, rkeys, rmatch
            pblk, pkeys, pmatch = lblk, lkeys, lmatch
            extents = getattr(node, "right_key_extents", ())
        if node.join_type == "inner":
            pblk, pkeys, pmatch = self._range_compact(
                node, bkeys, bmatch, pblk, pkeys, pmatch)
        self._lookup_probe_slots += self.n_dev * int(pblk.valid.shape[0])
        dense = self._dense_for(extents, bkeys)
        if self.sorted_lookup_shape(node, self.caps.dense_off):
            # a key extent past the knee of the directory gather (the
            # planner's pick, ops.join.sorted_lookup_eligible): no
            # directory, so no capacity and nothing to retry but oob
            with stage_scope("lookup_join"):
                bidx, counts, dense_oob = sorted_unique_lookup(
                    bkeys[0], bmatch, pkeys[0])
                counts = jnp.where(pmatch, counts, 0)
        elif dense is not None and len(bkeys) == 1:
            # unique build key (the fused-lookup planner claim): scatter
            # directory, NO build-side argsort per execution
            with stage_scope("lookup_join"):
                bidx, counts, dense_oob = dense_unique_lookup(
                    bkeys[0], bmatch, pkeys[0], dense[0], dense[1])
                counts = jnp.where(pmatch, counts, 0)
        else:
            with stage_scope("lookup_join"):
                order, lo, hi, dense_oob = _bounds(
                    bkeys, bmatch, pkeys, dense,
                    self._pack_for(node, bkeys))
                counts = jnp.where(pmatch, hi - lo, 0)
                m0 = bkeys[0].shape[0]
                bidx = order[jnp.clip(lo, 0, m0 - 1)]
        with stage_scope("join_out"):
            self._dense_oob = self._dense_oob + dense_oob.astype(jnp.int64) + \
                jnp.maximum(counts - 1, 0).sum().astype(jnp.int64)
            found = counts > 0
            probe_outer = node.join_type == "left"
            out_valid = pblk.valid if probe_outer else found
            if not probe_outer and node.residual is None:
                self._record(id(node), "join_out", out_valid.sum(),
                             out_valid.shape[0])
            built = bblk.take(bidx, out_valid)
            if probe_outer:
                # null extension: a probe row without a match reads NULL
                # in every build column, so their masks are read here;
                # the values stay deferred
                missing = ~found
                built = Block(built.columns, out_valid, {
                    cid: (built.nulls[cid] | missing
                          if cid in built.nulls else missing)
                    for cid in built.columns})
            blk = pblk.joined(built, out_valid)
            # selective FK join: compact BEFORE any build column is
            # gathered, so those gathers and everything downstream run at
            # the join-estimate size instead of the probe capacity
            k = self.caps.join_out.get(id(node))
            if (not probe_outer and node.residual is None and k is not None
                    and k < out_valid.shape[0]):
                blk = self._compact(blk, k)
            return blk

    def _range_compact(self, node: JoinNode, bkeys, bmatch, pblk: Block,
                       pkeys, pmatch):
        """A fused inner lookup's probe side cut to the rows whose keys
        lie inside the build side's matchable key range, before the
        probe: → (probe block, probe keys, probe match mask).

        A probe row outside [min, max] of a key, or with a NULL key,
        matches nothing, so no answer changes.  The rows inside are
        recorded (`join_range`); where capacity feedback installed a
        capacity (`Capacities.join_range`) and compacting this program's
        probe side to it pays — the width it was installed against may
        have shrunk since, under a compaction installed with it — they
        compact to it by one sort of positions, the keys follow by one
        gather at the new size and the probe runs there.  A date filter
        that keeps one interval of a fact table's date keys probes the
        interval's rows alone.  More rows inside than the capacity count
        as overflow (the host retries doubled)."""
        from .runner import compaction_pays

        with stage_scope("join_range"):
            inside = pmatch
            for b, p in zip(bkeys, pkeys):
                info = jnp.iinfo(b.dtype)
                lo = jnp.where(bmatch, b, info.max).min()
                hi = jnp.where(bmatch, b, info.min).max()
                inside = inside & (p >= lo) & (p <= hi)
            n = inside.shape[0]
            self._record(id(node), "join_range", inside.sum(), n)
            k = self.caps.join_range.get(id(node))
            if k is None or not compaction_pays(k, n):
                return pblk, pkeys, pmatch
            self._lookup_range_compactions += 1
            with stage_scope("compact"):
                por, out_valid, past = _compaction(inside, k)
                self._overflow = self._overflow + past
            return (pblk.take(por, out_valid), [p[por] for p in pkeys],
                    out_valid)

    def _exec_join(self, node: JoinNode, feeds) -> Block:
        lblk, rblk, lkeys, lmatch, rkeys, rmatch = \
            self._join_inputs(node, feeds)
        if node.join_type in ("semi", "anti"):
            return self._exec_semi_join(node, lblk, rblk, lkeys, lmatch,
                                        rkeys, rmatch)
        if getattr(node, "fuse_lookup", False) and not self.caps.dense_off:
            blk = self._exec_lookup_join(node, lblk, rblk, lkeys, lmatch,
                                         rkeys, rmatch)
            with stage_scope("join_out"):
                if node.residual is not None:
                    blk = blk.with_filter(predicate_mask(node.residual,
                                                         _src(blk), jnp))
                    if node.join_type == "inner":
                        # post-residual compaction: the residual-selective
                        # fused join can still shrink to its feedback size
                        self._record(id(node), "join_out", blk.valid.sum(),
                                     blk.valid.shape[0])
                        k = self.caps.join_out.get(id(node))
                        if k is not None and k < blk.valid.shape[0]:
                            blk = self._compact(blk, k)
            return blk
        with stage_scope("join_out"):
            out_cap = self.caps.join_out[id(node)]

            if node.join_type == "inner":
                # the planner picks the smaller side as build (sorted /
                # directory side); pair emission is symmetric for inner joins
                if getattr(node, "build_side", "right") == "left":
                    bkeys, bmatch, bblk = lkeys, lmatch, lblk
                    pkeys, pmatch, pblk = rkeys, rmatch, rblk
                    extents = getattr(node, "left_key_extents", ())
                else:
                    bkeys, bmatch, bblk = rkeys, rmatch, rblk
                    pkeys, pmatch, pblk = lkeys, lmatch, lblk
                    extents = getattr(node, "right_key_extents", ())
                dense = self._dense_for(extents, bkeys)
                bidx, pidx, out_valid, _miss, overflow, dense_oob = \
                    expand_join_pairs(bkeys, bmatch, pkeys, pmatch, pmatch,
                                      out_cap, probe_outer=False, dense=dense,
                                      pack=self._pack_for(node, bkeys))
                self._overflow = self._overflow + overflow.astype(jnp.int64)
                self._dense_oob = self._dense_oob + dense_oob.astype(jnp.int64)
                self._record(id(node), "join_out", out_valid.sum(), out_cap)
                blk = pblk.take(pidx, out_valid).joined(
                    bblk.take(bidx, out_valid), out_valid)
            else:
                blk = self._exec_outer_expand(node, lblk, rblk, lkeys, lmatch,
                                              rkeys, rmatch, out_cap)
            if node.residual is not None:
                blk = blk.with_filter(predicate_mask(node.residual,
                                                     _src(blk), jnp))
            return blk

    def _exec_semi_join(self, node: JoinNode, lblk: Block, rblk: Block,
                        lkeys, lmatch, rkeys, rmatch) -> Block:
        """Semi/anti join (decorrelated EXISTS / NOT EXISTS).

        Output rows ARE probe rows — no pair expansion, no emission
        buffer: without a residual this is one directory/binary-search
        bounds pass producing per-probe match counts (cheaper than any
        pair-emitting join).  With a cross-side residual (Q21's
        `l2.l_suppkey <> l1.l_suppkey`), candidate pairs expand, the
        residual evaluates per pair, and a scatter-max ORs survivors
        back onto probe rows.  With `flag_combine` (probe replicated
        over a sharded build) the per-device flags psum across the mesh.
        Reference semantics: semi/anti join rewrites in
        planner/recursive_planning.c:223."""
        with stage_scope("join_out"):
            from ..ops.join import _bounds

            dense = self._dense_for(getattr(node, "right_key_extents", ()),
                                    rkeys)
            n = lkeys[0].shape[0] if lkeys else lblk.valid.shape[0]
            if node.residual is None:
                order, lo, hi, dense_oob = _bounds(
                    rkeys, rmatch, lkeys, dense, self._pack_for(node, rkeys))
                self._dense_oob = self._dense_oob + dense_oob.astype(jnp.int64)
                matched = lmatch & (hi > lo)
            else:
                from ..planner.expr import expr_columns

                cap = self.caps.join_out[id(node)]
                bidx, pidx, out_valid, _miss, overflow, dense_oob = \
                    expand_join_pairs(rkeys, rmatch, lkeys, lmatch, lmatch,
                                      cap, probe_outer=False, dense=dense,
                                      pack=self._pack_for(node, rkeys))
                self._overflow = self._overflow + overflow.astype(jnp.int64)
                self._dense_oob = self._dense_oob + dense_oob.astype(jnp.int64)
                self._record(id(node), "join_out", out_valid.sum(), cap)
                # gather ONLY the residual's columns at pair capacity — the
                # output block is the probe block, so everything else would
                # be wasted HBM traffic on the widest intermediate
                need = expr_columns(node.residual)
                cols, nulls = {}, {}
                for cid in need:
                    if cid in lblk.columns:
                        cols[cid] = lblk.columns[cid][pidx]
                        nm = lblk.nulls.get(cid)
                        if nm is not None:
                            nulls[cid] = nm[pidx]
                    elif cid in rblk.columns:
                        cols[cid] = rblk.columns[cid][bidx]
                        nm = rblk.nulls.get(cid)
                        if nm is not None:
                            nulls[cid] = nm[bidx]
                pair = Block(cols, out_valid, nulls)
                ok = out_valid & predicate_mask(node.residual, _src(pair), jnp)
                matched = (jnp.zeros(n, jnp.int32)
                           .at[pidx].max(ok.astype(jnp.int32))) > 0
            if getattr(node, "flag_combine", False):
                matched = jax.lax.psum(matched.astype(jnp.int32),
                                       SHARD_AXIS) > 0
            return lblk.with_filter(~matched if node.join_type == "anti"
                                    else matched)

    def _exec_outer_expand(self, node: JoinNode, lblk: Block, rblk: Block,
                           lkeys, lmatch, rkeys, rmatch,
                           out_cap: int) -> Block:
        """LEFT/RIGHT/FULL pair emission + null extension.

        LEFT: unmatched probe rows emit once with build columns NULL.
        RIGHT/FULL: unmatched build rows append as a second fixed-size
        segment with probe columns NULL; a replicated (broadcast) build
        side combines matched flags across devices with psum and emits
        its unmatched rows on device 0 only.  Reference semantics:
        planner/multi_router_planner.c:187 outer-join handling."""
        probe_outer = node.join_type in ("left", "full")
        build_outer = node.join_type in ("right", "full")
        replicated_build = build_outer and node.strategy == "broadcast"
        dense = self._dense_for(getattr(node, "right_key_extents", ()),
                                rkeys)
        bidx, pidx, pair_valid, bmissing, unmatched_b, overflow, dense_oob \
            = expand_join_outer(rkeys, rblk.valid, rmatch,
                                lkeys, lblk.valid, lmatch, out_cap,
                                probe_outer, build_outer,
                                replicated_build, SHARD_AXIS, dense=dense,
                                pack=self._pack_for(node, rkeys))
        self._overflow = self._overflow + overflow.astype(jnp.int64)
        self._dense_oob = self._dense_oob + dense_oob.astype(jnp.int64)
        self._record(id(node), "join_out", pair_valid.sum(), out_cap)

        cols, nulls = {}, {}
        for cid, arr in lblk.columns.items():
            cols[cid] = arr[pidx]
        for cid, nmask in lblk.nulls.items():
            nulls[cid] = nmask[pidx]
        for cid, arr in rblk.columns.items():
            cols[cid] = arr[bidx]
            gathered = rblk.nulls.get(cid)
            nulls[cid] = (bmissing if gathered is None
                          else (gathered[bidx] | bmissing))
        valid = pair_valid

        if build_outer:
            m = rblk.valid.shape[0]
            seg_cols, seg_nulls = {}, {}
            for cid, arr in lblk.columns.items():
                seg_cols[cid] = jnp.broadcast_to(arr[0], (m,))
                seg_nulls[cid] = jnp.ones(m, jnp.bool_)
            for cid, arr in rblk.columns.items():
                seg_cols[cid] = arr
                nm = rblk.nulls.get(cid)
                seg_nulls[cid] = (jnp.zeros(m, jnp.bool_) if nm is None
                                  else nm)
            out_cols, out_nulls = {}, {}
            for cid in cols:
                out_cols[cid] = jnp.concatenate([cols[cid], seg_cols[cid]])
                pn = nulls.get(cid)
                if pn is None:
                    pn = jnp.zeros(pair_valid.shape, jnp.bool_)
                out_nulls[cid] = jnp.concatenate([pn, seg_nulls[cid]])
            return Block(out_cols,
                         jnp.concatenate([valid, unmatched_b]), out_nulls)
        return Block(cols, valid, nulls)

    # -- aggregation ----------------------------------------------------
    def _agg_values(self, node: AggregateNode, blk: Block):
        """Evaluate aggregate inputs → [(value, kind, contrib_valid)]."""
        values = []
        for a, cid in node.aggs:
            if a.kind == "count_star":
                values.append((jnp.ones(blk.valid.shape, jnp.int64),
                               "count", None))
                continue
            v, nmask = evaluate(a.arg, _src(blk), jnp)
            v = jnp.broadcast_to(v, blk.valid.shape)
            if a.kind in ("sum", "avg"):
                if jnp.issubdtype(v.dtype, jnp.floating):
                    v = v.astype(self.compute_dtype)
                else:
                    v = v.astype(jnp.int64)
            kind = "count" if a.kind == "count" else a.kind
            vv = None if nmask is None else ~jnp.broadcast_to(
                nmask, blk.valid.shape)
            values.append((v, kind, vv))
        return values

    def _agg_inputs(self, node: AggregateNode, blk: Block):
        """Evaluate group keys and aggregate inputs on the input block."""
        key_arrays = []
        key_meta = []  # (cid, dtype)
        for g, cid in node.group_keys:
            v, nmask = evaluate(g, _src(blk), jnp)
            v = jnp.broadcast_to(v, blk.valid.shape)
            key_arrays.append(v)
            if nmask is not None:
                # NULLs form their own group: null flag joins the key
                key_arrays.append(
                    jnp.broadcast_to(nmask, blk.valid.shape).astype(jnp.int32))
                key_meta.append((cid, True))
            else:
                key_meta.append((cid, False))
        values = self._agg_values(node, blk)
        return key_arrays, key_meta, values

    def _segment_aggregate_maybe_packed(self, node: AggregateNode,
                                        key_arrays, key_meta, values,
                                        valid):
        """One dispatch point for both sort-path aggregation stages:
        pack the composite key when ranges are known (accumulating the
        stale-range oob), plain multi-key segment_aggregate otherwise.
        The groups come out at the aggregate's capacity where it is
        below the input's slots (`_group_slots`), every per-group
        operation at that width; the gathers' index slots count into
        agg_sort_gather_slots_total, and the groups past the capacity
        into the overflow (`_group_overflow`).  → (group keys, results,
        group validity)."""
        n = valid.shape[0]
        out = self._group_slots(node, n)
        gathered = []
        packed, pack_oob = self._pack_group_keys(node, key_arrays,
                                                 key_meta, valid)
        if packed is not None:
            self._dense_oob = self._dense_oob + pack_oob
            sorted_key, order = self._composite_order(
                node, packed, valid) or (None, None)
            gk, res, gvalid, ngroups = segment_aggregate(
                [packed], values, valid, out_keys=key_arrays,
                order=order, sorted_key=sorted_key, out=out,
                gathered=gathered)
        else:
            gk, res, gvalid, ngroups = segment_aggregate(
                key_arrays, values, valid, out=out, gathered=gathered)
        self._agg_sort_gather_slots += sum(gathered) * self.n_dev
        self._group_overflow(node, ngroups, n)
        return gk, res, gvalid

    @staticmethod
    def _composite_order(node: AggregateNode, packed, valid):
        """(sorted key, order) of the rows by packed key, invalid rows
        last, from ONE single-operand sort of (key, row position)
        (`ops.aggregate.sort_packed`).  None where the two do not fit
        63 bits."""
        top = 1
        for _base, extent, _hn in node.key_ranges:
            top *= extent + 1     # the packed slot space, invalid = top
        with stage_scope("sort"):
            return sort_packed(packed, valid, top)

    def _pack_group_keys(self, node: AggregateNode, key_arrays, key_meta,
                         valid, kr=None):
        """Composite group keys → ONE int64 sort key, using the
        planner's statically-known ranges (key_ranges, or the explicit
        `kr` a caller passes — the bucketed grid reuses this exact
        layout for its slot ids so the two paths cannot diverge on
        null/oob edge cases).  Returns (packed [n] | None, oob scalar):
        single-operand argsorts are far faster on TPU than the
        multi-operand lexsort; rows whose key falls outside the planned
        range are COUNTED (they would alias another slot) so the
        dense_oob retry recompiles with packing off.  The null slot is
        always reserved — runtime null masks may exist even when the
        planner believed a key non-nullable — and is a key's first but
        for the keys of `nulls_last` (a window reads the groups in
        ascending order of those, NULLS LAST), whose values take slots
        0 … extent - 1 and NULL the last."""
        if kr is None:
            kr = getattr(node, "key_ranges", None)
        if kr is None or self.caps.dense_off or len(kr) != len(key_meta):
            return None, None
        expected = len(key_meta) + sum(1 for _c, f in key_meta if f)
        if expected != len(key_arrays):
            return None, None
        n = valid.shape[0]
        packed = jnp.zeros(n, jnp.int64)
        oob = jnp.zeros((), jnp.int64)
        ai = 0
        for i, ((base, extent, _hn), (cid, has_flag)) in enumerate(
                zip(kr, key_meta)):
            v = key_arrays[ai].astype(jnp.int64)
            ai += 1
            nm = None
            if has_flag:
                nm = key_arrays[ai] != 0
                ai += 1
            raw = v - jnp.int64(base)
            inb = (raw >= 0) & (raw < extent)
            width = extent + 1           # slot 0 = NULL
            null_slot, value_slot = 0, raw + 1
            if i in node.nulls_last:     # slot extent = NULL
                null_slot, value_slot = extent, raw
            if nm is not None:
                slot = jnp.where(nm, null_slot, value_slot)
                oob = oob + (valid & ~nm & ~inb).sum().astype(jnp.int64)
            else:
                slot = value_slot
                oob = oob + (valid & ~inb).sum().astype(jnp.int64)
            packed = packed * width + jnp.clip(slot, 0, width - 1)
        # invalid rows sort last (PACK_SLOT_LIMIT headroom guarantees no
        # collision with a real slot)
        packed = jnp.where(valid, packed, jnp.iinfo(jnp.int64).max)
        return packed, oob

    @staticmethod
    def agg_bucket_shape(node: AggregateNode, dense_off: bool) -> bool:
        """Single decision point for the bucketed dense-grid group-by:
        capacity planning (the result grid's agg_out sizing), the
        compiler dispatch, EXPLAIN's tag and the groupby_bucketed_total
        counter must all agree.  The pick itself is the planner's
        (`bucket_keys`, from ops.groupby.group_bucket_eligible)."""
        if dense_off or node.combine not in ("local", "repartition"):
            return False
        if not node.bucket_keys or node.bucket_total <= 0:
            return False
        # below the cap the flat dense grid wins
        return node.dense_keys is None

    @staticmethod
    def sorted_lookup_shape(node: JoinNode, dense_off: bool) -> bool:
        """Single decision point for the sort-and-scan lookup arm: the
        compiler's dispatch, the lookup_sorted_total counter and
        EXPLAIN's tag agree because all of them ask here.  The pick
        itself is the planner's (`lookup_sorted`, from
        ops.join.sorted_lookup_eligible)."""
        return bool(getattr(node, "lookup_sorted", False)
                    and getattr(node, "fuse_lookup", False)
                    and not dense_off)

    @staticmethod
    def dense_lookup_shape(node: JoinNode, dense_off: bool) -> bool:
        """Static mirror of _exec_lookup_join's dispatch onto
        ops.join.dense_unique_lookup, for the lookup_dense_total
        counter: a fused single-key lookup whose build key's extent is
        known and that does not sort.  Exact, though the dispatch itself
        asks dense_directory_ok with the padded build capacity: every
        extent the sorted arm leaves here is under
        SORTED_LOOKUP_MIN_EXTENT, and dense_directory_ok holds for
        those whatever the build side's size."""
        if dense_off or not getattr(node, "fuse_lookup", False) or \
                len(node.left_keys) != 1 or \
                getattr(node, "lookup_sorted", False):
            return False
        build_left = node.join_type == "inner" and \
            getattr(node, "build_side", "right") == "left"
        extents = getattr(node, "left_key_extents" if build_left
                          else "right_key_extents", ())
        return bool(extents) and extents[0] is not None

    @staticmethod
    def agg_pushdown_shape(node: AggregateNode) -> bool:
        """Static mirror of _try_join_agg_pushdown's eligibility: True ⇒
        the pushdown will handle this aggregate WITHOUT pair emission, so
        capacity planning must not charge the join-output buffer (at
        scale that phantom buffer can alone trip the plan-size guard)."""
        from ..planner import expr as ir

        if node.combine != "global" or node.group_keys:
            return False
        j = node.input
        if not isinstance(j, JoinNode) or j.join_type != "inner" or \
                j.residual is not None:
            return False
        if j.dist.kind == "replicated":
            return False
        lcids = set(j.left.out_columns)
        rcids = set(j.right.out_columns)
        agg_side = None
        for a, _cid in node.aggs:
            if a.kind == "count_star":
                continue
            if a.kind not in ("count", "sum", "min", "max"):
                return False
            cids = {c.cid for c in ir.walk(a.arg) if isinstance(c, ir.BCol)}
            side = ("left" if cids <= lcids
                    else "right" if cids <= rcids else None)
            if side is None or (agg_side is not None and side != agg_side):
                return False
            agg_side = side
        return True

    def _try_join_agg_pushdown(self, node: AggregateNode, feeds):
        """Global aggregate over an inner join WITHOUT pair emission.

        count(*) over a join is sum(matches-per-probe-row); sum/min/max
        whose arguments come from one side reduce over that side weighted
        by match counts.  The O(pairs) emission buffer (and its overflow
        retries) disappear entirely — the analogue of the reference
        pushing count/sum into worker queries instead of shipping join
        rows (planner/multi_logical_optimizer.c WorkerExtendedOpNode).
        Returns None when the shape doesn't qualify (eligibility mirrors
        agg_pushdown_shape, which capacity planning consults)."""
        from ..planner import expr as ir
        from ..ops.join import _bounds

        if not self.agg_pushdown_shape(node):
            return None
        j = node.input
        lcids = set(j.left.out_columns)
        agg_side = None
        for a, _cid in node.aggs:
            if a.kind == "count_star":
                continue
            cids = {c.cid for c in ir.walk(a.arg) if isinstance(c, ir.BCol)}
            agg_side = "left" if cids <= lcids else "right"
        if agg_side is None:
            # count(*) only: probe whichever side the planner made probe
            agg_side = ("left" if getattr(j, "build_side", "right")
                        == "right" else "right")

        if j.strategy in ("repart_both", "repart_left", "repart_right"):
            # shuffle-free variant: when the build key has a dense
            # extent, a psum'd count directory replaces BOTH all_to_all
            # repartitions — the worker-partial-aggregate move done
            # mesh-natively (see _agg_pushdown_psum_directory)
            pushed = self._agg_pushdown_psum_directory(node, j, agg_side,
                                                       feeds)
            if pushed is not None:
                return pushed

        lblk, rblk, lkeys, lmatch, rkeys, rmatch = \
            self._join_inputs(j, feeds)
        if agg_side == "left":
            pblk, pkeys, pmatch = lblk, lkeys, lmatch
            bkeys, bmatch = rkeys, rmatch
            extents = getattr(j, "right_key_extents", ())
        else:
            pblk, pkeys, pmatch = rblk, rkeys, rmatch
            bkeys, bmatch = lkeys, lmatch
            extents = getattr(j, "left_key_extents", ())
        with stage_scope("lookup_join"):
            dense = self._dense_for(extents, bkeys)
            _order, lo, hi, dense_oob = _bounds(bkeys, bmatch, pkeys, dense,
                                                self._pack_for(j, bkeys))
            self._dense_oob = self._dense_oob + dense_oob.astype(jnp.int64)
            counts = jnp.where(pmatch, (hi - lo).astype(jnp.int64), 0)
        return self._agg_from_match_counts(node, pblk, counts)

    # psum'd count directories stay worthwhile while the collective
    # volume (extent × 4 B, once per execution) is small next to the
    # all_to_all volume it replaces (the whole input, twice); 4M slots
    # = 16 MB over ICI is the break-even neighborhood on a v5e
    PSUM_DIRECTORY_MAX_SLOTS = 1 << 22

    def _agg_pushdown_psum_directory(self, node: AggregateNode, j,
                                     agg_side: str, feeds):
        """Global aggregate over a REPARTITION join without any
        shuffle: each device scatter-adds its local build rows into a
        [extent] count directory keyed by the dense join key, ONE psum
        makes the directory global, and every probe row reads its
        global match count locally.  The two all_to_all stages (and
        their pack sorts — the dominant cost of the dual-repartition
        shape) vanish; what crosses the mesh is extent × 4 bytes.
        Returns None when ineligible (multi-key join, no dense extent,
        directory too wide) — the caller falls back to the repartition
        pushdown, and a dense_oob retry (stale statistics) lands there
        too via caps.dense_off."""
        if self.caps.dense_off:
            return None
        if len(j.left_keys) != 1 or len(j.right_keys) != 1:
            return None
        extents = (getattr(j, "right_key_extents", ())
                   if agg_side == "left"
                   else getattr(j, "left_key_extents", ()))
        if not extents or extents[0] is None:
            return None
        base, extent = int(extents[0][0]), int(extents[0][1])
        if not (0 < extent + 1 <= self.PSUM_DIRECTORY_MAX_SLOTS):
            return None

        lblk = self._exec(j.left, feeds)
        rblk = self._exec(j.right, feeds)
        with stage_scope("join_out"):
            key_int32 = getattr(j, "key_int32", ())
            lkeys, lmatch = self._eval_keys(lblk, j.left_keys, key_int32)
            rkeys, rmatch = self._eval_keys(rblk, j.right_keys, key_int32)
            if j.left_match_filter is not None:
                lmatch = lmatch & predicate_mask(j.left_match_filter,
                                                 _src(lblk), jnp)
            if j.right_match_filter is not None:
                rmatch = rmatch & predicate_mask(j.right_match_filter,
                                                 _src(rblk), jnp)
        if agg_side == "left":
            pblk, pkeys, pmatch = lblk, lkeys, lmatch
            bkeys, bmatch = rkeys, rmatch
        else:
            pblk, pkeys, pmatch = rblk, rkeys, rmatch
            bkeys, bmatch = lkeys, lmatch

        with stage_scope("lookup_join"):
            # build-side rows outside the planned extent would silently
            # miss the directory — count them into dense_oob so stale
            # statistics recompile on the repartition path.  Probe-side
            # out-of-extent keys simply match nothing (exact, no retry).
            raw_b = bkeys[0].astype(jnp.int64) - jnp.int64(base)
            b_in = (raw_b >= 0) & (raw_b < extent)
            self._dense_oob = self._dense_oob + \
                (bmatch & ~b_in).sum().astype(jnp.int64)
            idx = jnp.where(bmatch & b_in, raw_b,
                            jnp.int64(extent)).astype(jnp.int32)
            dirc = jnp.zeros(extent + 1, jnp.int32).at[idx].add(
                jnp.int32(1), mode="drop")[:extent]
            dirc = jax.lax.psum(dirc, SHARD_AXIS)
            raw_p = pkeys[0].astype(jnp.int64) - jnp.int64(base)
            p_in = (raw_p >= 0) & (raw_p < extent)
            pidx = jnp.clip(raw_p, 0, extent - 1).astype(jnp.int32)
            counts = jnp.where(pmatch & p_in, dirc[pidx],
                               jnp.int32(0)).astype(jnp.int64)
        return self._agg_from_match_counts(node, pblk, counts,
                                           counts_global=True)

    def _agg_from_match_counts(self, node: AggregateNode, pblk: Block,
                               counts, counts_global: bool = False):
        """Finish an aggregate pushdown from per-probe-row match
        counts.  `counts_global=True` ⇒ counts already include every
        device's build rows (the psum-directory path) — the cross-
        device combine over PROBE rows is identical either way, since
        each probe row lives on exactly one device."""
        with stage_scope("agg_global"):
            values = self._agg_values(node, pblk)
            cols, nulls = {}, {}
            for (a, cid), (v, kind, vv) in zip(node.aggs, values):
                contrib = pblk.valid if vv is None else (pblk.valid & vv)
                w = jnp.where(contrib, counts, 0)
                if kind == "count":
                    total = jax.lax.psum(w.sum(), SHARD_AXIS)
                    cols[cid] = total[None].astype(jnp.int64)
                    continue
                if kind == "sum":
                    local = (jnp.where(contrib, v, jnp.zeros((), v.dtype))
                             * w.astype(v.dtype)).sum()
                    total = jax.lax.psum(local, SHARD_AXIS)
                elif kind == "min":
                    local = jnp.where(contrib & (w > 0), v,
                                      _big(v.dtype)).min()
                    total = jax.lax.pmin(local, SHARD_AXIS)
                elif kind == "max":
                    local = jnp.where(contrib & (w > 0), v,
                                      _small(v.dtype)).max()
                    total = jax.lax.pmax(local, SHARD_AXIS)
                else:
                    raise ExecutionError(f"bad agg kind {kind}")
                cols[cid] = total[None].astype(v.dtype)
                any_pairs = jax.lax.psum(w.sum(), SHARD_AXIS) > 0
                nulls[cid] = (~any_pairs)[None]
            my_dev = jax.lax.axis_index(SHARD_AXIS)
            return Block(cols, jnp.asarray([my_dev == 0]), nulls)

    def _exec_aggregate(self, node: AggregateNode, feeds) -> Block:
        pushed = self._try_join_agg_pushdown(node, feeds)
        if pushed is not None:
            return pushed
        blk = self._exec(node.input, feeds)
        if node.input.dist.kind == "replicated":
            # replicated rows exist on every device; aggregate them once
            blk = blk.with_filter(
                jnp.broadcast_to(jax.lax.axis_index(SHARD_AXIS) == 0,
                                 blk.valid.shape))
        if node.dense_keys is not None and not self.caps.dense_off and \
                node.combine in ("local", "repartition"):
            with stage_scope("agg_grid"):
                return self._exec_dense_aggregate(node, blk)
        if self.agg_bucket_shape(node, self.caps.dense_off):
            with stage_scope("agg_bucket"):
                bucketed = self._exec_bucketed_aggregate(node, blk)
            if bucketed is not None:
                return bucketed
            # None is a defensive invariant check (see the helper) —
            # with today's _agg_inputs/bucket_keys invariants it cannot
            # fire; falling through lands on the sort path regardless
        with stage_scope("agg_global" if node.combine == "global"
                         else "agg_sort"):
            key_arrays, key_meta, values = self._agg_inputs(node, blk)

        if node.combine == "global":
            with stage_scope("agg_global"):
                # no GROUP BY: reduce to one row per device, psum/pmin/pmax
                cols, nulls = {}, {}
                for (a, cid), (v, kind, vv) in zip(node.aggs, values):
                    contrib_valid = (blk.valid if vv is None
                                     else blk.valid & vv)
                    if kind == "count":
                        local = contrib_valid.astype(jnp.int64).sum()
                        total = jax.lax.psum(local, SHARD_AXIS)
                    elif kind == "sum":
                        local = jnp.where(contrib_valid, v,
                                          jnp.zeros((), v.dtype)).sum()
                        total = jax.lax.psum(local, SHARD_AXIS)
                    elif kind == "min":
                        big = _big(v.dtype)
                        local = jnp.where(contrib_valid, v, big).min()
                        total = jax.lax.pmin(local, SHARD_AXIS)
                    elif kind == "max":
                        small = _small(v.dtype)
                        local = jnp.where(contrib_valid, v, small).max()
                        total = jax.lax.pmax(local, SHARD_AXIS)
                    else:
                        raise ExecutionError(f"bad agg kind {kind}")
                    cols[cid] = total[None].astype(v.dtype) \
                        if kind != "count" else total[None].astype(jnp.int64)
                    # COUNT of zero rows is 0, not NULL; others are NULL
                    # on empty
                    if kind != "count":
                        any_rows = jax.lax.psum(
                            contrib_valid.sum(), SHARD_AXIS) > 0
                        nulls[cid] = (~any_rows)[None]
                # emit exactly one valid row on device 0
                my_dev = jax.lax.axis_index(SHARD_AXIS)
                valid = jnp.asarray([my_dev == 0])
                return Block(cols, valid, nulls)

        with stage_scope("agg_sort"):
            # companion contribution-counts per value aggregate: an all-NULL
            # group must yield NULL (not the reduction identity) for
            # sum/min/max/avg — count of contributors == 0 ⇒ NULL
            companions = []
            for (a, cid), (v, kind, vv) in zip(node.aggs, values):
                if kind != "count":
                    companions.append((v, "count", vv))
                else:
                    companions.append(None)
            all_values = values + [c for c in companions if c is not None]
            gk, res, gvalid = self._segment_aggregate_maybe_packed(
                node, key_arrays, key_meta, all_values, blk.valid)
            main_res = res[:len(values)]
            comp_res = res[len(values):]
            partial = self._partial_block(node, key_meta, gk, main_res, gvalid)
            ci = 0
            for (a, cid), comp in zip(node.aggs, companions):
                if comp is not None:
                    cnt = comp_res[ci]
                    ci += 1
                    partial = Block(
                        {**partial.columns, f"__cnt_{cid}": cnt},
                        partial.valid,
                        {**partial.nulls, cid: cnt == 0})

        if node.combine == "local":
            return partial
        if node.combine != "repartition":
            raise ExecutionError(f"bad combine mode {node.combine}")

        # shuffle partial groups by key hash, then merge partials.  Key
        # arrays include the null flags so NULL groups survive the shuffle
        # (routed by flag+zero value, consistently on every device).
        # repart_keys (DISTINCT rewrite) restricts ROUTING to a key
        # subset — co-routed rows still merge by the full key set
        with stage_scope("repartition"):
            route_idx = (set(node.repart_keys)
                         if getattr(node, "repart_keys", None) is not None
                         else None)
            shuffle_keys = []
            for ki, (cid, has_null) in enumerate(key_meta):
                if route_idx is not None and ki not in route_idx:
                    continue
                v = partial.columns[cid]
                if jnp.issubdtype(v.dtype, jnp.floating):
                    v = jax.lax.bitcast_convert_type(
                        v, jnp.int32 if v.dtype == jnp.float32 else jnp.int64)
                shuffle_keys.append(v.astype(jnp.int64))
                if has_null:
                    nm = partial.null_mask(cid)
                    # zero the value under NULL so routing is deterministic
                    shuffle_keys[-1] = jnp.where(nm, 0, shuffle_keys[-1])
                    shuffle_keys.append(nm.astype(jnp.int64))
        cap = self.caps.repartition[id(node)]
        shuffled = self._repartition(partial, None, self.n_dev,
                                     tuple(range(self.n_dev)), cap,
                                     key_arrays=shuffle_keys,
                                     valid=partial.valid,
                                     record_nid=id(node))
        with stage_scope("agg_sort"):
            key_arrays2 = []
            for cid, has_null in key_meta:
                key_arrays2.append(shuffled.columns[cid])
                if has_null:
                    key_arrays2.append(
                        shuffled.null_mask(cid).astype(jnp.int32))
            values2 = []
            comp_cids = []
            for a, cid in node.aggs:
                v = shuffled.columns[cid]
                kind = {"count": "sum", "count_star": "sum", "sum": "sum",
                        "avg": "sum", "min": "min", "max": "max"}[a.kind]
                values2.append((v, kind, None))
                if f"__cnt_{cid}" in shuffled.columns:
                    comp_cids.append(cid)
            for cid in comp_cids:
                values2.append((shuffled.columns[f"__cnt_{cid}"], "sum", None))
            gk2, res2, gvalid2 = self._segment_aggregate_maybe_packed(
                node, key_arrays2, key_meta, values2, shuffled.valid)
            final = self._partial_block(node, key_meta, gk2,
                                        res2[:len(node.aggs)], gvalid2)
            for cid, cnt in zip(comp_cids, res2[len(node.aggs):]):
                final = Block(final.columns, final.valid,
                              {**final.nulls, cid: cnt == 0})
            return final

    def _exec_dense_aggregate(self, node: AggregateNode, blk: Block) -> Block:
        """Dense-grid aggregation: group keys with known small value ranges
        map to one slot id; aggregation is unsorted stacked segment
        reductions over [total_slots] and the cross-device combine is
        psum/pmin/pmax — no sort, no all_to_all.  This is the TPU-native
        replacement for the reference's worker hash-aggregate + coordinator
        combine on low-cardinality GROUP BYs (multi_logical_optimizer.c):
        static shapes, MXU/VPU-friendly, ICI collectives."""
        specs = node.dense_keys
        total = node.dense_total
        n = blk.valid.shape[0]

        # slot id per row (invalid rows → trash slot `total`)
        slot = jnp.zeros(n, dtype=jnp.int32)
        stride = 1
        strides = []
        for (g, _cid), (base, extent, has_null) in zip(node.group_keys,
                                                       specs):
            v, nmask = evaluate(g, _src(blk), jnp)
            v = jnp.broadcast_to(v, (n,))
            # subtract base in the key's own width FIRST — int64 keys with
            # values past int32 would wrap if narrowed before rebasing
            rebased = v - jnp.asarray(base, v.dtype)
            idx = jnp.clip(rebased, 0, extent - 1).astype(jnp.int32)
            nm = (jnp.broadcast_to(nmask, (n,)) if nmask is not None
                  else None)
            # a key outside the planned extent means the stats the grid
            # was planned from went stale — surface as dense_oob (→ the
            # host retries on the sort path) rather than silently
            # clipping into a group
            oob = (rebased < 0) | (rebased >= extent)
            if nm is not None:
                oob = oob & ~nm
            if nm is not None and not has_null:
                # runtime NULLs the planner didn't predict: force a retry
                # path instead of mis-grouping them
                oob = oob | nm
            self._dense_oob = self._dense_oob + \
                (oob & blk.valid).sum().astype(jnp.int64)
            if has_null and nm is not None:
                idx = jnp.where(nm, jnp.int32(extent), idx)
            slot = slot + idx * stride
            strides.append(stride)
            stride *= extent + (1 if has_null else 0)
        slot = jnp.where(blk.valid, slot, jnp.int32(total))

        # value inputs (value, kind, contrib_valid) — counts in int32
        # (int64 segment ops are emulated on TPU), widened after reduce
        values = self._agg_values(node, blk)
        rows_per_slot = self._dense_segment_sum(
            blk.valid.astype(jnp.int32)[:, None], slot, total)[:total, 0]

        # stacked reductions: one segment op per (reduction kind, dtype)
        results: list = [None] * len(values)
        companions: list = [None] * len(values)
        by_kind: dict[tuple, list[tuple[int, jnp.ndarray]]] = {}
        for i, (v, kind, vv) in enumerate(values):
            contrib = blk.valid if vv is None else (blk.valid & vv)
            if kind == "count":
                arr = contrib.astype(jnp.int32)
                by_kind.setdefault(("sum", jnp.int32), []).append((i, arr))
                continue
            if kind == "sum":
                z = jnp.zeros((), v.dtype)
                arr = jnp.where(contrib, v, z)
                by_kind.setdefault(("sum", v.dtype), []).append((i, arr))
            elif kind == "min":
                arr = jnp.where(contrib, v, _big(v.dtype))
                by_kind.setdefault(("min", v.dtype), []).append((i, arr))
            elif kind == "max":
                arr = jnp.where(contrib, v, _small(v.dtype))
                by_kind.setdefault(("max", v.dtype), []).append((i, arr))
            else:
                raise ExecutionError(f"bad agg kind {kind}")
            # companion: non-NULL contribution count (all-NULL group → NULL)
            comp = contrib.astype(jnp.int32)
            by_kind.setdefault(("companion", jnp.int32), []).append((i, comp))
        for (op, _dt), items in by_kind.items():
            data = jnp.stack([a for _, a in items], axis=1)
            if op in ("sum", "companion"):
                red = self._dense_segment_sum(data, slot, total)
            elif op == "min":
                red = jax.ops.segment_min(data, slot,
                                          num_segments=total + 1)
            else:
                red = jax.ops.segment_max(data, slot,
                                          num_segments=total + 1)
            red = red[:total]
            for j, (i, _a) in enumerate(items):
                if op == "companion":
                    companions[i] = red[:, j]
                else:
                    results[i] = red[:, j]

        results, companions, rows_per_slot, out_valid = \
            self._combine_grid(node, values, results, companions,
                               rows_per_slot)

        # reconstruct key columns from the slot grid
        iota = jnp.arange(total, dtype=jnp.int32)
        cols: dict[str, jnp.ndarray] = {}
        nulls: dict[str, jnp.ndarray] = {}
        for (g, cid), (base, extent, has_null), st in zip(
                node.group_keys, specs, strides):
            ext = extent + (1 if has_null else 0)
            idx = (iota // st) % ext
            cols[cid] = (idx.clip(0, extent - 1).astype(jnp.int64)
                         + base).astype(g.dtype.numpy_dtype)
            if has_null:
                nulls[cid] = idx == extent
        for i, ((a, cid), (v, kind, _vv)) in enumerate(
                zip(node.aggs, values)):
            r = results[i]
            if kind == "count":
                r = r.astype(jnp.int64)
            cols[cid] = r
            if companions[i] is not None:
                nulls[cid] = companions[i] == 0
        return Block(cols, out_valid, nulls)

    @staticmethod
    def _combine_grid(node: AggregateNode, values, results, companions,
                      rows_per_slot):
        """Cross-device combine shared by the flat and bucketed dense
        grids (repartition → psum/pmin/pmax over the slot grid, device
        0 emits; local → per-device slots).  One implementation so the
        two paths' NULL-companion and combine semantics cannot
        diverge.  Returns (results, companions, rows_per_slot,
        out_valid)."""
        if node.combine == "repartition":
            rows_per_slot = jax.lax.psum(rows_per_slot, SHARD_AXIS)
            for i, (_v, kind, _vv) in enumerate(values):
                if kind in ("count", "sum"):
                    results[i] = jax.lax.psum(results[i], SHARD_AXIS)
                elif kind == "min":
                    results[i] = jax.lax.pmin(results[i], SHARD_AXIS)
                else:
                    results[i] = jax.lax.pmax(results[i], SHARD_AXIS)
                if companions[i] is not None:
                    companions[i] = jax.lax.psum(companions[i],
                                                 SHARD_AXIS)
            out_valid = (rows_per_slot > 0) & \
                (jax.lax.axis_index(SHARD_AXIS) == 0)
        else:
            out_valid = rows_per_slot > 0
        return results, companions, rows_per_slot, out_valid

    def _exec_bucketed_aggregate(self, node: AggregateNode,
                                 blk: Block) -> Block | None:
        """Bucketed dense-grid aggregation (ops/groupby.py): the packed
        composite slot (the same key_ranges packing the sort path
        uses) radix-partitions into GROUP_TILE_SLOTS-wide dense tiles,
        each reduced sort-free — no argsort over the input capacity,
        no all_to_all combine (cross-device merge is psum/pmin/pmax
        over the slot grid, exactly like the flat dense grid).  Stale
        key ranges count into dense_oob and the host retries on the
        sort path; the pack is sized by the input's slots whatever the
        key's distribution, so it has no capacity to overflow."""
        from ..ops.groupby import (
            bucketed_grid_aggregate,
            group_bucket_count,
            group_pack_shape,
        )
        from ..utils.faultinjection import fault_point

        # named seam: a failure while building the bucketed pack must
        # leave the plan cache without a half-built entry (fires at
        # trace time, like executor.plan_cache_fill)
        fault_point("executor.agg_bucket_fill")
        specs = node.bucket_keys
        total = node.bucket_total

        # packed slot per row — _pack_group_keys IS the slot layout
        # (width = extent + 1 per key, slot 0 = NULL or, for a key of
        # nulls_last, slot extent, out-of-range
        # values clipped but COUNTED into dense_oob so stale statistics
        # recompile on the sort path instead of returning aliased
        # groups); sharing the helper keeps the grid bit-identical to
        # the sort path's packed keys on every null/oob edge case
        key_arrays, key_meta, values = self._agg_inputs(node, blk)
        packed, oob = self._pack_group_keys(node, key_arrays, key_meta,
                                            blk.valid, kr=specs)
        if packed is None:
            # defensive: bucket_keys is one spec per group key and
            # key_arrays/key_meta come from the same _agg_inputs walk,
            # so the helper's shape bail-outs are statically
            # unreachable today — this guard only matters if a future
            # _agg_inputs change breaks that invariant
            return None
        self._dense_oob = self._dense_oob + oob
        # valid rows pack to < total (clipped per key); the invalid-row
        # int64-max sentinel is dropped by the pack's valid mask anyway
        slot32 = jnp.clip(packed, 0, total - 1).astype(jnp.int32)

        # value inputs, masked exactly like the flat dense grid:
        # sums/counts zero under non-contribution, min/max at identity;
        # a companion contribution count per value aggregate drives the
        # all-NULL-group → NULL rule
        op_values: list[tuple[jnp.ndarray, str]] = []
        comp_idx: list[int | None] = []
        for v, kind, vv in values:
            contrib = blk.valid if vv is None else (blk.valid & vv)
            if kind == "count":
                op_values.append((contrib.astype(jnp.int32), "count"))
                comp_idx.append(None)
                continue
            if kind == "sum":
                arr = jnp.where(contrib, v, jnp.zeros((), v.dtype))
            elif kind == "min":
                arr = jnp.where(contrib, v, _big(v.dtype))
            elif kind == "max":
                arr = jnp.where(contrib, v, _small(v.dtype))
            else:
                raise ExecutionError(f"bad agg kind {kind}")
            op_values.append((arr, kind))
            comp_idx.append(len(op_values))
            op_values.append((contrib.astype(jnp.int32), "count"))

        res, rows_per_slot = bucketed_grid_aggregate(
            slot32, blk.valid, op_values, total)
        nc, chunk = group_pack_shape(int(slot32.shape[0]),
                                     group_bucket_count(total))
        self._agg_bucket_slots += self.n_dev * nc * chunk

        results = []
        companions = []
        for i, (_v, kind, _vv) in enumerate(values):
            pos = sum(1 for c in comp_idx[:i] if c is not None) + i
            results.append(res[pos])
            ci = comp_idx[i]
            companions.append(None if ci is None else res[ci])

        results, companions, rows_per_slot, out_valid = \
            self._combine_grid(node, values, results, companions,
                               rows_per_slot)
        # 'agg_grid', not 'agg_out': shrinking THIS buffer means
        # installing a real compaction pass over the slot grid, so
        # feedback must apply the ≥3× compaction economics — the sort
        # path's agg_out is a free slice and tightens at 0.85
        self._record(id(node), "agg_grid",
                     (rows_per_slot > 0).sum(), total)

        # reconstruct key columns from the packed slot (first key is
        # most significant; lane 0 of each key's width is NULL, the last
        # lane for a key of nulls_last)
        iota = jnp.arange(total, dtype=jnp.int32)
        cols: dict[str, jnp.ndarray] = {}
        nulls: dict[str, jnp.ndarray] = {}
        stride = total
        for i, ((base, extent, _hn), (g, cid)) in enumerate(
                zip(specs, node.group_keys)):
            width = extent + 1
            stride //= width
            idx = (iota // stride) % width
            if i in node.nulls_last:
                idx = (idx + 1) % width
            cols[cid] = ((idx - 1).clip(0, extent - 1).astype(jnp.int64)
                         + base).astype(g.dtype.numpy_dtype)
            nulls[cid] = idx == 0
        for i, ((_a, cid), (_v, kind, _vv)) in enumerate(
                zip(node.aggs, values)):
            r = results[i]
            if kind == "count":
                r = r.astype(jnp.int64)
            cols[cid] = r
            if companions[i] is not None:
                nulls[cid] = companions[i] == 0
        out = Block(cols, out_valid, nulls)

        # high-cardinality grids are mostly empty under selective
        # filters: compact live slots to the estimated group capacity
        # (underestimates overflow and regrow like every static buffer)
        k = self.caps.agg_out.get(id(node))
        if k is not None and k < total:
            with stage_scope("agg_out"):
                out = self._compact(out, k)
        return out

    # one-hot MXU segment-sum eligibility bound: bench_kernels.py on
    # TPU v5e measured the matmul formulation 2-10× faster than XLA's
    # scatter-based segment_sum up to ~4096 slots, slower past ~8192
    DENSE_ONEHOT_MAX_SLOTS = 4096

    def _dense_segment_sum(self, data: jnp.ndarray, slot: jnp.ndarray,
                           total: int) -> jnp.ndarray:
        """Σ per slot of [n, m] data → [total+1, m].

        Routes to one-hot × data on the MXU when exactness allows:
        f32 sums accumulate in f32 either way, and int32 counts are
        exact in f32 while n < 2^24 (n is the static row capacity).
        int64 / f64 stacks stay on segment_sum (exact)."""
        n, _m = data.shape
        dt = data.dtype
        eligible = (total + 1 <= self.DENSE_ONEHOT_MAX_SLOTS
                    and (dt == jnp.float32
                         or (dt == jnp.int32 and n < (1 << 24))))
        if not eligible:
            return jax.ops.segment_sum(data, slot, num_segments=total + 1)
        onehot = (slot[:, None] == jnp.arange(
            total + 1, dtype=jnp.int32)[None, :]).astype(jnp.float32)
        red = jax.lax.dot_general(
            onehot, data.astype(jnp.float32),
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return red.astype(dt) if dt == jnp.int32 else red

    def _group_slots(self, node: AggregateNode, n: int) -> int:
        """The group slots a sort-path aggregation over `n` input slots
        produces: the planner's (or capacity feedback's) estimate where
        it is below `n`, else `n`."""
        agg_cap = self.caps.agg_out.get(id(node))
        return n if agg_cap is None or agg_cap >= n else agg_cap

    def _group_overflow(self, node: AggregateNode, ngroups, n: int):
        """Record the true group count over `n` input slots (capacity
        feedback); groups beyond the capacity count as overflow (→
        retry, doubled)."""
        with stage_scope("agg_out"):
            self._record(id(node), "agg_out", ngroups, n)
            out = self._group_slots(node, n)
            if out < n:
                self._overflow = self._overflow + jnp.maximum(
                    ngroups.astype(jnp.int64) - out, 0)

    def _partial_block(self, node: AggregateNode, key_meta, gk, res,
                       gvalid) -> Block:
        cols, nulls = {}, {}
        i = 0
        for cid, has_null in key_meta:
            cols[cid] = gk[i]
            i += 1
            if has_null:
                nulls[cid] = gk[i].astype(jnp.bool_)
                i += 1
        for (a, cid), r in zip(node.aggs, res):
            cols[cid] = r
        return Block(cols, gvalid, nulls)


def _seg_last(boundary: jnp.ndarray, iota: jnp.ndarray) -> jnp.ndarray:
    """Per row: position of the LAST row of its segment (boundary marks
    segment STARTS) — reverse running-min over next-boundary positions."""
    n = iota.shape[0]
    nb = jnp.concatenate([boundary[1:], jnp.ones((1,), jnp.bool_)])
    return jnp.flip(jax.lax.cummin(
        jnp.flip(jnp.where(nb, iota, jnp.int32(n - 1)))))


def _src(blk: Block) -> ColumnSource:
    return ColumnSource(blk.columns, blk.nulls)


def _big(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf, dtype)
    return jnp.asarray(jnp.iinfo(dtype).max, dtype)


def _small(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(-jnp.inf, dtype)
    return jnp.asarray(jnp.iinfo(dtype).min, dtype)
