"""Streamed execution: tables larger than HBM feed in stripe batches.

The reference never holds a whole table in memory — the columnar reader
iterates stripe-by-stripe (columnar/columnar_reader.c:323) and the adaptive
executor streams task results.  The resident-feed executor here does the
opposite (whole padded table in HBM, executor/feed.py), which caps table
size at device memory.  This module restores the streaming property the
TPU-native way:

* the LARGEST sharded scan of the plan is picked as the *stream* node;
* its stripes are assembled into fixed-shape [n_dev, batch_cap] batches
  (same capacity every batch ⇒ ONE compiled program, reused);
* a background thread prefetches + device_puts batch i+1 while the mesh
  executes batch i (the double-buffered stripe→HBM pipeline of SURVEY §7
  step 4);
* per-batch device outputs merge on the host: group rows re-aggregate
  (count/sum/min/max are distributive; avg is already split into
  sum+count by the planner), plain row outputs concatenate.

Eligibility is a plan-shape property (`_stream_path`): every join between
the stream scan and the root must see the full other side per batch and
emit each output row in exactly one batch — inner joins anywhere, outer
joins only when the streamed side is the preserved side.  Aggregates are
allowed only at the root (distributive merge); windows never (a window
partition must see all its rows at once).
"""

from __future__ import annotations

import queue
import threading
import time

import numpy as np

from ..planner.plan import (
    AggregateNode,
    JoinNode,
    PlanNode,
    ProjectNode,
    QueryPlan,
    ScanNode,
    WindowNode,
    table_placement,
)
from ..catalog import DistributionMethod
from .cache import feeds_signature, node_fingerprint
from .compiler import FeedSpec, _round_cap, unpack_outputs
from .feed import _feed_scan_cached, walk_plan


# ---------------------------------------------------------------------------
# eligibility + sizing

def _scan_width_bytes(node: ScanNode, catalog, compute_dtype) -> int:
    """Per-row feed bytes for one scan: column widths (after the f64→
    compute-dtype policy) + a null byte per column + the validity byte."""
    meta = catalog.table(node.rel.table)
    w = 1
    for cid in node.columns:
        cname = cid.split(".", 1)[1]
        dt = meta.schema.column(cname).dtype.numpy_dtype
        if dt == np.float64 and compute_dtype is not None:
            dt = np.dtype(compute_dtype)
        w += np.dtype(dt).itemsize + 1
    return w


def _scan_dev_rows(node: ScanNode, catalog, store, n_dev: int) -> int:
    """Max rows any device would hold for this scan (pre-padding)."""
    meta = catalog.table(node.rel.table)
    shards = catalog.table_shards(node.rel.table)
    if meta.method != DistributionMethod.HASH:
        return store.table_row_count(node.rel.table)
    placement = table_placement(catalog, node.rel.table, n_dev)
    per_dev = [0] * n_dev
    for s, dev in zip(shards, placement):
        if node.pruned_shards is not None and \
                s.shard_index not in node.pruned_shards:
            continue
        per_dev[dev] += store.shard_row_count(node.rel.table, s.shard_id)
    return max(per_dev) if per_dev else 0


def _stream_path(plan: QueryPlan, stream_id: int) -> bool:
    """Is batching the scan `stream_id` semantics-preserving?

    Path constraints (root → stream scan):
    * JoinNode: inner always; LEFT only when the stream side is the left
      (preserved/probe) subtree; RIGHT only when it is the right.  FULL
      never (both sides preserved — unmatched flags need global state).
    * AggregateNode: only as the plan ROOT (its distributive partials
      merge host-side); a nested aggregate (DISTINCT rewrite) would
      dedupe per batch only.
    * WindowNode: never on the path.
    """

    def path_to(node: PlanNode) -> list[PlanNode] | None:
        if id(node) == stream_id:
            return [node]
        kids = []
        if isinstance(node, JoinNode):
            kids = [node.left, node.right]
        elif isinstance(node, (AggregateNode, ProjectNode, WindowNode)):
            kids = [node.input]
        for k in kids:
            p = path_to(k)
            if p is not None:
                return [node] + p
        return None

    path = path_to(plan.root)
    if path is None:
        return False
    for i, node in enumerate(path[:-1]):
        if isinstance(node, JoinNode):
            on_left = path[i + 1] is node.left
            if node.join_type == "inner":
                continue
            if node.join_type in ("left", "semi", "anti") and on_left:
                # semi/anti distribute over probe batches when the build
                # side is fully resident (each batch sees every match)
                continue
            if node.join_type == "right" and not on_left:
                continue
            return False
        if isinstance(node, WindowNode):
            return False
        if isinstance(node, AggregateNode):
            if i != 0:
                return False
            if not _mergeable_aggregate(node):
                return False
    return True


def _mergeable_aggregate(node: AggregateNode) -> bool:
    for a, _cid in node.aggs:
        if getattr(a, "distinct", False):
            return False
        if a.kind not in ("count", "count_star", "sum", "min", "max"):
            return False
    return True


def stream_candidates(plan: QueryPlan, catalog) -> list[ScanNode]:
    """Hash-distributed scans on a semantics-preserving stream path —
    the eligibility half of pick_stream_node, shared with the OOM
    degradation ladder (can a forced-stream rung help this plan?)."""
    return [s for s in walk_plan(plan.root) if isinstance(s, ScanNode)
            and catalog.table(s.rel.table).method ==
            DistributionMethod.HASH and _stream_path(plan, id(s))]


def pick_stream_node(plan: QueryPlan, catalog, store, n_dev: int,
                     compute_dtype, budget: int, forced_rows: int = 0,
                     shrink: int = 1, force: bool = False,
                     prefetch_depth: int = 1):
    """(stream ScanNode, batch_cap) or None.

    Streams only when the combined per-device feed bytes exceed `budget`
    and the largest sharded scan is on a semantics-preserving path.  A
    non-zero `forced_rows` (test/tuning knob) overrides batch sizing.

    `shrink`/`force` are the OOM degradation ladder's inputs
    (executor.Executor.degrade_for_oom): `shrink` divides the computed
    batch_cap (each level is one recompile, memoized via the plan
    fingerprint), `force` streams even when the feeds fit the
    configured budget — a real allocator OOM proved the effective
    ceiling lower than the configured one.

    `prefetch_depth` is the bounded batch-queue depth
    (scan_prefetch_depth): depth+1 batches can be device-resident at
    once, so the per-batch budget divisor scales with it — a deeper
    queue must mean smaller batches, never more resident bytes than
    the budget the streaming path exists to honor."""
    scans = [n for n in walk_plan(plan.root) if isinstance(n, ScanNode)]
    sizes = {}
    for s in scans:
        rows = _scan_dev_rows(s, catalog, store, n_dev)
        sizes[id(s)] = _round_cap(max(rows, 1)) * \
            _scan_width_bytes(s, catalog, compute_dtype)
    total = sum(sizes.values())
    if total <= budget and not force:
        return None
    candidates = [s for s in scans
                  if catalog.table(s.rel.table).method ==
                  DistributionMethod.HASH and _stream_path(plan, id(s))]
    if not candidates:
        return None
    stream = max(candidates, key=lambda s: sizes[id(s)])
    width = _scan_width_bytes(stream, catalog, compute_dtype)
    stream_rows = max(1, sizes[id(stream)] // width)
    if forced_rows:
        return stream, _round_cap(max(1, forced_rows // max(1, shrink)))
    other = total - sizes[id(stream)]
    # resident batches (depth queued + 1 consumed) + downstream join/
    # shuffle intermediates sized off the batch: budget each batch at
    # 1/(depth+5) of what remains (depth 1 keeps the historic 1/6)
    div = max(1, int(prefetch_depth)) + 5
    avail = budget - other
    if avail < div * width * 4096 and not force:
        return None  # other feeds leave no useful room — fall through
    batch_cap = int(max(avail, div * width * 1024) // (div * width))
    if force:
        # a forced stream must actually batch: at least 2 batches even
        # when the sizing math says everything fits — and the usual
        # 1024-row floor must not re-inflate a small table's halved
        # cap back into one full-table batch (128 is the _round_cap
        # floor; shrink may push small tables' batches below 1024 by
        # design — that is exactly what the rung is for)
        batch_cap = min(batch_cap, -(-stream_rows // 2))
    floor = 128 if force else 1024
    batch_cap = _round_cap(max(floor, batch_cap // max(1, shrink)))
    if not force and batch_cap * 1.05 >= stream_rows:
        return None  # would be a single batch anyway
    return stream, batch_cap


# ---------------------------------------------------------------------------
# batched stream feeds

class StreamBatcher:
    """Assemble one scan's stripes into fixed-shape [n_dev, batch_cap]
    feed batches, reading lazily (at most one open stripe per device)."""

    def __init__(self, node: ScanNode, catalog, store, mesh, n_dev: int,
                 compute_dtype, batch_cap: int, accountant=None,
                 stats=None):
        from .hbm import accountant_for

        self.stats = stats
        self.node = node
        self.catalog = catalog
        self.store = store
        self.mesh = mesh
        self.n_dev = n_dev
        self.compute_dtype = compute_dtype
        self.batch_cap = batch_cap
        self.accountant = (accountant_for(store.data_dir)
                           if accountant is None else accountant)
        table = node.rel.table
        shards = catalog.table_shards(table)
        placement = table_placement(catalog, table, n_dev)
        self.colnames = [cid.split(".", 1)[1] for cid in node.columns]
        # same storage-name-mapped chunk-group skip filter the resident
        # feed path applies (min/max pruning must not vanish just
        # because the table streams)
        self._chunk_filter = None
        if node.filter is not None:
            from .feed import make_chunk_filter

            meta0 = catalog.table(table)
            name_map = {c.name: store.storage_column_name(table, c.name)
                        for c in meta0.schema.columns}
            self._chunk_filter = make_chunk_filter(node.filter, None,
                                                   name_map)
        self._dev_shards: list[list[int]] = [[] for _ in range(n_dev)]
        self._dev_rows = [0] * n_dev
        for s, dev in zip(shards, placement):
            if node.pruned_shards is not None and \
                    s.shard_index not in node.pruned_shards:
                continue
            self._dev_shards[dev].append(s.shard_id)
            self._dev_rows[dev] += store.shard_row_count(table, s.shard_id)
        self.n_batches = max(
            1, max(-(-r // batch_cap) for r in self._dev_rows))
        # per-device pull state: a stripe iterator + carryover remainder
        self._iters = [self._stripes(d) for d in range(n_dev)]
        self._carry: list[tuple[dict, dict, int] | None] = [None] * n_dev
        # Which columns carry a nulls plane is decided ONCE, from
        # manifest stripe stats, so every batch presents the same pytree
        # structure to the compiled program (a per-batch decision would
        # crash the cached executable when NULL presence differs across
        # stripes).  Missing stats are treated as "may have NULLs".
        null_cols: set[str] = set()
        storage_of = {c: store.storage_column_name(table, c)
                      for c in self.colnames}
        recs = [r for sids in self._dev_shards for sid in sids
                for r in store.shard_stripe_records(table, sid)]
        for cname in self.colnames:
            s_name = storage_of[cname]
            for r in recs:
                stats = r.get("stats") or {}
                s = stats.get(s_name)
                if s is None or len(s) < 3 or s[2]:
                    # stats missing / pre-null-count manifest / has NULLs
                    null_cols.add(cname)
                    break
        self._null_cols = null_cols

    def _stripes(self, dev: int):
        for sid in self._dev_shards[dev]:
            yield from self.store.iter_shard_stripes(
                self.node.rel.table, sid, self.colnames,
                self._chunk_filter)

    def _pull(self, dev: int, want: int):
        """Up to `want` rows from device dev's stripe stream."""
        vals: list[dict] = []
        got = 0
        while got < want:
            if self._carry[dev] is not None:
                v, m, n = self._carry[dev]
                self._carry[dev] = None
            else:
                try:
                    v, m, n = next(self._iters[dev])
                except StopIteration:
                    break
                if n == 0:
                    continue
            take = min(n, want - got)
            if take < n:
                self._carry[dev] = (
                    {c: a[take:] for c, a in v.items()},
                    {c: a[take:] for c, a in m.items()}, n - take)
                v = {c: a[:take] for c, a in v.items()}
                m = {c: a[:take] for c, a in m.items()}
            vals.append((v, m, take))
            got += take
        return vals, got

    def feed(self, batch_index: int) -> FeedSpec | None:
        """Build the next batch (sequential; called once per index).
        Returns None when the stream is exhausted — checked BEFORE any
        buffer allocation or device transfer, so exhaustion costs
        nothing.  Batch 0 always materializes (empty-table queries still
        need one execution)."""
        from ..stats.tracing import trace_span

        node, rel = self.node, self.node.rel
        cap, n_dev = self.batch_cap, self.n_dev
        t_pull = time.perf_counter()
        with trace_span("stream.decode"):
            per_dev = [self._pull(d, cap) for d in range(n_dev)]
        if self.stats is not None:
            self.stats.add(
                stream_decode_seconds=time.perf_counter() - t_pull)
        self.last_rows = sum(got for _v, got in per_dev)
        if batch_index > 0 and self.last_rows == 0:
            return None
        arrays, nulls = {}, {}
        for cid, cname in zip(node.columns, self.colnames):
            dtype = rel.schema.column(cname).dtype.numpy_dtype
            if dtype == np.float64 and self.compute_dtype is not None:
                dtype = np.dtype(self.compute_dtype)
            buf = np.zeros((n_dev, cap), dtype=dtype)
            with_nulls = cname in self._null_cols
            nbuf = np.zeros((n_dev, cap), dtype=bool) if with_nulls \
                else None
            for d in range(n_dev):
                pos = 0
                for v, m, take in per_dev[d][0]:
                    buf[d, pos:pos + take] = v[cname].astype(dtype)
                    if with_nulls:
                        nbuf[d, pos:pos + take] = ~m[cname]
                    pos += take
            arrays[cid] = buf
            if with_nulls:
                nulls[cid] = nbuf
        valid = np.zeros((n_dev, cap), dtype=bool)
        for d in range(n_dev):
            valid[d, :per_dev[d][1]] = True
        feed = FeedSpec(node=node, sharded=True, arrays=arrays,
                        nulls=nulls, valid=valid, capacity=cap,
                        dev_rows=[per_dev[d][1] for d in range(n_dev)])
        # accounted placement (executor/hbm.py): a batch that does not
        # fit raises the classified DeviceMemoryExhausted through the
        # consumer queue, and its charge releases with the batch arrays
        acc = self.accountant

        def put(a):
            # device-owned slice seam: each device's batch rows (built
            # from only its own shards' stripes) transfer independently
            # and charge per device (executor/hbm.py)
            return acc.place_sharded_slices(
                self.mesh, [a[d] for d in range(self.n_dev)], "stream")

        t_put = time.perf_counter()
        with trace_span("stream.transfer"):
            feed.arrays = {c: put(a) for c, a in feed.arrays.items()}
            feed.nulls = {c: put(a) for c, a in feed.nulls.items()}
            feed.valid = put(feed.valid)
        if self.stats is not None:
            self.stats.add(
                stream_transfer_seconds=time.perf_counter() - t_put)
        return feed


# ---------------------------------------------------------------------------
# host merge

def _flatten_batch(cols, nulls, valid):
    v = np.asarray(valid).reshape(-1)
    fc, fn = {}, {}
    for cid in cols:
        fc[cid] = np.asarray(cols[cid]).reshape(-1)[v]
        fn[cid] = np.asarray(nulls[cid]).reshape(-1)[v]
    return fc, fn


_BIG = {"min": lambda dt: (np.inf if np.issubdtype(dt, np.floating)
                           else np.iinfo(dt).max),
        "max": lambda dt: (-np.inf if np.issubdtype(dt, np.floating)
                           else np.iinfo(dt).min)}


def merge_aggregate_parts(node: AggregateNode, parts):
    """Re-aggregate per-batch group rows host-side (the coordinator
    combine over per-batch partials — same split the reference's logical
    optimizer plans, planner/multi_logical_optimizer.c:1419)."""
    cids = ([cid for _g, cid in node.group_keys]
            + [cid for _a, cid in node.aggs])
    cat, catn = {}, {}
    for cid in cids:
        cat[cid] = np.concatenate([p[0][cid] for p in parts])
        catn[cid] = np.concatenate([p[1][cid] for p in parts])
    n = len(next(iter(cat.values()))) if cids else 0
    if n == 0:
        return cat, catn  # typed empties straight through

    key_cols = []
    for _g, cid in node.group_keys:
        v = cat[cid]
        if np.issubdtype(v.dtype, np.floating):
            v = (v.astype(np.float32).view(np.int32)
                 if v.dtype == np.float32 else v.view(np.int64))
        nm = catn[cid]
        key_cols.append(np.where(nm, 0, v.astype(np.int64)))
        key_cols.append(nm.astype(np.int64))
    if key_cols:
        mat = np.stack(key_cols, axis=1)
        _, first, inv = np.unique(mat, axis=0, return_index=True,
                                  return_inverse=True)
        inv = inv.reshape(-1)
        m = len(first)
    else:
        first = np.zeros(1, dtype=np.int64)
        inv = np.zeros(n, dtype=np.int64)
        m = 1

    out_c, out_n = {}, {}
    for _g, cid in node.group_keys:
        out_c[cid] = cat[cid][first]
        out_n[cid] = catn[cid][first]
    for a, cid in node.aggs:
        v, nm = cat[cid], catn[cid]
        if a.kind in ("count", "count_star"):
            acc = np.zeros(m, dtype=v.dtype)
            np.add.at(acc, inv, v)
            out_c[cid] = acc
            out_n[cid] = np.zeros(m, dtype=bool)
            continue
        contrib = ~nm
        if a.kind == "sum":
            acc = np.zeros(m, dtype=v.dtype)
            np.add.at(acc, inv[contrib], v[contrib])
        elif a.kind == "min":
            acc = np.full(m, _BIG["min"](v.dtype), dtype=v.dtype)
            np.minimum.at(acc, inv[contrib], v[contrib])
        else:  # max
            acc = np.full(m, _BIG["max"](v.dtype), dtype=v.dtype)
            np.maximum.at(acc, inv[contrib], v[contrib])
        cnt = np.zeros(m, dtype=np.int64)
        np.add.at(cnt, inv, contrib.astype(np.int64))
        out_c[cid] = acc
        out_n[cid] = cnt == 0
    return out_c, out_n


# ---------------------------------------------------------------------------
# driver

def try_execute_streamed(executor, plan: QueryPlan, raw: bool,
                         return_parts: bool = False,
                         no_cache_nodes=frozenset()):
    """Streamed execution when the plan's feeds exceed the HBM budget;
    None ⇒ caller proceeds on the resident-feed path.

    `return_parts=True` (the multipass driver's mode) skips the final
    host combine and returns (parts, rows_scanned, retries, batches,
    caps) — flattened per-batch column/null dicts the caller merges
    across its own passes before ONE host combine."""
    from ..stats.tracing import trace_span

    settings = executor.settings
    with trace_span("route"):
        budget = settings.get("max_feed_bytes_per_device")
        if budget <= 0:
            return None
        # the accountant may know a REAL ceiling below the configured
        # one (armed MemSim, hbm_budget_bytes, backend bytes_limit):
        # size the stream against it so the statement streams at the
        # true budget up front instead of discovering it through an OOM
        # round-trip
        hw = executor.accountant.budget_bytes(settings)
        if hw:
            budget = min(budget, hw)
        compute_dtype = np.dtype(settings.get("compute_dtype"))
        n_dev = plan.n_devices
        oom = executor.oom
        picked = pick_stream_node(plan, executor.catalog, executor.store,
                                  n_dev, compute_dtype, budget,
                                  settings.get("stream_batch_rows"),
                                  shrink=oom.batch_shrink,
                                  force=oom.force_stream,
                                  prefetch_depth=settings.get(
                                      "scan_prefetch_depth"))
    if picked is None:
        return None
    stream_node, batch_cap = picked

    # scale cardinality estimates along the stream path: downstream
    # buffers size per batch, not per table
    total_rows = sum(
        executor.store.shard_row_count(stream_node.rel.table, s.shard_id)
        for s in executor.catalog.table_shards(stream_node.rel.table))
    frac = min(1.0, (batch_cap * n_dev) / max(1, total_rows))
    _scale_path_estimates(plan, id(stream_node), frac)

    batcher = StreamBatcher(stream_node, executor.catalog, executor.store,
                            executor.mesh, n_dev, compute_dtype, batch_cap,
                            accountant=executor.accountant,
                            stats=executor.scan_stats)
    feeds: dict[int, FeedSpec] = {}
    for node in walk_plan(plan.root):
        if isinstance(node, ScanNode) and node is not stream_node:
            cache = (None if id(node) in no_cache_nodes
                     else executor.feed_cache)
            feeds[id(node)] = _feed_scan_cached(
                node, executor.catalog, executor.store, executor.mesh,
                n_dev, compute_dtype, cache,
                executor.counters, executor.accountant,
                executor.scan_stats)

    # prefetch thread: builds + device_puts the next batch while the mesh
    # chews the current one (scan_prefetch_depth batches in flight —
    # the same knob that bounds the pipelined scan's column prefetch).
    # stop_evt lets a failing consumer unblock the producer's bounded
    # put (a plain put would pin the thread and a device-resident batch
    # forever).
    fetched: queue.Queue = queue.Queue(
        maxsize=max(1, settings.get("scan_prefetch_depth")))
    stop_evt = threading.Event()

    def _put(item) -> bool:
        while not stop_evt.is_set():
            try:
                fetched.put(item, timeout=0.2)
                return True
            except queue.Full:
                continue
        return False

    from ..stats.tracing import adopt_context, capture_context

    trace_ctx = capture_context()

    def producer():
        from ..utils.faultinjection import fault_point

        # the batch producer adopts the statement's trace context so
        # its stream.decode/stream.transfer spans land on their own
        # track of the statement trace (leak-proof: adopt_context
        # force-closes anything left open)
        with adopt_context(trace_ctx):
            try:
                i = 0
                while not stop_evt.is_set():
                    # named seam: a prefetch-thread death mid-stream
                    # must surface as a query error, never a hang or
                    # partial result (VERDICT r3 weak #6)
                    fault_point("stream.prefetch")
                    feed = batcher.feed(i)
                    if feed is None:
                        break
                    if not _put(("ok", feed)):
                        return
                    i += 1
                _put(("done", None))
            except BaseException as e:  # graftlint: ignore[swallowed-base-exception] — not swallowed: forwarded over the queue and re-raised on the consumer thread
                _put(("err", e))

    t = threading.Thread(target=producer, daemon=True)
    t.start()

    topk_sig = (plan.device_topk, tuple(
        (repr(e), d, nf) for e, d, nf in plan.host_order_by)
        if plan.device_topk is not None else ())
    caps = None
    tallies = (0, 0, 0, 0)   # the last batch's program's, as caps is
    fingerprint = None
    fn = out_meta = None
    parts = []
    rows_scanned = 0
    retries_total = 0
    agg_root = (plan.root if isinstance(plan.root, AggregateNode)
                else None)
    n_consumed = 0
    from ..utils.cancellation import check_cancel

    try:
        while True:
            # batch boundaries are the streaming path's cancellation
            # seams: a statement_timeout_ms deadline or Session.cancel()
            # stops between batches (the finally below unwinds the
            # prefetch thread cleanly).  The bounded get keeps the
            # deadline live even when the producer is wedged.
            check_cancel()
            try:
                kind, payload = fetched.get(timeout=0.25)
            except queue.Empty:
                continue
            if kind == "err":
                raise payload
            if kind == "done":
                break
            n_consumed += 1
            feeds[id(stream_node)] = payload
            if caps is None:
                fingerprint = ("stream", batch_cap,
                               node_fingerprint(plan.root), n_dev,
                               str(compute_dtype),
                               feeds_signature(plan, feeds), topk_sig,
                               executor.settings.get("group_by_kernel"))
                memo = executor._caps_memo.get(fingerprint)
                caps = (executor._caps_from_order(plan, memo)
                        if memo is not None
                        else executor._initial_capacities(plan, feeds))
            # no feedback tightening mid-stream: batches share one
            # compiled program, and per-batch actuals vary — tightening
            # on batch 1 would risk a recompile-overflow-regrow cycle
            # on a later, fuller batch
            with trace_span("stream.batch", batch=n_consumed - 1):
                packed, out_meta, caps, r, tallies = \
                    executor.run_with_retry(
                        plan, feeds, caps, fingerprint, compute_dtype,
                        allow_tighten=False)
                retries_total += r
                cols, nulls, valid = unpack_outputs(packed, out_meta)
                rows_scanned += int(np.asarray(valid).size)
                parts.append(_flatten_batch(cols, nulls, valid))
    finally:
        stop_evt.set()
        while True:  # drain so a blocked put wakes immediately
            try:
                fetched.get_nowait()
            except queue.Empty:
                break
        t.join(timeout=5.0)

    if return_parts:
        return (parts, rows_scanned, retries_total, n_consumed, caps,
                tallies)
    if agg_root is not None:
        merged_c, merged_n = merge_aggregate_parts(agg_root, parts)
    else:
        merged_c = {cid: np.concatenate([p[0][cid] for p in parts])
                    for cid in parts[0][0]} if parts else {}
        merged_n = {cid: np.concatenate([p[1][cid] for p in parts])
                    for cid in parts[0][1]} if parts else {}
    n = len(next(iter(merged_c.values()))) if merged_c else 0
    valid = np.ones((1, n), dtype=bool)
    cols = {cid: a.reshape(1, n) for cid, a in merged_c.items()}
    nulls = {cid: a.reshape(1, n) for cid, a in merged_n.items()}
    result = executor._host_combine(plan, cols, nulls, valid, raw)
    result.retries = retries_total
    result.device_rows_scanned = rows_scanned
    result.streamed_batches = n_consumed
    from .runner import feed_device_rows

    rows_in = feed_device_rows(
        {k: v for k, v in feeds.items() if k != id(stream_node)}, n_dev)
    totals = rows_in if rows_in is not None else [0] * n_dev
    for d, r in enumerate(batcher._dev_rows):
        totals[d] += int(r)
    result.device_rows_in = totals
    if executor.counters is not None:
        from ..stats.counters import QUERIES_STREAMED

        executor.counters.increment(QUERIES_STREAMED)
    if caps is not None:
        # once per STATEMENT, after the batch loop (run_with_retry runs
        # per batch and must not inflate the statement-level counter)
        executor.count_picks(plan, caps, tallies)
    return result


def _scale_path_estimates(plan: QueryPlan, stream_id: int,
                          frac: float) -> None:
    """Scale est_rows along root→stream-scan (output cardinality of every
    node containing the streamed batch scales with the batch fraction)."""

    def rec(node: PlanNode) -> bool:
        here = id(node) == stream_id
        kids = []
        if isinstance(node, JoinNode):
            kids = [node.left, node.right]
        elif isinstance(node, (AggregateNode, ProjectNode, WindowNode)):
            kids = [node.input]
        on_path = here or any(rec(k) for k in kids)
        if on_path and getattr(node, "est_rows", None):
            node.est_rows = max(1, int(node.est_rows * frac))
        return on_path

    rec(plan.root)
