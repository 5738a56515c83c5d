"""Host→device data feed: shard stripes → padded mesh-sharded arrays.

Replaces the reference's per-tuple worker scan + COPY result streaming with
bulk columnar placement: each device's rows are the concatenation of its
shards' stripes (colocation-preserving), padded to a common static
capacity, laid out as [n_devices, capacity] and device_put with a
NamedSharding over the 'shards' mesh axis.  Reference tables feed as
replicated [capacity] arrays.

Shard pruning (ScanNode.pruned_shards) skips entire shards at feed time —
the PruneShards analogue executed host-side.
"""

from __future__ import annotations

import math

import numpy as np
from jax.sharding import Mesh

from ..catalog import Catalog, DistributionMethod
from ..catalog.catalog import is_intermediate
from ..errors import ExecutionError
from ..planner.plan import (
    AggregateNode,
    JoinNode,
    PlanNode,
    ProjectNode,
    QueryPlan,
    ScanNode,
    WindowNode,
)
from ..stats.counters import (
    FEED_CACHE_HIT_BYTES_TOTAL,
    FEED_CACHE_MISS_BYTES_TOTAL,
)
from ..storage import TableStore
from .compiler import FeedSpec, _round_cap


def walk_plan(node: PlanNode):
    yield node
    if isinstance(node, JoinNode):
        yield from walk_plan(node.left)
        yield from walk_plan(node.right)
    elif isinstance(node, (AggregateNode, ProjectNode, WindowNode)):
        yield from walk_plan(node.input)


def build_feeds(plan: QueryPlan, catalog: Catalog, store: TableStore,
                mesh: Mesh, compute_dtype=np.float32,
                cache=None, counters=None, accountant=None,
                no_cache_nodes=frozenset(), stats=None
                ) -> dict[int, FeedSpec]:
    """`no_cache_nodes`: node ids whose feeds bypass the device cache —
    the multipass driver's per-pass split feeds must NOT pin every
    pass's partition resident at once (that would defeat the pass)."""
    feeds: dict[int, FeedSpec] = {}
    for node in walk_plan(plan.root):
        if isinstance(node, ScanNode):
            node_cache = None if id(node) in no_cache_nodes else cache
            feeds[id(node)] = _feed_scan_cached(node, catalog, store, mesh,
                                                plan.n_devices, compute_dtype,
                                                node_cache, counters,
                                                accountant, stats)
    return feeds


def skippable_tests(filter_expr) -> tuple:
    """Canonical (col, op, value) skip tests from a scan filter — also the
    feed-cache key component (feeds built under different chunk filters
    hold different rows and must not share a cache slot)."""
    from ..planner import expr as ir

    if filter_expr is None:
        return ()
    _FLIP = {"<": ">", "<=": ">=", ">": "<", ">=": "<=", "=": "="}
    # BParam carries its bound value: chunk skipping is host-side per
    # execution, so generic plans keep min/max pruning (and the feed
    # cache keys on the VALUE, as it must — different values read
    # different chunks)
    const_types = (ir.BConst, ir.BParam)
    tests: list[tuple[str, str, object]] = []
    for c in ir.split_conjuncts(filter_expr):
        if isinstance(c, ir.BCmp) and c.op in _FLIP:
            if isinstance(c.left, ir.BCol) \
                    and isinstance(c.right, const_types) \
                    and c.right.value is not None:
                tests.append((c.left.cid.split(".", 1)[1], c.op,
                              c.right.value))
            elif isinstance(c.right, ir.BCol) and \
                    isinstance(c.left, const_types) \
                    and c.left.value is not None:
                tests.append((c.right.cid.split(".", 1)[1], _FLIP[c.op],
                              c.left.value))
        elif isinstance(c, ir.BInConst) and not c.negated and \
                isinstance(c.operand, ir.BCol) and c.values:
            tests.append((c.operand.cid.split(".", 1)[1], "in",
                          tuple(c.values)))
    return tuple(sorted(tests, key=repr))


def make_chunk_filter(filter_expr, counters=None, storage_name=None):
    """ScanNode filter → per-chunk min/max skip predicate.

    The chunk-granularity PruneShards analogue (reference:
    columnar_reader.c:323 chunk-group filtering over ColumnChunkSkipNode
    min/max).  Handles AND-ed `col <op> const` comparisons and positive
    IN-lists (string predicates arrive as dictionary-code IN-lists from
    the binder); any unsatisfiable conjunct skips the whole chunk.
    Returns None when the filter has no skippable shape.

    `storage_name` maps current → on-disk column names: stripe stats are
    keyed by storage names, which diverge after ALTER TABLE RENAME.
    """
    tests = skippable_tests(filter_expr)
    if not tests:
        return None
    if storage_name:
        tests = tuple((storage_name.get(col, col), op, val)
                      for col, op, val in tests)

    def chunk_filter(stats: dict) -> bool:
        for col, op, val in tests:
            s = stats.get(col)
            if s is None:
                continue
            mn, mx, _nulls = s
            if mn is None:
                # no stats for this column (e.g. dictionary-coded strings
                # in older stripes) — cannot conclude anything
                continue
            ok = ((op == "<" and mn < val) or (op == "<=" and mn <= val)
                  or (op == ">" and mx > val) or (op == ">=" and mx >= val)
                  or (op == "=" and mn <= val <= mx)
                  or (op == "in" and any(mn <= v <= mx for v in val)))
            if not ok:
                _count_skip(counters)
                return False
        return True

    return chunk_filter


def _count_skip(counters) -> None:
    if counters is not None:
        from ..stats.counters import CHUNKS_SKIPPED

        counters.increment(CHUNKS_SKIPPED)


def _overlay_touches(store: TableStore, table: str) -> bool:
    ov = store.overlay
    if ov is None:
        return False
    return (any(t == table for t, _ in ov.records)
            or any(t == table for t, _, _ in ov.deletes))


def _feed_scan_cached(node: ScanNode, catalog: Catalog, store: TableStore,
                      mesh: Mesh, n_dev: int, compute_dtype,
                      cache, counters=None, accountant=None,
                      stats=None) -> FeedSpec:
    """Device-feed cache wrapper: HBM-resident table arrays keyed on
    (table, columns, pruning, placement, data version) — see
    executor/cache.py.  Open-transaction overlays bypass the cache (their
    visibility is session-private and changes mid-transaction)."""
    table = node.rel.table
    if cache is None or _overlay_touches(store, table):
        return _feed_scan(node, catalog, store, mesh, n_dev, compute_dtype,
                          counters, accountant, category="feed",
                          stats=stats)
    shards = catalog.table_shards(table)
    placement_sig = tuple(
        (s.shard_id, catalog.active_placement(s.shard_id).node_id)
        for s in shards)
    # skip-filter fingerprint under STORAGE column names — the names the
    # chunk filter actually tests stripe stats against.  Keying on the
    # current names would let two filters that alias through a rename
    # share one skip-pruned (possibly prefetched) feed; the mapped
    # fingerprint makes cacheability a function of what was READ
    skip_fp = tuple(
        (store.storage_column_name(table, col), op, val)
        for col, op, val in skippable_tests(node.filter))
    key = (table, store.data_version(table), tuple(node.columns),
           None if node.pruned_shards is None else tuple(node.pruned_shards),
           n_dev, str(np.dtype(compute_dtype)), placement_sig,
           skip_fp)
    entry = cache.get(key)
    if entry is None:
        # superseded versions of this table can never hit again — free
        # their HBM before resident-caching the fresh feed
        cache.invalidate_table(table, keep_version=key[1])
        # accounted as "cache" from the start: the arrays become
        # cache-resident below, and cache bytes are the evictable
        # class the ladder/admission pressure treats as reclaimable
        spec = _feed_scan(node, catalog, store, mesh, n_dev, compute_dtype,
                          counters, accountant, category="cache",
                          stats=stats)
        from .cache import CachedFeed

        nbytes = sum(int(np.dtype(a.dtype).itemsize * a.size)
                     for a in list(spec.arrays.values())
                     + list(spec.nulls.values()) + [spec.valid])
        entry = CachedFeed(sharded=spec.sharded, arrays=spec.arrays,
                           nulls=spec.nulls, valid=spec.valid,
                           capacity=spec.capacity, nbytes=nbytes,
                           dev_rows=spec.dev_rows)
        cache.put(key, entry)
        if counters is not None:
            counters.increment(FEED_CACHE_MISS_BYTES_TOTAL, nbytes)
        return spec
    if counters is not None:
        counters.increment(FEED_CACHE_HIT_BYTES_TOTAL, entry.nbytes)
    return FeedSpec(node=node, sharded=entry.sharded, arrays=entry.arrays,
                    nulls=entry.nulls, valid=entry.valid,
                    capacity=entry.capacity, dev_rows=entry.dev_rows)


def _feed_scan(node: ScanNode, catalog: Catalog, store: TableStore,
               mesh: Mesh, n_dev: int, compute_dtype,
               counters=None, accountant=None,
               category: str = "feed", stats=None) -> FeedSpec:
    if is_intermediate(node.rel.table):
        # a subplan's rows, which the store holds in memory as typed
        # arrays (TableStore.hold_resident): there is no stripe to
        # prefetch or decode, so the reference-table branch below reads
        # them as they are
        from ..stats.tracing import trace_span

        with trace_span("subplan.feed"):
            return _feed_eager(node, catalog, store, mesh, n_dev,
                               compute_dtype, counters, accountant,
                               category)
    # pipelined path first (executor/scanpipe.py): prefetch + decode on
    # a producer thread overlapped with accounted placement, optional
    # on-device decode.  None ⇒ ineligible (scan_pipeline off, tiny
    # table under 'auto', open overlay) or shed after a prefetch OOM —
    # the eager path below is both the fallback and the reference
    # semantics the fuzzer parity slice pins the pipeline to.
    from .scanpipe import maybe_pipelined_feed

    pipelined = maybe_pipelined_feed(node, catalog, store, mesh, n_dev,
                                     compute_dtype, counters, accountant,
                                     category, stats)
    if pipelined is not None:
        return pipelined
    return _feed_eager(node, catalog, store, mesh, n_dev, compute_dtype,
                       counters, accountant, category)


def _feed_eager(node: ScanNode, catalog: Catalog, store: TableStore,
                mesh: Mesh, n_dev: int, compute_dtype,
                counters=None, accountant=None,
                category: str = "feed") -> FeedSpec:
    rel = node.rel
    meta = catalog.table(rel.table)
    colnames = [cid.split(".", 1)[1] for cid in node.columns]
    shards = catalog.table_shards(rel.table)
    chunk_filter = None
    if node.filter is not None:
        name_map = {c.name: store.storage_column_name(rel.table, c.name)
                    for c in meta.schema.columns}
        chunk_filter = make_chunk_filter(node.filter, counters, name_map)

    if meta.method == DistributionMethod.HASH:
        # device-owned assembly: each device's slice is built from ONLY
        # the shards the catalog's node↔device map assigns it, as an
        # independent [cap] buffer — never one [n_dev, cap] host concat.
        # Placement below transfers the slices individually, so an
        # N-device mesh absorbs N dispatches in parallel.
        per_dev_vals: list[dict[str, list[np.ndarray]]] = [
            {c: [] for c in colnames} for _ in range(n_dev)]
        per_dev_mask: list[dict[str, list[np.ndarray]]] = [
            {c: [] for c in colnames} for _ in range(n_dev)]
        per_dev_rows = [0] * n_dev
        from ..planner.plan import table_placement

        placement = table_placement(catalog, rel.table, n_dev)
        for s, dev in zip(shards, placement):
            if node.pruned_shards is not None and \
                    s.shard_index not in node.pruned_shards:
                continue
            vals, mask, n = store.read_shard(rel.table, s.shard_id, colnames,
                                             chunk_filter)
            if n == 0:
                continue
            per_dev_rows[dev] += n
            for c in colnames:
                per_dev_vals[dev][c].append(vals[c])
                per_dev_mask[dev][c].append(mask[c])
        cap = _round_cap(max(per_dev_rows) if any(per_dev_rows) else 1)
        arrays, nulls = {}, {}
        for cid, cname in zip(node.columns, colnames):
            dtype = rel.schema.column(cname).dtype.numpy_dtype
            if dtype == np.float64 and compute_dtype is not None:
                dtype = np.dtype(compute_dtype)
            slices = []
            nslices = []
            has_nulls = False
            for d in range(n_dev):
                sl = np.zeros(cap, dtype=dtype)
                nsl = np.zeros(cap, dtype=bool)
                if per_dev_vals[d][cname]:
                    v = np.concatenate(per_dev_vals[d][cname]).astype(dtype)
                    m = np.concatenate(per_dev_mask[d][cname])
                    sl[:len(v)] = v
                    if not m.all():
                        has_nulls = True
                        nsl[:len(m)] = ~m
                slices.append(sl)
                nslices.append(nsl)
            arrays[cid] = slices
            if has_nulls:
                nulls[cid] = nslices
        valid = []
        for d in range(n_dev):
            vsl = np.zeros(cap, dtype=bool)
            vsl[:per_dev_rows[d]] = True
            valid.append(vsl)
        feed = FeedSpec(node=node, sharded=True, arrays=arrays, nulls=nulls,
                        valid=valid, capacity=cap,
                        dev_rows=list(per_dev_rows))
    else:
        # reference/local: single shard replicated to every device
        if len(shards) != 1:
            raise ExecutionError(
                f"table {rel.table}: expected single shard")
        vals, mask, n = store.read_shard(rel.table, shards[0].shard_id,
                                         colnames, chunk_filter)
        cap = _round_cap(max(n, 1))
        arrays, nulls = {}, {}
        for cid, cname in zip(node.columns, colnames):
            dtype = rel.schema.column(cname).dtype.numpy_dtype
            if dtype == np.float64 and compute_dtype is not None:
                dtype = np.dtype(compute_dtype)
            buf = np.zeros(cap, dtype=dtype)
            if n:
                buf[:n] = vals[cname].astype(dtype)
                if not mask[cname].all():
                    nbuf = np.zeros(cap, dtype=bool)
                    nbuf[:n] = ~mask[cname]
                    nulls[cid] = nbuf
            arrays[cid] = buf
        valid = np.zeros(cap, dtype=bool)
        valid[:n] = True
        feed = FeedSpec(node=node, sharded=False, arrays=arrays, nulls=nulls,
                        valid=valid, capacity=cap)

    # place on the mesh through the ONE accounted seam (executor/hbm.py)
    from ..utils.faultinjection import fault_point
    from .hbm import accountant_for

    # named seam: a host→HBM transfer failure (device OOM, remote-
    # attached link drop) must surface as a retryable statement error,
    # never a partially placed feed
    fault_point("executor.device_put")
    acc = accountant_for(store.data_dir) if accountant is None \
        else accountant

    def put(a):
        # sharded feeds arrive as per-device slice lists (device-owned
        # path: independent per-device transfers through the slice
        # seam, charged per device); replicated feeds as one host array
        if feed.sharded:
            return acc.place_sharded_slices(mesh, a, category)
        return acc.place(mesh, a, False, category)

    feed.arrays = {c: put(a) for c, a in feed.arrays.items()}
    feed.nulls = {c: put(a) for c, a in feed.nulls.items()}
    feed.valid = put(feed.valid)
    return feed
