"""Execution caches: compiled plans + resident device tables.

The reference amortizes per-query work two ways: cached local plans
(planner/local_plan_cache.c:1-60 keeps prepared shard plans keyed on the
shard interval) and long-lived worker connections/pools reused across
queries (executor/adaptive_executor.c:962).  The TPU-native analogues:

* **Plan cache** — the jitted XLA program for a plan shape is cached keyed
  on a deterministic structural fingerprint (plan tree + expressions +
  static capacities + feed array signature + dtype).  A repeated or
  parameterized-with-same-shape query skips trace + compile entirely.

* **Feed cache** — per-table device-resident column arrays ([n_dev, cap]
  padded, mesh-sharded) keyed on (table, columns, pruning, placement,
  data version).  Re-running a query re-uses HBM-resident arrays instead
  of re-reading stripes, decompressing, padding, and device_put-ing.
  Invalidation: TableStore bumps a per-table data version on every
  manifest mutation (the CitusTableCacheEntry invalidation analogue,
  metadata/metadata_cache.c:287).

Both caches are LRU-bounded (plans by entry count, feeds by device bytes).
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass, field

from ..planner.plan import (
    AggregateNode,
    JoinNode,
    PlanNode,
    ProjectNode,
    QueryPlan,
    ScanNode,
    WindowNode,
)


def _dist_sig(dist) -> str:
    return (f"{dist.kind}:{sorted(dist.cids)}:{dist.shard_count}:"
            f"{dist.placement}:{dist.bounds}")


def node_fingerprint(node: PlanNode) -> str:
    """Deterministic structural serialization of a plan subtree.

    Covers everything PlanCompiler bakes into the traced program:
    expression trees (constants included — they become XLA literals),
    join strategies, aggregate modes, and distribution descriptors.
    Frozen-dataclass reprs contain only field values, so the string is
    stable across processes.
    """
    if isinstance(node, ScanNode):
        return (f"S({node.rel.rel_index};{node.rel.identity};{node.columns};"
                f"{node.pruned_shards};{node.filter!r};"
                f"{_dist_sig(node.dist)})")
    if isinstance(node, ProjectNode):
        exprs = [(repr(e), cid) for e, cid in node.exprs]
        return f"P({node_fingerprint(node.input)};{exprs})"
    if isinstance(node, JoinNode):
        return (f"J({node.strategy};{node.join_type};{node.repart_key_idx};"
                f"{node.build_side};{node.left_key_extents};"
                f"{node.right_key_extents};{node.key_int32};"
                f"{node.fuse_lookup};{node.lookup_sorted};"
                f"{node.flag_combine};{node.key_pack};"
                f"{node_fingerprint(node.left)};"
                f"{node_fingerprint(node.right)};"
                f"{[repr(k) for k in node.left_keys]};"
                f"{[repr(k) for k in node.right_keys]};"
                f"{node.residual!r};{node.left_match_filter!r};"
                f"{node.right_match_filter!r};{_dist_sig(node.dist)})")
    if isinstance(node, WindowNode):
        fns = [(repr(w), cid) for w, cid in node.functions]
        return (f"W({node.combine};{fns};"
                f"{[repr(p) for p in node.partition_by]};{node.having!r};"
                f"{node.presorted};"
                f"{node_fingerprint(node.input)};{_dist_sig(node.dist)})")
    if isinstance(node, AggregateNode):
        groups = [(repr(g), cid) for g, cid in node.group_keys]
        aggs = [(repr(a), cid) for a, cid in node.aggs]
        return (f"A({node.combine};{node.repart_keys};"
                f"{node_fingerprint(node.input)};"
                f"{groups};{aggs};{node.dense_keys};{node.dense_total};"
                f"{node.key_ranges};{node.bucket_keys};"
                f"{node.bucket_total};"
                f"{node.nulls_last};{_dist_sig(node.dist)})")
    raise TypeError(f"unknown plan node {type(node).__name__}")


def plan_order(plan: QueryPlan) -> dict[int, int]:
    """id(node) → deterministic plan-walk index (for serializing the
    id-keyed Capacities dicts into cache keys)."""
    from .feed import walk_plan

    return {id(n): i for i, n in enumerate(walk_plan(plan.root))}


def caps_signature(plan: QueryPlan, caps) -> tuple:
    order = plan_order(plan)
    return (tuple(sorted((order[k], v) for k, v in caps.repartition.items())),
            tuple(sorted((order[k], v) for k, v in caps.join_out.items())),
            tuple(sorted((order[k], v) for k, v in caps.agg_out.items())),
            caps.dense_off,
            tuple(sorted((order[k], v) for k, v in caps.scan_out.items())),
            caps.output_repart,
            tuple(sorted((order[k], v)
                         for k, v in caps.join_range.items())))


def feeds_signature(plan: QueryPlan, feeds) -> tuple:
    """Feed array structure in deterministic plan order: what the jitted
    function's input signature depends on (shapes, dtypes, null columns)."""
    from .feed import walk_plan

    sig = []
    for node in walk_plan(plan.root):
        if isinstance(node, ScanNode):
            f = feeds[id(node)]
            sig.append((
                f.sharded, f.capacity,
                tuple((cid, str(f.arrays[cid].dtype), f.arrays[cid].shape)
                      for cid in sorted(f.arrays)),
                tuple(sorted(f.nulls)),
            ))
    return tuple(sig)


class PlanCache:
    """LRU cache of jitted executables keyed by plan fingerprint.

    Thread-safe: concurrent sessions threads race get/put (two threads
    compiling the same new plan is wasted work, never wrong results)."""

    def __init__(self, max_entries: int = 256):
        self.max_entries = max_entries
        self._entries: OrderedDict[tuple, object] = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def get(self, key: tuple):
        with self._lock:
            fn = self._entries.get(key)
            if fn is not None:
                self._entries.move_to_end(key)
                self.hits += 1
            else:
                self.misses += 1
            return fn

    def put(self, key: tuple, fn) -> None:
        if self.max_entries <= 0:
            return
        with self._lock:
            self._entries[key] = fn
            self._entries.move_to_end(key)
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def __len__(self):
        return len(self._entries)


@dataclass
class CachedFeed:
    """Device-resident arrays for one (table, columns, pruning) scan."""

    sharded: bool
    arrays: dict          # cid → jax.Array on mesh
    nulls: dict
    valid: object
    capacity: int
    nbytes: int = 0
    dev_rows: list | None = None  # per-device row counts (Mesh: line)


class FeedCache:
    """LRU byte-bounded cache of device-resident table feeds.

    Thread-safe; an evicted entry's arrays stay alive for any thread
    already holding them (jax arrays are reference-counted)."""

    def __init__(self, max_bytes: int = 4 << 30):
        self.max_bytes = max_bytes
        self._entries: OrderedDict[tuple, CachedFeed] = OrderedDict()
        # per-table key index (key layout: (table, version, ...)):
        # every DML bumps the written table's data version and calls
        # invalidate_table — scanning the WHOLE entry dict under the
        # lock on each write serialized concurrent small writers behind
        # reader traffic for nothing
        self._by_table: dict[str, set] = {}
        self._lock = threading.Lock()
        self._total_bytes = 0
        self.hits = 0
        self.misses = 0
        self.invalidations = 0

    def get(self, key: tuple) -> CachedFeed | None:
        with self._lock:
            e = self._entries.get(key)
            if e is not None:
                self._entries.move_to_end(key)
                self.hits += 1
            else:
                self.misses += 1
            return e

    def _pop_locked(self, key: tuple) -> None:
        self._total_bytes -= self._entries.pop(key).nbytes
        keys = self._by_table.get(key[0])
        if keys is not None:
            keys.discard(key)
            if not keys:
                del self._by_table[key[0]]

    def put(self, key: tuple, feed: CachedFeed) -> None:
        if self.max_bytes <= 0:
            return
        with self._lock:
            if key in self._entries:
                self._pop_locked(key)
            self._entries[key] = feed
            self._by_table.setdefault(key[0], set()).add(key)
            self._total_bytes += feed.nbytes
            while self._total_bytes > self.max_bytes \
                    and len(self._entries) > 1:
                self._pop_locked(next(iter(self._entries)))

    def invalidate_table(self, table: str, keep_version: int | None = None
                         ) -> None:
        """Drop entries for `table` via the per-table key index (no
        full-cache scan); keep_version spares the current version's
        entries."""
        with self._lock:
            keys = self._by_table.get(table)
            if not keys:
                return
            stale = [k for k in keys if k[1] != keep_version]
            for k in stale:
                self._pop_locked(k)
            self.invalidations += len(stale)

    def evict_coldest(self, target_bytes: int | None = None) -> int:
        """Evict entries in LRU (coldest-first) order until
        `target_bytes` have been freed — everything when None.  The OOM
        degradation ladder's first rung (executor.Executor.
        degrade_for_oom): the arrays' device memory is reclaimed as
        soon as no in-flight statement still references them.  Returns
        entries evicted."""
        with self._lock:
            evicted = 0
            freed = 0
            while self._entries and (target_bytes is None
                                     or freed < target_bytes):
                key = next(iter(self._entries))
                freed += self._entries[key].nbytes
                self._pop_locked(key)
                evicted += 1
            return evicted

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._by_table.clear()
            self._total_bytes = 0

    @property
    def total_bytes(self) -> int:
        return self._total_bytes

    def __len__(self):
        return len(self._entries)
