"""Cluster stat counters — citus_stat_counters analogue.

The reference keeps lock-free per-backend counter slots in shared memory,
aggregated into a per-database hash when backends exit
(/root/reference/src/backend/distributed/stats/stat_counters.c, README
§"stat counters").  Here the slot design maps to threads: each thread
increments its private slot without locking; snapshots sum across slots.
Slots are kept for the registry's lifetime (sessions are not expected to
churn thousands of threads).
"""

from __future__ import annotations

import threading
from collections import defaultdict

# counter names (the reference's are connection/query-execution oriented;
# ours mirror the TPU execution paths)
QUERIES_SINGLE_SHARD = "queries_single_shard"
QUERIES_MULTI_SHARD = "queries_multi_shard"
QUERIES_REPARTITION = "queries_repartition"
QUERIES_FAST_PATH = "queries_fast_path"
POINT_INDEX_LOOKUPS = "point_index_lookups"
SUBPLANS_EXECUTED = "subplans_executed"
# what recursive planning stored as temp reference tables for the outer
# statement to scan (session._store_result): rows, and bytes of their
# typed columns and validity masks
INTERMEDIATE_ROWS_TOTAL = "intermediate_rows_total"
INTERMEDIATE_BYTES_TOTAL = "intermediate_bytes_total"
# … and the results handed to the outer statement's feed in memory
# (storage/table_store.py hold_resident; one of zero rows counts too)
INTERMEDIATE_RESIDENT_TOTAL = "intermediate_resident_total"
# … and the string values of those results typed and interned into a
# temp table's dictionaries on the host (rows times string columns)
INTERMEDIATE_STRING_VALUES_TOTAL = "intermediate_string_values_total"
# string predicates (LIKE, IN, BETWEEN, <) resolved to dictionary codes
# at bind time by walking a dictionary's values (planner/bind.py
# _codes_where): walks that visited any value, and the values visited.
# A dictionary keeps each predicate's codes, so both stay at 0 for a
# statement bound again over dictionaries that have not grown
DICT_PREDICATE_WALKS_TOTAL = "dict_predicate_walks_total"
DICT_PREDICATE_VALUES_TOTAL = "dict_predicate_values_total"
# rows the executed statements' converged programs sent through their
# repartition exchanges, over the mesh, and rows of the fullest
# (source device, target device) bucket of each exchange: hot × buckets
# / rows is the imbalance the static per-bucket capacity has to absorb
REPARTITION_ROWS_TOTAL = "repartition_rows_total"
REPARTITION_HOT_BUCKET_ROWS_TOTAL = "repartition_hot_bucket_rows_total"
ROWS_INGESTED = "rows_ingested"
ROWS_RETURNED = "rows_returned"
DML_UPDATE = "dml_update_count"
DML_DELETE = "dml_delete_count"
DML_MERGE = "dml_merge_count"
DDL_COMMANDS = "ddl_commands"
CAPACITY_RETRIES = "capacity_retries"
DEVICE_ROWS_SCANNED = "device_rows_scanned"
INSERT_SELECT_PUSHDOWN = "insert_select_pushdown"
INSERT_SELECT_REPARTITION = "insert_select_repartition"
INSERT_SELECT_PULL = "insert_select_pull"
CHUNKS_SKIPPED = "chunks_skipped"
QUERIES_STREAMED = "queries_streamed"
# pipelined columnar scan (executor/scanpipe.py): chunk groups decoded
# ahead by the prefetch producer, consumer waits on an empty prefetch
# queue (pipeline underruns), bytes expanded by on-device decode
CHUNKS_PREFETCHED_TOTAL = "chunks_prefetched_total"
PREFETCH_STALLS_TOTAL = "prefetch_stalls_total"
DEVICE_DECODED_BYTES_TOTAL = "device_decoded_bytes_total"
# statements whose plan executed the bucketed dense-grid group-by
# (ops/groupby.py) instead of the sort path
GROUPBY_BUCKETED_TOTAL = "groupby_bucketed_total"
# statements dispatched with at least one fused lookup join on the
# sort-and-scan arm (ops.join.sorted_unique_lookup: a key extent past
# the knee of the directory gather)
LOOKUP_SORTED_TOTAL = "lookup_sorted_total"
# … and on the other arm, the gather from a dense directory
# (ops.join.dense_unique_lookup: a key extent under that)
LOOKUP_DENSE_TOTAL = "lookup_dense_total"
# the same two picks counted by JOIN: fused lookup joins of the executed
# statements' converged plans on each arm (a star join runs several, and
# may mix the arms in one fragment)
LOOKUP_SORTED_JOINS_TOTAL = "lookup_sorted_joins_total"
LOOKUP_DENSE_JOINS_TOTAL = "lookup_dense_joins_total"
# probe slots of those fused lookup joins: the sum, over the executed
# statements' converged programs, of the probe side's static size at
# each fused lookup, over the mesh — what every later probe, gather and
# sort of a star join is sized by, so what the join order decides
LOOKUP_PROBE_SLOTS_TOTAL = "lookup_probe_slots_total"
# packed slots of the bucketed group-bys: the sum, over the executed
# statements' converged programs, of the chunked pack's static size
# (ops.groupby.group_pack_shape: NC x C) at each bucketed group-by,
# over the mesh — what the pack's layout decides
AGG_BUCKET_SLOTS_TOTAL = "agg_bucket_slots_total"
# static slots of the window operators: the sum, over the executed
# statements' converged programs, of each window operator's input size
# over the mesh (what its lexsort and segmented scans run over) …
WINDOW_ROWS_TOTAL = "window_rows_total"
# … and the window operators of those plans that run over the groups
# of an aggregate (a window over GROUP BY: after it and its HAVING, or
# stacked over such a window, one a PARTITION BY clause) …
WINDOW_OVER_GROUP_TOTAL = "window_over_group_total"
# … the lexsorts of validity, partition and order keys those operators
# ran (executor/compiler.py _window_sort: one a window ORDER BY spec) …
WINDOW_SORTS_TOTAL = "window_sorts_total"
# … and the ORDER BY specs they read in the groups' own order instead,
# with no sort (WindowNode.presorted)
WINDOW_PRESORTED_TOTAL = "window_presorted_total"
# the operands of the build-side sorts of the executed statements'
# converged programs' multi-key joins: one a sort of the keys packed
# into one int64 with the row's position (the planner knew every key's
# range), else the keys and the validity of a lexsort
JOIN_SORT_OPERANDS_TOTAL = "join_sort_operands_total"
# the index slots of every gather the executed statements' converged
# programs' sort-path group-bys made (ops/aggregate.py
# segment_aggregate: the values in sorted order at the input's width,
# each group's first row, keys and reductions at the group capacity),
# summed over both levels of a repartition and over the mesh
AGG_SORT_GATHER_SLOTS_TOTAL = "agg_sort_gather_slots_total"
# the fused inner lookups of the executed statements' converged programs
# whose probe side compacts to the rows inside the build keys' range
# before the probe (executor/compiler.py _range_compact: capacity
# feedback installed a capacity there, where the range keeps few
# enough rows for the compaction to pay)
LOOKUP_RANGE_COMPACTIONS_TOTAL = "lookup_range_compactions_total"
# broadcast joins (a replicated side — a reference table — joined in
# place on every device) in the executed statements' converged plans
BROADCAST_JOINS_TOTAL = "broadcast_joins_total"
# columns (a null mask counting as one) the executed statements'
# programs carried across a compaction or a lookup as a row index,
# without a gather at that step (executor/batch.py Block.take) …
DEFERRED_COLUMNS_TOTAL = "deferred_columns_total"
# … and the gathers those programs issued for them later: values, null
# masks and index compositions.  The difference is what deferring saved
DEFERRED_GATHERS_TOTAL = "deferred_gathers_total"
# … the sum of those gathers' index sizes over the mesh: the slots they
# read, what the order of an index chain's composition decides …
DEFERRED_GATHER_SLOTS_TOTAL = "deferred_gather_slots_total"
# … the chains of three or more row indexes composed from the top
# (executor/batch.py _RowIndex.get: each gather at the newest, narrowest
# index's size) …
DEFERRED_TOPDOWN_CHAINS_TOTAL = "deferred_topdown_chains_total"
# … and the times a middle index such a chain gathered through was
# gathered through again: by another chain, or composed at its own size
# by a later read of its own group.  What composing from the top cost
DEFERRED_TOPDOWN_RECOMPOSED_TOTAL = "deferred_topdown_recomposed_total"
# device bytes of the scan feeds the executed statements were served
# from the feed cache (executor/cache.py FeedCache: resident between
# statements) and of those they had to build — read, decode, place —
# because the cache held none (executor/feed.py _feed_scan_cached)
FEED_CACHE_HIT_BYTES_TOTAL = "feed_cache_hit_bytes_total"
FEED_CACHE_MISS_BYTES_TOTAL = "feed_cache_miss_bytes_total"
# static all_to_all shuffle buffer volume the executed plans moved over
# the mesh (per-device capacity × devices² × row width, summed over the
# plan's repartition stages and every stream batch) — the EXPLAIN
# ANALYZE Mesh: line and bench_multichip.py read the per-statement
# delta to show what cross-device scaling actually costs
SHUFFLE_BYTES_TOTAL = "shuffle_bytes_total"
# bytes every execution pulled back from the mesh: the packed output
# block ([2 x columns + 1, devices x capacity] 64-bit words: each
# column, each null mask and the valid mask) and the overflow block,
# as the host received them (executor/runner.py, beside the `settle`
# verdict; a capacity retry's execution counts too)
FETCH_BYTES_TOTAL = "fetch_bytes_total"
# collections of generation 1 or 2 that ran on a thread while a
# statement of this session was open on it, and the microseconds they
# took (stats/tracing.py _gc_hook; a traced statement also records a
# `gc.pause` span).  Generation 0 and collections between statements
# are not counted: gc.get_stats() has the process's totals
GC_PAUSES_TOTAL = "gc_pauses_total"
GC_PAUSE_US_TOTAL = "gc_pause_us_total"
# resilient statement execution (session retry loop / deadline seams)
RETRIES_TOTAL = "retries_total"
FAILOVERS_TOTAL = "failovers_total"
TIMEOUTS_TOTAL = "timeouts_total"
QUERIES_CANCELED = "queries_canceled"
FAULTS_INJECTED_TOTAL = "faults_injected_total"
# mesh fault tolerance (session mesh-degrade path): devices observed
# lost, successful shrink-and-failover passes, and statements that
# ultimately ANSWERED because a failover rescued them (the
# kill-to-first-answer numerator bench_multichip's device_loss
# scenario publishes)
DEVICE_LOST_TOTAL = "device_lost_total"
MESH_FAILOVERS_TOTAL = "mesh_failovers_total"
QUERIES_RESCUED_TOTAL = "queries_rescued_total"
# workload manager (wlm/manager.py admission gate)
WLM_ADMITTED_TOTAL = "wlm_admitted_total"
WLM_QUEUED_TOTAL = "wlm_queued_total"
WLM_SHED_TOTAL = "wlm_shed_total"
WLM_QUEUE_WAIT_MS = "wlm_queue_wait_ms"
# serving layer (serving/ — cross-session micro-batcher + CDC-
# invalidated result cache; requester-side folds, the shared-layer
# totals live on the batcher/cache and surface via citus_stat_serving)
SERVING_BATCHED_LOOKUPS_TOTAL = "serving_batched_lookups_total"
SERVING_BATCH_DISPATCH_TOTAL = "serving_batch_dispatch_total"
SERVING_CACHE_HITS_TOTAL = "serving_cache_hits_total"
SERVING_CACHE_MISSES_TOTAL = "serving_cache_misses_total"
SERVING_CACHE_INVALIDATIONS_TOTAL = "serving_cache_invalidations_total"
# persistent executable cache + single-flight compile dedup + warm-
# before-admit (executor/execcache.py): disk adoptions vs cold misses
# vs detected-rot rejects, compiles saved by following another
# session's in-flight compile, and executables pre-adopted by the
# warmup phase before admission opened
EXEC_CACHE_HITS_TOTAL = "exec_cache_hits_total"
EXEC_CACHE_MISSES_TOTAL = "exec_cache_misses_total"
EXEC_CACHE_REJECTS_TOTAL = "exec_cache_rejects_total"
COMPILES_DEDUPED_TOTAL = "compiles_deduped_total"
WARMUP_COMPILES_TOTAL = "warmup_compiles_total"
# device-memory governance (executor/hbm.py accountant + the OOM
# degradation ladder in executor/runner.py degrade_for_oom)
OOM_EVENTS_TOTAL = "oom_events_total"
CACHE_EVICTIONS_TOTAL = "cache_evictions_total"
STREAM_BATCH_SHRINKS_TOTAL = "stream_batch_shrinks_total"
SPILL_PASSES_TOTAL = "spill_passes_total"
# storage integrity (storage/integrity.py read-path accounting folded
# in per statement; scrub counters from operations/scrubber.py)
# replication (replication/ — CDC log shipping leader→followers):
# batches staged by ship() / rolled in by apply_pending(), followers
# promoted to leader, zombie-leader ships rejected by epoch fencing,
# and the follower staleness gate's cumulative observed lag in lsns
# (the wlm_queue_wait_ms idiom: a lag-sum sample per staleness check —
# divide by checks for an average; the live per-follower lag is
# citus_stat_replication's column)
LOG_BATCHES_SHIPPED_TOTAL = "log_batches_shipped_total"
LOG_BATCHES_APPLIED_TOTAL = "log_batches_applied_total"
REPLICAS_PROMOTED_TOTAL = "replicas_promoted_total"
REPLICATION_FENCED_TOTAL = "replication_fenced_total"
REPLICA_LAG_LSN = "replica_lag_lsn"
STRIPES_VERIFIED_TOTAL = "stripes_verified_total"
CORRUPTION_DETECTED_TOTAL = "corruption_detected_total"
READ_REPAIRS_TOTAL = "read_repairs_total"
SCRUB_RUNS_TOTAL = "scrub_runs_total"
SCRUB_REPAIRS_TOTAL = "scrub_repairs_total"

ALL_COUNTERS = [
    QUERIES_SINGLE_SHARD, QUERIES_MULTI_SHARD, QUERIES_REPARTITION,
    QUERIES_FAST_PATH, POINT_INDEX_LOOKUPS,
    SUBPLANS_EXECUTED, INTERMEDIATE_ROWS_TOTAL, INTERMEDIATE_BYTES_TOTAL,
    INTERMEDIATE_RESIDENT_TOTAL, INTERMEDIATE_STRING_VALUES_TOTAL,
    DICT_PREDICATE_WALKS_TOTAL, DICT_PREDICATE_VALUES_TOTAL,
    REPARTITION_ROWS_TOTAL, REPARTITION_HOT_BUCKET_ROWS_TOTAL,
    ROWS_INGESTED, ROWS_RETURNED,
    DML_UPDATE, DML_DELETE, DML_MERGE, DDL_COMMANDS,
    CAPACITY_RETRIES, DEVICE_ROWS_SCANNED,
    INSERT_SELECT_PUSHDOWN, INSERT_SELECT_REPARTITION, INSERT_SELECT_PULL,
    CHUNKS_SKIPPED, QUERIES_STREAMED, GROUPBY_BUCKETED_TOTAL,
    LOOKUP_SORTED_TOTAL, LOOKUP_DENSE_TOTAL,
    LOOKUP_SORTED_JOINS_TOTAL, LOOKUP_DENSE_JOINS_TOTAL,
    LOOKUP_PROBE_SLOTS_TOTAL, AGG_BUCKET_SLOTS_TOTAL,
    WINDOW_ROWS_TOTAL, WINDOW_OVER_GROUP_TOTAL,
    WINDOW_SORTS_TOTAL, WINDOW_PRESORTED_TOTAL, JOIN_SORT_OPERANDS_TOTAL,
    AGG_SORT_GATHER_SLOTS_TOTAL, LOOKUP_RANGE_COMPACTIONS_TOTAL,
    BROADCAST_JOINS_TOTAL,
    DEFERRED_COLUMNS_TOTAL, DEFERRED_GATHERS_TOTAL,
    DEFERRED_GATHER_SLOTS_TOTAL, DEFERRED_TOPDOWN_CHAINS_TOTAL,
    DEFERRED_TOPDOWN_RECOMPOSED_TOTAL,
    FEED_CACHE_HIT_BYTES_TOTAL, FEED_CACHE_MISS_BYTES_TOTAL,
    SHUFFLE_BYTES_TOTAL, FETCH_BYTES_TOTAL,
    GC_PAUSES_TOTAL, GC_PAUSE_US_TOTAL,
    CHUNKS_PREFETCHED_TOTAL, PREFETCH_STALLS_TOTAL,
    DEVICE_DECODED_BYTES_TOTAL,
    RETRIES_TOTAL, FAILOVERS_TOTAL, TIMEOUTS_TOTAL, QUERIES_CANCELED,
    FAULTS_INJECTED_TOTAL,
    DEVICE_LOST_TOTAL, MESH_FAILOVERS_TOTAL, QUERIES_RESCUED_TOTAL,
    WLM_ADMITTED_TOTAL, WLM_QUEUED_TOTAL, WLM_SHED_TOTAL,
    WLM_QUEUE_WAIT_MS,
    SERVING_BATCHED_LOOKUPS_TOTAL, SERVING_BATCH_DISPATCH_TOTAL,
    SERVING_CACHE_HITS_TOTAL, SERVING_CACHE_MISSES_TOTAL,
    SERVING_CACHE_INVALIDATIONS_TOTAL,
    EXEC_CACHE_HITS_TOTAL, EXEC_CACHE_MISSES_TOTAL,
    EXEC_CACHE_REJECTS_TOTAL, COMPILES_DEDUPED_TOTAL,
    WARMUP_COMPILES_TOTAL,
    OOM_EVENTS_TOTAL, CACHE_EVICTIONS_TOTAL,
    STREAM_BATCH_SHRINKS_TOTAL, SPILL_PASSES_TOTAL,
    LOG_BATCHES_SHIPPED_TOTAL, LOG_BATCHES_APPLIED_TOTAL,
    REPLICAS_PROMOTED_TOTAL, REPLICATION_FENCED_TOTAL, REPLICA_LAG_LSN,
    STRIPES_VERIFIED_TOTAL, CORRUPTION_DETECTED_TOTAL,
    READ_REPAIRS_TOTAL, SCRUB_RUNS_TOTAL, SCRUB_REPAIRS_TOTAL,
]


class StatCounters:
    def __init__(self):
        self._local = threading.local()
        self._slots_lock = threading.Lock()
        self._slots: list[defaultdict] = []

    def _slot(self) -> defaultdict:
        slot = getattr(self._local, "slot", None)
        if slot is None:
            slot = defaultdict(int)
            self._local.slot = slot
            with self._slots_lock:
                self._slots.append(slot)
        return slot

    def increment(self, name: str, by: int = 1) -> None:
        self._slot()[name] += by

    def snapshot(self) -> dict[str, int]:
        with self._slots_lock:
            slots = list(self._slots)
        out: dict[str, int] = {}
        for slot in slots:
            for k, v in slot.items():
                out[k] = out.get(k, 0) + v
        return {k: out.get(k, 0) for k in ALL_COUNTERS}

    def reset(self) -> None:
        with self._slots_lock:
            for slot in self._slots:
                slot.clear()
