"""Configuration variable registry (the GUC analogue).

The reference registers 145 `citus.*` GUCs in one place
(/root/reference/src/backend/distributed/shared_library_init.c:982,
RegisterCitusConfigVariables) with typed definitions, defaults, ranges, and
docstrings.  This module mirrors that shape: a central typed registry, a
session-scoped settings object, and `set`/`get`/`show_all` with validation.

Only variables that are meaningful for the TPU build are defined; each entry
cites the reference GUC it corresponds to where one exists.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import Any, Callable

from .errors import ConfigError


@dataclass(frozen=True)
class ConfigVar:
    name: str
    default: Any
    doc: str
    vartype: type = int
    min_value: Any = None
    max_value: Any = None
    choices: tuple | None = None
    validate: Callable[[Any], None] | None = None


_REGISTRY: dict[str, ConfigVar] = {}


def _register(var: ConfigVar) -> None:
    if var.name in _REGISTRY:
        raise ConfigError(f"duplicate config var {var.name}")
    _REGISTRY[var.name] = var


def registered_vars() -> dict[str, ConfigVar]:
    return dict(_REGISTRY)


# --- sharding / placement -------------------------------------------------
_register(ConfigVar(
    "shard_count", 8,
    "Number of hash shards for new distributed tables "
    "(ref: citus.shard_count, shared_library_init.c:2616).",
    int, min_value=1, max_value=64000))

_register(ConfigVar(
    "shard_replication_factor", 1,
    "Placements per shard on distinct nodes; reads fail over to the next "
    "replica when a node is disabled/removed "
    "(ref: citus.shard_replication_factor, shared_library_init.c).",
    int, min_value=1, max_value=64))

_register(ConfigVar(
    "mesh_failover", True,
    "Query-level failover on device loss: when a mesh device dies, "
    "hangs or errors mid-statement (DeviceLostError), rebuild a "
    "shrunken mesh from the survivors, mark the dead device's nodes in "
    "the catalog health ledger, re-route shard reads onto surviving "
    "replica placements (shard_replication_factor >= 2) and re-execute "
    "the statement.  Off = a DeviceLostError surfaces immediately "
    "(legacy fail-fast semantics).  No direct reference equivalent — "
    "closest is the adaptive executor's task failover on connection "
    "loss (adaptive_executor.c:95-116).",
    bool))

_register(ConfigVar(
    "mesh_devices", 0,
    "Mesh width for new sessions that pass no explicit n_devices: use "
    "this many devices of the backend (0 = every visible device).  The "
    "catalog's node↔device map folds logical nodes onto the mesh "
    "(catalog.node_device_map); citus_rebalance_mesh() grows the node "
    "set onto a wider mesh.  No reference equivalent — the cluster size "
    "there is the worker node list (pg_dist_node).",
    int, min_value=0, max_value=4096))

# --- executor -------------------------------------------------------------
_register(ConfigVar(
    "enable_repartition_joins", True,
    "Allow dual/single repartition (all_to_all) joins "
    "(ref: citus.enable_repartition_joins, shared_library_init.c:1609).",
    bool))
_register(ConfigVar(
    "compute_dtype", "float32",
    "Device accumulation dtype: float32 (TPU-fast) or float64 (exact; CPU "
    "test meshes). No reference equivalent — TPU-specific policy.",
    str, choices=("float32", "float64")))
_register(ConfigVar(
    "repartition_capacity_factor", 1.5,
    "Static all_to_all buffer headroom over expected rows/partition. "
    "Overflow triggers host-level retry with doubled capacity.",
    float, min_value=1.0, max_value=64.0))
_register(ConfigVar(
    "join_output_capacity_factor", 1.0,
    "Static join-output headroom over probe-side capacity.",
    float, min_value=0.1, max_value=64.0))
_register(ConfigVar(
    "agg_group_capacity_factor", 1.5,
    "Static aggregate-output headroom over the estimated group count.",
    float, min_value=1.0, max_value=64.0))
_register(ConfigVar(
    "group_by_kernel", "auto",
    "High-cardinality GROUP BY path: 'auto' (planner pick — bucketed "
    "dense-grid aggregation on TPU where structurally eligible, sort "
    "path elsewhere), 'sort' (always the argsort/segmented-scan path), "
    "'bucketed' (force the bucketed grid: one sort by slot, each "
    "tile's rows cut into fixed-size chunks, XLA one-hot dot_general "
    "a chunk; sized by the rows whatever the key's distribution, so "
    "it has no capacity to set), 'bucketed_pallas' (force it with the "
    "Pallas tile kernel a chunk). bench_kernels.py groupby A/Bs them "
    "and the chunk size on the target hardware; auto stays "
    "measurement-gated so CPU meshes keep the sort path.",
    str, choices=("auto", "sort", "bucketed", "bucketed_pallas")))
_register(ConfigVar(
    "enable_capacity_feedback", True,
    "After a clean execution, shrink buffers whose recorded actual row "
    "counts sit far below the planner's estimate and recompile once "
    "(the adaptive-executor actual-size feedback, adaptive_executor.c:962"
    ", done the static-shape way).",
    bool))
_register(ConfigVar(
    "enable_fast_path_router", True,
    "Execute single-shard pruned queries host-side, skipping the mesh "
    "program entirely (ref: citus.enable_fast_path_router_planner, "
    "planner/fast_path_router_planner.c:530).",
    bool))
_register(ConfigVar(
    "enable_point_lookup_index", True,
    "Answer WHERE distcol = const through the persistent per-shard "
    "point-lookup index (storage/pkindex.py; ref: columnar btree/hash "
    "index support, columnar/README.md:176).",
    bool))
_register(ConfigVar(
    "fast_path_max_rows", 65536,
    "Row ceiling for host-side fast-path execution; bigger single-shard "
    "scans still use the device path.",
    int, min_value=0, max_value=1 << 24))
_register(ConfigVar(
    "exec_cache_enabled", True,
    "Persistent compiled-executable cache + single-flight compile "
    "dedup (executor/execcache.py): serialized AOT executables land "
    "in <data_dir>/exec_cache/ through the durable-io seam, a fresh "
    "process loads-doesn't-compile on a plan-cache miss, and N "
    "sessions racing a cold shape produce ONE compile (followers "
    "wait under their own statement_timeout_ms/cancel budget).  "
    "Corrupt/torn/version-skewed entries are detected (CRC + "
    "environment stamp) and recompile cleanly.  Off restores the "
    "compile-per-process behavior (the bench cold_start baseline "
    "arm).  No reference GUC — the analogue is an inference server's "
    "model-artifact store (PystachIO, PAPERS.md).",
    bool))
_register(ConfigVar(
    "warmup_budget_ms", 0,
    "Warm-before-admit budget: a fresh session pre-adopts the "
    "persisted executable cache's hottest shapes (warmup_top_shapes) "
    "while the workload manager holds non-exempt admissions, for at "
    "most this long — then the hold auto-expires and the remainder "
    "loads lazily (graceful degradation, never an indefinite block). "
    "0 disables the hold (executables still load lazily on demand). "
    "No reference GUC — the analogue is a serving replica reporting "
    "ready only after model load.",
    int, min_value=0, max_value=600_000))
_register(ConfigVar(
    "warmup_top_shapes", 8,
    "How many of the persisted executable cache's hottest entries "
    "(by hit count, then recency) the warm-before-admit phase "
    "pre-adopts (see warmup_budget_ms).",
    int, min_value=1, max_value=4096))
_register(ConfigVar(
    "max_cached_plans", 256,
    "Compiled-executable cache entries; a structurally repeated query "
    "skips XLA trace+compile (ref: planner/local_plan_cache.c:1-60).",
    int, min_value=0, max_value=100_000))
_register(ConfigVar(
    "max_cached_feed_bytes", 4 << 30,
    "HBM byte budget for device-resident table feeds reused across "
    "queries (ref: connection/pool reuse, executor/adaptive_executor.c:962).",
    int, min_value=0, max_value=1 << 40))
_register(ConfigVar(
    "max_feed_bytes_per_device", 6 << 30,
    "Per-device feed-byte ceiling before the executor streams the largest "
    "scan in stripe batches (double-buffered stripe→HBM pipeline; the "
    "resident path replaces the reference's per-stripe reader, "
    "columnar/columnar_reader.c:323). 0 disables streaming.",
    int, min_value=0, max_value=1 << 40))
_register(ConfigVar(
    "stream_batch_rows", 0,
    "Fixed per-device rows per stream batch (0 = size from the "
    "max_feed_bytes_per_device budget). Test/tuning knob.",
    int, min_value=0, max_value=1 << 30))
_register(ConfigVar(
    "scan_pipeline", "auto",
    "Columnar scan feed pipeline (executor/scanpipe.py): 'off' = the "
    "eager read-everything-then-transfer path; 'host' = prefetch + "
    "native-codec decode on a producer thread overlapped with device "
    "placement, column by column; 'device' = host pipeline plus "
    "on-device decode — frame-of-reference packed ints, dictionary-"
    "coded low-NDV columns and bit-packed validity planes cross the "
    "wire and expand on the mesh (XLA formulations). 'auto' picks device on accelerator "
    "backends and host on CPU meshes, engaging only above a small "
    "row floor (same measurement-gated contract as "
    "group_by_kernel). No reference GUC — the analogue is the "
    "columnar reader's chunk streaming, columnar_reader.c:323.",
    str, choices=("auto", "off", "host", "device")))
_register(ConfigVar(
    "scan_prefetch_depth", 2,
    "Bounded depth of the pipelined-scan prefetch queue (columns in "
    "flight between the decode producer and the placing consumer) and "
    "of the stream path's batch prefetch queue.  Higher depths hide "
    "more decode latency behind transfer at the cost of prefetch-"
    "category HBM residency (the OOM ladder sheds prefetch first).",
    int, min_value=1, max_value=64))
_register(ConfigVar(
    "max_plan_buffer_bytes", 32 << 30,
    "Ceiling on a plan's largest static device buffer. Plans over it "
    "whose shape the OOM degradation ladder can help (streamable / "
    "multi-pass-splittable) degrade instead of erroring; genuinely "
    "ineligible shapes (windows, cartesian blowups) keep the clean "
    "immediate reject. 0 disables the guard.",
    int, min_value=0, max_value=1 << 44))

# --- device-memory governance (executor/hbm.py accountant + the OOM
# degradation ladder) -------------------------------------------------------
_register(ConfigVar(
    "hbm_budget_bytes", 0,
    "Explicit per-device HBM byte budget the accountant enforces the "
    "capacity-regrow guard against (executor/hbm.py). 0 = derive from "
    "an armed MemSim budget or the backend's reported bytes_limit "
    "where available; no enforcement when neither exists. No direct "
    "reference GUC — the analogue is the work_mem family bounding "
    "per-node memory.",
    int, min_value=0, max_value=1 << 44))
_register(ConfigVar(
    "oom_degradation", True,
    "Route DeviceMemoryExhausted (allocator RESOURCE_EXHAUSTED) "
    "through the degradation ladder — evict caches, shrink stream "
    "batches, force streaming, multi-pass partitioned execution — "
    "retrying after each rung (executor.Executor.degrade_for_oom). "
    "Off surfaces the first OOM as a clean ResourceExhausted "
    "immediately (the bench memory_pressure A/B's ungoverned arm).",
    bool))
_register(ConfigVar(
    "oom_max_spill_passes", 16,
    "Ceiling on multi-pass partitioned execution's pass count "
    "(executor/multipass.py); the ladder surfaces a clean "
    "ResourceExhausted rather than splitting further. Grace-style "
    "partition counts beyond ~16 mean the statement is hopeless at "
    "this memory size anyway.",
    int, min_value=2, max_value=4096))

# --- resilience -----------------------------------------------------------
_register(ConfigVar(
    "max_statement_retries", 2,
    "Bounded per-statement retry loop for transient failures (injected "
    "faults, storage IO): classify, mark the failing placement suspect, "
    "run 2PC recovery, back off, re-execute (the adaptive executor's "
    "task retry onto replica placements, adaptive_executor.c:95-116). "
    "0 disables.",
    int, min_value=0, max_value=32))
_register(ConfigVar(
    "retry_backoff_base_ms", 5.0,
    "First retry backoff; doubles per attempt with ±50% jitter "
    "(decorrelated-jitter analogue of the reference's connection "
    "retry pacing).",
    float, min_value=0.0, max_value=60_000.0))
_register(ConfigVar(
    "retry_backoff_max_ms", 200.0,
    "Backoff ceiling for the statement retry loop.",
    float, min_value=0.0, max_value=600_000.0))
_register(ConfigVar(
    "statement_timeout_ms", 0,
    "Cooperative per-statement deadline, checked at fault points, "
    "stream/COPY batch boundaries, retry iterations and workload-"
    "manager queue waits; ONE budget spans admission queueing plus "
    "execution. Raises StatementTimeout (PostgreSQL statement_timeout "
    "analogue; the reference additionally enforces "
    "citus.node_connection_timeout per worker connection). 0 disables.",
    int, min_value=0, max_value=86_400_000))

# --- workload management (wlm/ — the shared-pool governor analogue) -------
def _validate_tenant_weights(value: str) -> None:
    from .wlm.manager import parse_tenant_weights

    parse_tenant_weights(value)  # raises ConfigError on malformed spec


_register(ConfigVar(
    "wlm_enabled", True,
    "Route every non-exempt statement through the workload manager's "
    "admission gate (slots + HBM budget + per-tenant fair queue, "
    "wlm/manager.py).  Off restores the ungoverned race into the "
    "executor (ref: the citus.max_shared_pool_size governor as a "
    "whole, shared_library_init.c).",
    bool))
_register(ConfigVar(
    "max_concurrent_statements", 8,
    "Admission slots: statements executing concurrently across every "
    "session sharing this data_dir; the rest queue per tenant and "
    "priority class (ref: citus.max_shared_pool_size / "
    "citus.max_adaptive_executor_pool_size).",
    int, min_value=1, max_value=1024))
_register(ConfigVar(
    "wlm_queue_depth", 64,
    "Bounded admission queue per priority class; arrivals beyond it "
    "shed with a clean AdmissionRejected instead of queueing without "
    "bound (overload backpressure; 0 sheds whenever the gate is "
    "saturated).",
    int, min_value=0, max_value=1_000_000))
_register(ConfigVar(
    "wlm_default_priority", "interactive",
    "Priority class this session's statements enqueue at.  Classes "
    "dispatch strictly interactive > batch > background; background "
    "rebalance/maintenance jobs always enqueue at background.",
    str, choices=("interactive", "batch", "background")))
_register(ConfigVar(
    "wlm_tenant", "",
    "Explicit tenant identity for fair queueing.  Empty derives the "
    "tenant from the statement's distcol = const pin (the "
    "citus_stat_tenants attribution, stats/tenants.py), falling back "
    "to 'default'.",
    str))
_register(ConfigVar(
    "wlm_tenant_weights", "",
    "Weighted round-robin shares per tenant within a priority class, "
    "as 'tenantA:3,tenantB:1' (unlisted tenants weigh 1).  A tenant "
    "with weight w dispatches w statements per round while others "
    "wait their turn — proportional share, no starvation within a "
    "class (ref: citus_stat_tenants attribution + the rebalancer's "
    "by-disk-size strategy weights).",
    str, validate=_validate_tenant_weights))

# --- serving layer (serving/ — fast-path router + prepared-statement
# caching taken to inference-serving batching, PystachIO-style) ------------
_register(ConfigVar(
    "serving_enabled", True,
    "Route fast-path point-index lookups through the per-data_dir "
    "cross-session micro-batcher (serving/batcher.py): concurrent "
    "lookups coalesce into one batched stripe/chunk probe, single-"
    "flight when alone so an idle system adds no latency.  Also gates "
    "the CDC-invalidated result cache (serving_result_cache_bytes). "
    "Off restores the per-statement solo path (ref: the fast-path "
    "router + local plan cache pair this layer generalizes, "
    "planner/fast_path_router_planner.c:530 + local_plan_cache.c).",
    bool))
_register(ConfigVar(
    "serving_max_batch", 64,
    "Ceiling on point lookups coalesced into ONE batched index probe "
    "per dispatch; arrivals beyond it form the next batch.  No direct "
    "reference GUC — the analogue is an inference server's "
    "max_batch_size.",
    int, min_value=1, max_value=4096))
_register(ConfigVar(
    "serving_batch_window_ms", 2.0,
    "How long a batch leader that found company holds the door open "
    "for the burst's tail before dispatching.  0 dispatches whatever "
    "is queued immediately; a lone request NEVER waits (single-"
    "flight).",
    float, min_value=0.0, max_value=1000.0))
_register(ConfigVar(
    "serving_result_cache_bytes", 256 << 20,
    "Byte budget for the shared per-data_dir result cache of repeated "
    "read statements (serving/result_cache.py).  Freshness is CDC-"
    "driven — entries drop when the change journal shows a write to a "
    "table they read, never on a wall-clock TTL — with a manifest-"
    "identity backstop for mutations the journal missed.  0 disables "
    "(ref: prepared-statement caching, planner/local_plan_cache.c, "
    "taken one level further to the finished result).",
    int, min_value=0, max_value=1 << 40))

# --- columnar storage (ref: columnar GUCs + columnar.options catalog) -----
_register(ConfigVar(
    "columnar_stripe_row_limit", 150_000,
    "Rows per stripe (ref default 150000, columnar/README.md:96-112).",
    int, min_value=1_000, max_value=10_000_000))
_register(ConfigVar(
    "columnar_chunk_group_row_limit", 10_000,
    "Rows per chunk group (ref default 10000).",
    int, min_value=128, max_value=1_000_000))
_register(ConfigVar(
    "columnar_compression", "zstd",
    "Per-chunk compression codec (ref: none/pglz/lz4/zstd; here "
    "none/zlib/zstd).", str, choices=("none", "zlib", "zstd")))
_register(ConfigVar(
    "columnar_compression_level", 3,
    "Codec level (ref: columnar.compression_level).",
    int, min_value=1, max_value=19))

# --- durability & integrity (PostgreSQL data_checksums analogue) -----------
_register(ConfigVar(
    "storage_verify_checksums", True,
    "Verify stripe chunk/footer CRC32s on every read; a mismatch raises "
    "CorruptStripe and the read transparently repairs from a surviving "
    "replica copy when shard_replication_factor >= 2 (ref: PostgreSQL "
    "data_checksums, which Citus inherits per node). Off skips the CRC "
    "pass (structural checks only) — measurement knob, not a production "
    "mode.",
    bool))
_register(ConfigVar(
    "scrub_interval_ms", -1,
    "Maintenance-daemon storage scrub: periodically verify every "
    "placement copy's checksums, quarantine corrupt placements and "
    "re-replicate them from a verified copy (operations/scrubber.py); "
    "-1 disables (run on demand via citus_check_cluster()). No direct "
    "reference GUC — the closest analogue is running pg_checksums/"
    "amcheck from cron.",
    int, min_value=-1, max_value=86_400_000))
_register(ConfigVar(
    "scrub_temp_max_age_s", 300.0,
    "Age floor before the scrubber removes orphan temp files (.tmp / "
    ".aw.*) left by crashes — young temps may belong to an in-flight "
    "writer in another session.",
    float, min_value=0.0, max_value=86_400.0))

# --- ingest ---------------------------------------------------------------
_register(ConfigVar(
    "copy_pipeline", True,
    "Overlap COPY parsing with convert/compress/write via a bounded "
    "producer queue (the per-shard stream overlap of the reference's "
    "COPY, commands/multi_copy.c:315).",
    bool))
_register(ConfigVar(
    "copy_batch_rows", 65_536,
    "Rows parsed per ingest batch before routing "
    "(analogue of per-shard COPY buffering, commands/multi_copy.c).",
    int, min_value=1024, max_value=4_000_000))

# --- transactions / maintenance ------------------------------------------
_register(ConfigVar(
    "recover_2pc_interval_ms", 60_000,
    "How often the maintenance loop retries unresolved prepared commits "
    "(ref: citus.recover_2pc_interval, shared_library_init.c:2510).",
    int, min_value=-1, max_value=7_200_000))
_register(ConfigVar(
    "max_background_task_executors", 4,
    "Parallel background tasks (ref: citus.max_background_task_executors).",
    int, min_value=1, max_value=1000))
_register(ConfigVar(
    "defer_shard_delete_interval_ms", 15_000,
    "Deferred cleanup sweep interval (ref: citus.defer_shard_delete_interval).",
    int, min_value=-1, max_value=86_400_000))
_register(ConfigVar(
    "health_check_interval_ms", -1,
    "Maintenance-daemon node health sweep: probe every node (device + "
    "storage) and disable failures so reads fail over to replicas; -1 "
    "disables (ref: operations/health_check.c). Off by default — probes "
    "pay a device round trip per node, expensive on remote-attached "
    "meshes.",
    int, min_value=-1, max_value=86_400_000))

# --- rebalancer (ref: shard_rebalancer.c + pg_dist_rebalance_strategy) ----
_register(ConfigVar(
    "rebalance_threshold", 0.1,
    "Utilization imbalance tolerated before a move is planned "
    "(ref default 10%, distributed/README.md:2455-2570).",
    float, min_value=0.0, max_value=1.0))
_register(ConfigVar(
    "rebalance_improvement_threshold", 0.5,
    "Minimum relative improvement for a move to be worth it (ref 50%).",
    float, min_value=0.0, max_value=1.0))

# --- tracing / observability (stats/tracing.py span flight recorder) ------
_register(ConfigVar(
    "trace_enabled", True,
    "Always-on span flight recorder (stats/tracing.py): every "
    "statement records a span tree (parse/queue/plan/compile/feed/"
    "mesh/serving/retry phases, carried across producer threads), "
    "folds its wall time into per-statement-class DDSketch latency "
    "histograms (citus_stat_latency()), and keeps recent traces in a "
    "bounded ring.  Off disables ALL recording (the bench overhead "
    "A/B's comparison arm).  No direct reference GUC — the analogue "
    "is pg_stat_statements + EXPLAIN ANALYZE timing always being on.",
    bool))
_register(ConfigVar(
    "trace_ring_statements", 128,
    "Completed statement traces kept in the in-memory ring (oldest "
    "dropped; spans per trace are additionally capped, so trace "
    "memory stays bounded under a many-session hammer).",
    int, min_value=1, max_value=100_000))
_register(ConfigVar(
    "trace_slow_statement_ms", 5000,
    "Statements slower than this persist their full span tree as "
    "JSON under <data_dir>/slow_traces/ through the durable-write "
    "seam (newest 32 kept; tools/trace_summarize.py prints the "
    "newest one).  0 disables the slow-query log "
    "(PostgreSQL log_min_duration_statement analogue).",
    int, min_value=0, max_value=86_400_000))
_register(ConfigVar(
    "trace_fast_statement_ms", 5.0,
    "Auto-degrade threshold: statement classes whose OBSERVED mean "
    "wall (DDSketch histogram, ≥8 calls) is below this record full "
    "span trees only 1 in trace_fast_sample_every statements — "
    "sub-ms cache-hit workloads would otherwise pay the recorder "
    "~15% of pure-Python statement cost (span trees cost ~15 µs; "
    "attribution of a 0.3 ms statement is rarely the question being "
    "asked).  The default sits above the serving hammer's contended "
    "walls (GIL waits inflate a 0.3 ms statement to ~3 ms of wall) "
    "and below every statement class attribution exists for.  "
    "Classes at or above the threshold, cold classes (<8 calls), and "
    "every histogram update stay always-on.  0 disables the degrade "
    "(every statement records a tree).",
    float, min_value=0.0, max_value=60_000.0))
_register(ConfigVar(
    "trace_fast_sample_every", 16,
    "Tree-recording sample rate for sub-threshold statement classes "
    "(see trace_fast_statement_ms).",
    int, min_value=1, max_value=1_000_000))

# --- replication ----------------------------------------------------------
_register(ConfigVar(
    "replica_max_staleness_lsn", -1,
    "Follower read gate: the max lsns a replica may lag its leader and "
    "still answer.  Beyond the bound a statement fails with a clean "
    "ReplicaTooStale (reroute to the leader or a fresher replica) — "
    "staleness stays bounded and VISIBLE, never silently old rows.  "
    "-1 = unbounded (serve whatever was shipped; lag is still reported "
    "by citus_stat_replication).  Closest reference knobs: "
    "hot-standby max_standby_*_delay + citus.metadata_sync staleness "
    "reporting.",
    int, min_value=-1, max_value=1_000_000_000))

_register(ConfigVar(
    "replication_ship_interval_ms", 0,
    "Leader maintenance-daemon duty: ship a replication batch to every "
    "registered follower each interval, so follower staleness is "
    "bounded by cadence without explicit citus_replication_ship() "
    "calls.  0 = off (explicit ship only — the deterministic-test "
    "default).  The analogue of the reference's metadata-sync daemon "
    "interval (citus.metadata_sync_interval).",
    int, min_value=0, max_value=3_600_000))

# --- planner --------------------------------------------------------------
_register(ConfigVar(
    "log_distributed_plans", False,
    "Debug-log every distributed plan chosen (ref: citus.log_multi_join_order "
    "/ explain_all_tasks family).", bool))


class Settings:
    """Session-scoped mutable settings over the global registry."""

    def __init__(self, overrides: dict[str, Any] | None = None):
        self._values: dict[str, Any] = {}
        # bumped on every mutation; consumers (the serving result
        # cache's key memo) cache derived fingerprints per version
        self.version = 0
        self._profile: tuple | None = None
        for name, value in (overrides or {}).items():
            self.set(name, value)

    def get(self, name: str) -> Any:
        if name in self._values:
            return self._values[name]
        var = _REGISTRY.get(name)
        if var is None:
            raise ConfigError(f"unrecognized configuration parameter {name!r}")
        return var.default

    def set(self, name: str, value: Any) -> None:
        var = _REGISTRY.get(name)
        if var is None:
            raise ConfigError(f"unrecognized configuration parameter {name!r}")
        if var.vartype is bool:
            if isinstance(value, str):
                lowered = value.strip().lower()
                if lowered in ("on", "true", "1", "yes"):
                    value = True
                elif lowered in ("off", "false", "0", "no"):
                    value = False
                else:
                    raise ConfigError(
                        f"{name}: invalid boolean value {value!r}")
            value = bool(value)
        elif var.vartype is int:
            value = int(value)
        elif var.vartype is float:
            value = float(value)
        elif var.vartype is str:
            value = str(value)
        if var.min_value is not None and value < var.min_value:
            raise ConfigError(f"{name}: {value} below minimum {var.min_value}")
        if var.max_value is not None and value > var.max_value:
            raise ConfigError(f"{name}: {value} above maximum {var.max_value}")
        if var.choices is not None and value not in var.choices:
            raise ConfigError(f"{name}: invalid value {value!r}; choose from {var.choices}")
        if var.validate is not None:
            var.validate(value)
        self._values[name] = value
        self.version += 1
        self._profile = None

    def reset(self, name: str) -> None:
        self._values.pop(name, None)
        self.version += 1
        self._profile = None

    def show_all(self) -> dict[str, Any]:
        return {name: self.get(name) for name in sorted(_REGISTRY)}

    def profile(self) -> tuple:
        """The full settings profile as a sorted, hashable tuple —
        cached per version so hot paths (the serving result-cache key
        covers every knob) don't re-enumerate the registry per call.

        The memo is stamped with the version read BEFORE enumerating:
        a SET racing a concurrent statement can install a stale tuple,
        but the stamp no longer matches and the next call recomputes —
        a plain `None` sentinel would let the stale tuple (and the
        result-cache keys built from it) persist until the next SET."""
        p = self._profile
        if p is None or p[0] != self.version:
            v = self.version
            p = (v, tuple(sorted(self.show_all().items())))
            self._profile = p
        return p[1]

    @contextlib.contextmanager
    def override(self, **kwargs):
        saved = dict(self._values)
        try:
            for k, v in kwargs.items():
                self.set(k, v)
            yield self
        finally:
            self._values = saved
            self.version += 1
            self._profile = None
