"""Session: the connection-equivalent public API.

Ties the stack together the way the reference's hook layer does
(shared_library_init.c installing planner/utility hooks): parse → route
DDL/utility statements to catalog+storage, SELECTs through the planner
cascade to the distributed executor.

UDF surface parity: `SELECT create_distributed_table('t', 'col')` works
like the reference's UDFs, alongside the direct Python methods.

Recursive planning (GenerateSubplansForSubqueriesAndCTEs analogue,
/root/reference/src/backend/distributed/planner/recursive_planning.c:223):
CTEs, FROM-subqueries, IN/EXISTS/scalar subqueries execute first, bottom-up;
row results materialize as temporary *reference* tables (the
read_intermediate_result analogue — broadcast-visible to every device;
their rows stay in memory, TableStore.hold_resident) or fold into literals,
then the rewritten outer query plans normally.
"""

from __future__ import annotations

import contextlib
import os
import tempfile
from dataclasses import replace as dc_replace

import numpy as np

from .catalog import Catalog, DistributionMethod
from .catalog.catalog import INTERMEDIATE_PREFIX
from .config import Settings
from .errors import (
    CatalogError,
    ExecutionError,
    PlanningError,
    UnsupportedQueryError,
)
from .planner.bind import Binder, BoundQuery, DictProvider
from .planner.explain import explain_tag, format_plan
from .planner.plan import DistributedPlanner, QueryPlan, StatsProvider
from .runtime import ensure_jax_configured
from .sql import ast, parse
from .storage import TableStore
from .types import ColumnDef, DataType, TableSchema, sql_type_to_datatype

_UDFS = ("create_distributed_table", "create_reference_table",
         "citus_add_node", "citus_remove_node", "citus_disable_node",
         "citus_activate_node", "rebalance_table_shards",
         "citus_move_shard_placement", "citus_get_node_clock",
         "citus_stat_counters", "citus_stat_counters_reset",
         "citus_stat_statements", "citus_stat_statements_reset",
         "citus_stat_latency", "citus_stat_latency_reset",
         "citus_stat_tenants", "citus_stat_activity", "citus_stat_wlm",
         "citus_stat_serving", "citus_stat_memory", "citus_stat_mesh",
         "citus_rebalance_mesh", "citus_drain_device",
         "get_rebalance_progress",
         "citus_split_shard_by_split_points", "isolate_tenant_to_node",
         "citus_cleanup_orphaned_resources",
         "citus_rebalance_start", "citus_rebalance_wait",
         "citus_job_wait", "citus_job_cancel", "citus_job_list",
         "citus_change_feed", "citus_create_restore_point",
         "citus_check_cluster_node_health", "citus_promote_node",
         "citus_check_cluster",
         "citus_stat_replication", "citus_replication_ship",
         "citus_promote_replica",
         "nextval", "currval",
         "citus_tables", "citus_shards")


class _StoreStats(StatsProvider):
    def __init__(self, store: TableStore):
        self.store = store

    def table_rows(self, table: str) -> int:
        return self.store.table_row_count(table)

    def column_ndv(self, table: str, column: str, dtype) -> int | None:
        ext = self.column_extent(table, column, dtype)
        return None if ext is None else ext[1]

    def column_extent(self, table: str, column: str,
                      dtype) -> tuple[int, int] | None:
        if dtype == DataType.STRING:
            try:
                d = self.store.dictionary(table, column)
            except Exception:
                return None
            return (0, len(d)) if len(d) else None
        if dtype in (DataType.INT32, DataType.INT64, DataType.DATE,
                     DataType.BOOL):
            rng = self.store.column_range(table, column)
            if rng is None:
                return None
            return int(rng[0]), int(rng[1] - rng[0]) + 1
        return None


class _StoreDicts(DictProvider):
    def __init__(self, store: TableStore):
        self.store = store

    def dictionary(self, table: str, column: str):
        return self.store.dictionary(table, column)


class Session:
    def __init__(self, data_dir: str | None = None,
                 n_devices: int | None = None, platform: str | None = None,
                 mesh=None, **settings):
        """`mesh` accepts an externally built single-axis
        jax.sharding.Mesh — the multi-host path: initialize
        jax.distributed on every host, build one global Mesh over all
        chips (ICI within hosts, DCN across), and hand it in; the
        executor's collectives ride it unchanged (SURVEY §2.6 TPU-native
        comm backend)."""
        ensure_jax_configured(platform=platform)
        self.data_dir = data_dir or tempfile.mkdtemp(prefix="citus_tpu_")
        os.makedirs(self.data_dir, exist_ok=True)
        self.settings = Settings(settings or None)
        cat_path = os.path.join(self.data_dir, "catalog.json")
        self.catalog = (Catalog.load(cat_path) if os.path.exists(cat_path)
                        else Catalog())
        self.store = TableStore(self.data_dir, self.catalog,
                                self.settings)
        from .distributed.mesh import SHARD_AXIS, make_mesh

        if mesh is not None:
            if tuple(mesh.axis_names) != (SHARD_AXIS,):
                raise CatalogError(
                    f"external mesh must have the single axis "
                    f"{SHARD_AXIS!r}, got {mesh.axis_names}")
            self.mesh = mesh
        else:
            if n_devices is None:
                # mesh_devices config var: the settings-level mesh
                # width for sessions that pass no explicit n_devices
                # (0 = every visible device, the historic default)
                cfg = self.settings.get("mesh_devices")
                n_devices = cfg or None
            self.mesh = make_mesh(n_devices)
        self.n_devices = len(self.mesh.devices.flatten())
        if not self.catalog.nodes:
            for i in range(self.n_devices):
                self.catalog.add_node(f"device:{i}")
        import itertools
        import threading

        self._temp_counter = itertools.count(1)
        # cooperative cross-thread cancel flag (pg_cancel_backend
        # analogue): Session.cancel() sets it; the executing thread
        # notices at the next seam and raises QueryCanceled
        self._cancel_evt = threading.Event()
        # PREPARE registry: name → statement AST (session-scoped, like PG)
        self._prepared: dict[str, ast.Statement] = {}
        # hot-statement memo: script text → parsed statement tuple.
        # Frozen AST nodes are reusable value objects, so a repeated
        # statement (the serving workload) skips the lexer/parser AND
        # replays the SAME tree — which lets the result-cache key memo
        # ride on the node (result_cache.cache_key).  Plain dict ops
        # only (GIL-atomic; Session.execute supports concurrent
        # callers), reset wholesale when full.
        self._hot_stmts: dict[str, tuple] = {}
        # per-session handle to the shared serving result cache (the
        # registry lookup realpath-walks the data_dir; resolve once).
        # Guarded: concurrent execute() racing check-then-acquire would
        # take TWO registry refs for one session and close() releases
        # only one — pinning the cache bytes for the process lifetime
        self._result_cache_handle = None
        self._result_cache_mu = threading.Lock()
        # EXECUTE args visible to recursive planning (subqueries run
        # BEFORE the outer binder sees the params; thread-local because
        # Session.execute supports concurrent callers)
        self._params_tls = threading.local()
        self._view_tls = threading.local()  # view-expansion cycle guard
        from .executor.runner import Executor
        from .stats import SessionStats

        self.stats = SessionStats(self.data_dir, self.settings)
        self.executor = Executor(self.catalog, self.store, self.settings,
                                 self.mesh, counters=self.stats.counters)
        # workload manager: sessions sharing a data_dir share ONE
        # admission gate (they share the device, the compile cache and
        # the HBM feed budget — wlm/manager.py)
        from .wlm import workload_manager_for

        self.wlm = workload_manager_for(self.data_dir)
        # per-thread record of the last admission (EXPLAIN ANALYZE's
        # Workload: line reads it after the admitted statement planned)
        self._wlm_tls = threading.local()
        # per-thread record of the last follower staleness check
        # (EXPLAIN ANALYZE's Replication: line)
        self._replica_stale_tls = threading.local()
        # transaction coordinator + shared lock table; interrupted 2PCs
        # from a previous process roll forward/back NOW, before any read
        # (the maintenance-daemon recovery pass at backend start;
        # ref: transaction/transaction_recovery.c)
        from .transaction.locks import lock_manager_for
        from .transaction.manager import TransactionManager

        self.txn_manager = TransactionManager(self.store, self.data_dir)
        self.locks = lock_manager_for(self.data_dir)
        self.txn_manager.recover()
        # crash-recovery sweep: half-finished splits/moves resolve against
        # the catalog (operations/cleanup.py; ref: shard_cleaner.c)
        from .operations.cleanup import cleanup_registry_for

        cleanup_registry_for(self.data_dir).sweep(self.store, self.catalog)
        # replication role (replication/): a follower data_dir drains
        # any batches shipped while no session was open, BEFORE serving
        # (the same open-time catch-up 2PC recovery just did for the
        # leader-local txnlog), then re-reads its catalog — the shipped
        # one supersedes whatever this constructor loaded
        from .replication import apply_pending, replication_for

        self.replication = replication_for(self.data_dir)
        if self.replication.is_follower():
            res = apply_pending(self.data_dir,
                                counters=self.stats.counters,
                                store=self.store)
            if res["applied"]:
                self.catalog.maybe_reload(cat_path)
        # background services: job runner (pg_dist_background_task
        # executors) + maintenance daemon (2PC recovery, deferred cleanup,
        # deadlock checks — utils/maintenanced.c:460)
        from .background import BackgroundJobRunner, MaintenanceDaemon

        self.jobs = BackgroundJobRunner(
            self.settings.get("max_background_task_executors"),
            wlm=self.wlm, wlm_request=self._wlm_background_request)
        self.maintenance = MaintenanceDaemon(self)
        self.maintenance.start()
        # warm-before-admit (executor/execcache.py): a restarted
        # process with a populated executable cache pre-adopts its
        # hottest shapes while the WLM holds non-exempt admissions —
        # bounded by warmup_budget_ms (the hold auto-expires, so an
        # overrun degrades to lazy loading, never an admission block)
        self._warmup_thread = None
        self._warmup_stop = threading.Event()
        import time as _time

        warm_ms = self.settings.get("warmup_budget_ms")
        if warm_ms > 0 and self.settings.get("exec_cache_enabled") \
                and self.executor.exec_cache.has_entries():
            deadline = _time.monotonic() + warm_ms / 1000.0
            self.wlm.hold_admissions(deadline)
            self._warmup_thread = threading.Thread(
                target=self._run_warmup, args=(deadline,),
                name="citus-tpu-warmup", daemon=True)
            self._warmup_thread.start()

    def _run_warmup(self, deadline: float) -> None:
        """Warmup-thread body (session-owned; close() signals the stop
        event and joins it — the admission hold on the SHARED workload
        manager must not outlive the session that requested it): adopt
        persisted executables, then ALWAYS release the hold."""
        try:
            self.executor.warmup_from_cache(
                deadline, self.settings.get("warmup_top_shapes"),
                stop=self._warmup_stop)
        finally:
            self.wlm.release_admissions()

    # -- public API --------------------------------------------------------
    def execute(self, sql: str):
        """Run a SQL script; returns the last statement's ResultSet/None."""
        import time as _time

        from .stats import extract_tenants

        result = None
        tenant_hits: list[tuple[str, object]] = []
        # adopt another session's committed DDL (one stat per call);
        # never mid-transaction — the open txn pinned its snapshot
        if self.txn_manager.current is None:
            self.catalog.maybe_reload(
                os.path.join(self.data_dir, "catalog.json"))
        self._cancel_evt.clear()  # a fresh script clears stale cancels
        from .stats import counters as sc
        from .stats.tracing import trace_span
        from .storage import integrity as _integrity

        # span flight recorder: each statement of the script gets its
        # own trace; the first one's covers parse (hot-statement memo
        # hits make repeats ~free), so top-level spans tile the wall
        tracer = self.stats.tracing
        th = tracer.begin(sql)
        trace_err = None
        try:
            stmts = self._hot_stmts.get(sql)
            if stmts is None:
                with trace_span("parse"):
                    stmts = tuple(parse(sql))
                if len(self._hot_stmts) >= 512:
                    self._hot_stmts.clear()
                self._hot_stmts[sql] = stmts
            with self.stats.activity.track(sql) as activity:
                t0 = _time.perf_counter()
                first_stmt = True
                for stmt in stmts:
                    if not first_stmt:
                        tracer.end(th)
                        th = tracer.begin(sql)
                    first_stmt = False
                    activity.retries = 0
                    activity.read_repairs = 0
                    # per-STATEMENT snapshot (like the retries reset):
                    # the citus_stat_activity cache columns show the
                    # in-flight statement's own traffic, not the whole
                    # script's
                    activity.cache_base = (
                        self.executor.plan_cache.hits,
                        self.executor.plan_cache.misses,
                        self.executor.feed_cache.hits,
                        self.executor.feed_cache.misses)
                    ibase = _integrity.snapshot()
                    try:
                        result = self._execute_admitted(stmt, activity)
                    finally:
                        # fold this statement's storage-integrity
                        # traffic (module-wide accounting, like
                        # faults_injected) into the session counters +
                        # the activity row
                        idelta = _integrity.delta(ibase)
                        c = self.stats.counters
                        if idelta["stripes_verified"]:
                            c.increment(sc.STRIPES_VERIFIED_TOTAL,
                                        idelta["stripes_verified"])
                        if idelta["corruption_detected"]:
                            c.increment(sc.CORRUPTION_DETECTED_TOTAL,
                                        idelta["corruption_detected"])
                        if idelta["read_repairs"]:
                            c.increment(sc.READ_REPAIRS_TOTAL,
                                        idelta["read_repairs"])
                            activity.read_repairs += \
                                idelta["read_repairs"]
                    self._count_statement(stmt, result)
                    tenant_hits.extend(extract_tenants(stmt,
                                                       self.catalog))
                elapsed_ms = (_time.perf_counter() - t0) * 1000.0
        except BaseException as e:
            trace_err = e
            raise
        finally:
            tracer.end(th, error=trace_err)
        rows = getattr(result, "row_count", 0) if result is not None else 0
        self.stats.queries.record(sql, elapsed_ms, rows)
        for table, tenant in tenant_hits:
            self.stats.tenants.record(table, tenant, elapsed_ms)
        return result

    def _count_statement(self, stmt: ast.Statement, result) -> None:
        from .stats import counters as sc

        c = self.stats.counters
        if isinstance(stmt, ast.Select):
            if (not stmt.from_items and len(stmt.items) == 1
                    and isinstance(stmt.items[0].expr, ast.FuncCall)
                    and stmt.items[0].expr.name in _UDFS):
                return  # admin UDF calls aren't query traffic
            if result is not None:
                c.increment(sc.ROWS_RETURNED, result.row_count)
                c.increment(sc.CAPACITY_RETRIES, result.retries)
                c.increment(sc.DEVICE_ROWS_SCANNED,
                            result.device_rows_scanned)
                if getattr(result, "fast_path", False):
                    c.increment(sc.QUERIES_FAST_PATH)
        elif isinstance(stmt, ast.Update):
            c.increment(sc.DML_UPDATE)
        elif isinstance(stmt, ast.Delete):
            c.increment(sc.DML_DELETE)
        elif isinstance(stmt, ast.Merge):
            c.increment(sc.DML_MERGE)
        elif isinstance(stmt, (ast.CreateTable, ast.DropTable)):
            c.increment(sc.DDL_COMMANDS)

    # -- workload management -----------------------------------------------
    def _wlm_background_request(self):
        """Admission request for background job tasks (rebalance moves
        etc., background/jobs.py): background class — user statements
        always dispatch first — with an effectively unbounded queue (a
        maintenance task waits for capacity rather than shedding)."""
        from .wlm import AdmissionRequest

        return AdmissionRequest(
            tenant="background", priority="background",
            max_slots=self.settings.get("max_concurrent_statements"),
            max_feed_bytes=self.settings.get("max_feed_bytes_per_device"),
            queue_depth=1_000_000)

    def _execute_admitted(self, stmt: ast.Statement, activity=None):
        """Admission wraps the resilience envelope: classify the
        statement, hold a slot + HBM budget through every retry of its
        execution, release at statement end.  Exempt statements
        (utility, transaction control, admin UDFs, fast-path point
        reads) skip the gate — see wlm/admission.py.  Queue waits honor
        statement_timeout_ms and Session.cancel() exactly like
        execution does."""
        from .errors import (
            AdmissionRejected,
            QueryCanceled,
            StatementTimeout,
        )
        from .stats import counters as sc
        from .utils.cancellation import deadline_scope
        from .wlm import (
            AdmissionRequest,
            parse_tenant_weights,
            planned_feed_bytes,
            statement_exempt,
            statement_tenant,
        )

        self._wlm_tls.last = None
        # EXECUTE name(...) classifies by its prepared target statement
        # (the admission decision should see the real query shape)
        target = stmt
        if isinstance(stmt, ast.ExecutePrepared):
            target = self._prepared.get(stmt.name, stmt)
        # statements inside an OPEN transaction bypass the gate: the
        # transaction already owns its resources (the reference's pool
        # slot is acquired once and held for the txn), and queueing
        # mid-transaction while holding 2PL locks would create
        # slot↔lock deadlock cycles the lock-manager's detector cannot
        # see (it only walks lock waits — a slot edge is invisible)
        from .stats.tracing import trace_span

        # exemption classification is admission work: its (small, but
        # catalog/store-touching) cost books under the queue phase so
        # top-level spans tile the statement wall (no meta: this span
        # is on the serving hot path, and the kwargs dict costs QPS —
        # the WAIT span below is the one carrying queued_ms)
        with trace_span("queue"):
            exempt = (self.txn_manager.current is not None
                      or not self.settings.get("wlm_enabled")
                      or statement_exempt(target, self.catalog,
                                          self.settings, _UDFS))
        if exempt:
            return self._execute_resilient(stmt, activity)

        # the "queue" span covers classification + the slot/HBM queue
        # wait (its duration reconciles against ticket.queued_ms —
        # tests pin the two within tolerance)
        with trace_span("queue") as qspan:
            tenant = statement_tenant(target, self.catalog,
                                      self.settings)
            weights = parse_tenant_weights(
                self.settings.get("wlm_tenant_weights"))
            req = AdmissionRequest(
                tenant=tenant,
                priority=self.settings.get("wlm_default_priority"),
                feed_bytes=planned_feed_bytes(target, self.catalog,
                                              self.store, self.n_devices,
                                              self.settings),
                weight=weights.get(tenant, 1),
                max_slots=self.settings.get("max_concurrent_statements"),
                max_feed_bytes=self.settings.get(
                    "max_feed_bytes_per_device"),
                queue_depth=self.settings.get("wlm_queue_depth"))
            timeout_ms = self.settings.get("statement_timeout_ms")
            if activity is not None:
                activity.wait_state = "queued"
            try:
                # the queue wait carries the same deadline/cancel
                # machinery as execution (check_cancel fires every
                # wait slice)
                with deadline_scope(timeout_ms or None,
                                    self._cancel_evt):
                    ticket = self.wlm.admit(req)
            except Exception as e:
                if activity is not None:
                    activity.wait_state = "running"
                if isinstance(e, AdmissionRejected):
                    self.stats.counters.increment(sc.WLM_SHED_TOTAL)
                elif isinstance(e, StatementTimeout):
                    self.stats.counters.increment(sc.TIMEOUTS_TOTAL)
                elif isinstance(e, QueryCanceled):
                    self.stats.counters.increment(sc.QUERIES_CANCELED)
                raise
            if qspan is not None:
                qspan.meta = {"tenant": ticket.tenant,
                              "queued_ms": round(ticket.queued_ms, 3)}
        if activity is not None:
            activity.wait_state = "admitted"
            activity.queued_ms = ticket.queued_ms
        self.stats.counters.increment(sc.WLM_ADMITTED_TOTAL)
        if ticket.was_queued:
            self.stats.counters.increment(sc.WLM_QUEUED_TOTAL)
            self.stats.counters.increment(
                sc.WLM_QUEUE_WAIT_MS, int(round(ticket.queued_ms)))
        self._wlm_tls.last = {
            "tenant": ticket.tenant, "priority": ticket.priority,
            "queued_ms": ticket.queued_ms,
            "feed_bytes": ticket.feed_bytes,
            "slots_in_use": ticket.slots_in_use,
            "slots_total": ticket.slots_total}
        # ONE deadline spans queue wait + execution: the time spent
        # queued comes out of the execution budget (a statement must
        # not run for ~2× its configured timeout)
        remaining_ms = (max(1.0, timeout_ms - ticket.queued_ms)
                        if timeout_ms else None)
        try:
            if activity is not None:
                activity.wait_state = "running"
            return self._execute_resilient(stmt, activity,
                                           timeout_ms=remaining_ms)
        finally:
            self.wlm.release(ticket)

    # -- resilient statement execution -------------------------------------
    # fault points that fire AFTER a write's visibility flip: the effect
    # is already committed, so re-executing the statement would apply it
    # twice — the error propagates instead (the reference likewise never
    # retries a task once its placement reported success)
    _NON_RETRYABLE_POINTS = frozenset({"cdc.append"})

    def cancel(self) -> None:
        """Cooperative cross-thread cancel of in-flight statements (the
        pg_cancel_backend analogue): executing threads notice at their
        next seam — fault point, stream/COPY batch boundary, retry
        iteration — and raise QueryCanceled."""
        self._cancel_evt.set()

    def _execute_resilient(self, stmt: ast.Statement, activity=None,
                           timeout_ms=None):
        """One statement under the resilience envelope: a cooperative
        deadline (`statement_timeout_ms` + Session.cancel) around a
        bounded retry loop (`max_statement_retries`, exponential backoff
        with jitter) that classifies errors, marks failing placements
        suspect so the retry's routing fails over to surviving replicas,
        and runs 2PC recovery first so no retry observes half-applied
        state — the adaptive executor's task-retry/failover loop
        (adaptive_executor.c:95-116) hoisted to the statement level.

        `timeout_ms=None` reads `statement_timeout_ms`; the admission
        wrapper passes the budget REMAINING after its queue wait so one
        deadline spans the whole statement."""
        import random as _random
        import time as _time

        from .errors import (
            DeviceLostError,
            DeviceMemoryExhausted,
            MeshDegradedError,
            PlacementLostError,
            QueryCanceled,
            ResourceExhausted,
            StatementTimeout,
        )
        from .stats import counters as sc
        from .stats.tracing import trace_span
        from .utils.cancellation import check_cancel, deadline_scope

        max_retries = self.settings.get("max_statement_retries")
        if timeout_ms is None:
            timeout_ms = self.settings.get("statement_timeout_ms")
        attempt = 0
        oom_steps = 0  # statement-local position on the OOM ladder
        mesh_steps = 0  # statement-local device-loss failover count
        rescued = False  # a mesh failover happened; count on success
        width0 = self.n_devices  # bounds the failover budget
        with deadline_scope(timeout_ms or None,
                            self._cancel_evt) as deadline:
            while True:
                # a COMMIT that dies mid-2PC is resolved through
                # recovery, never re-execution — remember its txid now
                # (the manager clears `current` on the way out)
                commit_txid = None
                if isinstance(stmt, ast.TransactionStmt) and \
                        stmt.kind == "commit" and \
                        self.txn_manager.current is not None:
                    commit_txid = self.txn_manager.current.txid
                try:
                    check_cancel()
                    n_attempt = attempt + oom_steps + mesh_steps
                    # first attempts (the steady state) skip the meta
                    # kwargs dict — serving-QPS hot path
                    espan = (trace_span("execute") if n_attempt == 0
                             else trace_span("execute",
                                             attempt=n_attempt))
                    with espan:
                        result = self._execute_statement(stmt)
                    if rescued:
                        # the statement ANSWERED because the mesh-
                        # degrade path rescued it — the device_loss
                        # bench's kill-to-first-answer numerator
                        self.stats.counters.increment(
                            sc.QUERIES_RESCUED_TOTAL)
                    return result
                except (StatementTimeout, QueryCanceled) as e:
                    if commit_txid is not None and \
                            self._resolve_failed_commit(commit_txid):
                        # the deadline/cancel fired inside the 2PC with
                        # the commit record already durable: the txn IS
                        # committed (recovery just rolled it forward) —
                        # report success, not a lying timeout
                        return None
                    self.stats.counters.increment(
                        sc.TIMEOUTS_TOTAL
                        if isinstance(e, StatementTimeout)
                        else sc.QUERIES_CANCELED)
                    raise
                except Exception as e:
                    if getattr(e, "injected_fault", False):
                        self.stats.counters.increment(
                            sc.FAULTS_INJECTED_TOTAL)
                    # device loss is *retryable-after-mesh-degrade*:
                    # mark the device suspect in the catalog health
                    # ledger, rebuild a shrunken mesh from the
                    # survivors, re-plan through the node↔device map
                    # (replicated shard placements fail over to
                    # surviving nodes) and re-run — ending in a clean
                    # MeshDegradedError when nothing survives or an
                    # unreplicated shard is stranded, never wrong rows
                    # or a hung process.  Mesh failovers ride their own
                    # counter, not max_statement_retries: the budget is
                    # the mesh width (each failover buries ≥1 device),
                    # not a transient-fault allowance.  A COMMIT dying
                    # mid-2PC resolves through recovery instead (the
                    # generic path below).
                    # (COPY is excluded — it commits per parsed batch,
                    # so a mesh-degraded re-run would double-load the
                    # committed batches; its host-side ingest never
                    # touches the mesh seams anyway)
                    if isinstance(e, DeviceLostError) and \
                            commit_txid is None and \
                            not isinstance(stmt, ast.CopyFrom):
                        self.stats.counters.increment(
                            sc.DEVICE_LOST_TOTAL)
                        did = getattr(e, "device_id", None)
                        if did is not None:
                            self.catalog.set_device_state(did, "suspect")
                        if isinstance(e, MeshDegradedError) or \
                                not self.settings.get("mesh_failover"):
                            raise
                        mesh_steps += 1
                        if mesh_steps > max(1, width0):
                            raise MeshDegradedError(
                                f"device-loss failover budget spent "
                                f"after {mesh_steps - 1} mesh "
                                f"degrade(s): {e}",
                                device_id=did, seam=e.seam) from e
                        with trace_span("mesh.degrade"):
                            status = self._degrade_mesh(e)
                        if status == "unsurvivable":
                            raise MeshDegradedError(
                                f"no surviving mesh device to fail "
                                f"over to: {e}",
                                device_id=did, seam=e.seam) from e
                        if status == "failover":
                            self.stats.counters.increment(
                                sc.MESH_FAILOVERS_TOTAL)
                            rescued = True
                        # 'transient': probe found every device alive
                        # (a link flap) — bare re-run, same budget
                        if activity is not None:
                            activity.retries = \
                                attempt + oom_steps + mesh_steps
                        continue  # re-plan + re-run (deadline intact)
                    # an unroutable shard while devices are down is the
                    # replication-1 terminal case of device loss: the
                    # only placement sits on a dead device — surface it
                    # as the DeviceLostError-derived clean error it is
                    if isinstance(e, PlacementLostError) and \
                            self.catalog.dead_nodes():
                        raise MeshDegradedError(
                            "shard unroutable after device loss (its "
                            "only placement is on a dead device; "
                            "shard_replication_factor >= 2 would have "
                            f"failed over): {e}") from e
                    # device-memory exhaustion is *retryable-after-
                    # degradation*: each OOM applies the next rung of
                    # the ladder (evict caches → shrink stream batches
                    # → force streaming → multi-pass), then re-runs —
                    # ending in a clean ResourceExhausted when no rung
                    # can help, never a dead process or wrong rows.
                    # Degradation retries ride their own counter, not
                    # max_statement_retries: the ladder's depth is a
                    # property of the shape, not a transient-fault
                    # budget.  A write's device SELECT half runs before
                    # any visibility flip, so the re-run is safe.
                    if isinstance(e, DeviceMemoryExhausted) and \
                            commit_txid is None:
                        self.stats.counters.increment(
                            sc.OOM_EVENTS_TOTAL)
                        if not self.settings.get("oom_degradation"):
                            raise
                        oom_steps += 1
                        with trace_span("oom.degrade", rung=oom_steps):
                            rung = self.executor.degrade_for_oom(
                                oom_steps, getattr(e, "nbytes", None))
                        if rung is None:
                            raise ResourceExhausted(
                                "statement does not fit device memory "
                                f"even after {oom_steps - 1} "
                                f"degradation rung(s): {e}") from e
                        if activity is not None:
                            activity.retries = attempt + oom_steps
                        continue  # re-run degraded (deadline intact)
                    retryable = self._retryable_error(e)
                    # COPY commits each parsed batch independently, so
                    # re-executing a partially ingested file would
                    # double-load the committed batches — the failure
                    # surfaces instead (same double-apply rule as the
                    # post-visibility seams)
                    if isinstance(stmt, ast.CopyFrom):
                        retryable = False
                    # max_statement_retries=0 switches the whole
                    # resilient layer off (legacy crash semantics:
                    # the NEXT session's recovery pass resolves)
                    if commit_txid is not None and retryable and \
                            max_retries > 0:
                        if self._resolve_failed_commit(commit_txid):
                            return None  # recovery rolled it forward
                        raise  # rolled back: a clean, reported failure
                    if not retryable or attempt >= max_retries:
                        raise
                    attempt += 1
                    self.stats.counters.increment(sc.RETRIES_TOTAL)
                    if activity is not None:
                        activity.retries = attempt
                    self._mark_failover(e)
                    # retries must never observe half-applied state:
                    # finish any interrupted 2PC before re-executing
                    # (transaction_recovery.c at the retry boundary).
                    # Recovery runs deadline-free — an expired deadline
                    # must not abort the roll-forward it deserves.
                    if self.txn_manager.current is None:
                        try:
                            with deadline_scope(None):
                                self.txn_manager.recover()
                        except Exception:
                            pass  # recovery retries on the next pass
                    base_s = self.settings.get(
                        "retry_backoff_base_ms") / 1000.0
                    cap_s = self.settings.get(
                        "retry_backoff_max_ms") / 1000.0
                    delay = base_s * (2 ** (attempt - 1))
                    delay *= 0.5 + _random.random()  # ±50% jitter
                    delay = min(cap_s, delay)  # cap AFTER jitter
                    rem = deadline.remaining()
                    if rem is not None:
                        delay = max(0.0, min(delay, rem))
                    if delay:
                        # waiting on the cancel event (not time.sleep)
                        # keeps Session.cancel() prompt even mid-backoff
                        with trace_span("retry.backoff"):
                            self._cancel_evt.wait(delay)
                    # loop: the next check_cancel raises if the sleep
                    # consumed the deadline or a cancel arrived

    def _retryable_error(self, e: BaseException) -> bool:
        """Transient ⇒ retry: injected faults (the killed-connection
        analogue), storage IO.  Semantic errors (parse/planning/catalog/
        capacity), cancellation, and post-visibility faults are not."""
        from .errors import QueryCanceled, StorageError
        from .utils.faultinjection import InjectedFault

        if isinstance(e, QueryCanceled):
            return False
        # post-visibility failures (tagged by the seam itself — e.g.
        # ChangeLog.emit runs after the manifest flip — or recognized by
        # fault-point name): the effect is committed, a rerun would
        # double-apply
        if getattr(e, "post_visibility", False):
            return False
        if getattr(e, "fault_point", None) in self._NON_RETRYABLE_POINTS:
            return False
        return isinstance(e, (InjectedFault, StorageError, OSError))

    def _degrade_mesh(self, e: BaseException) -> str:
        """Shrink this session's mesh around a lost device.  Returns
        'failover' (mesh rebuilt from survivors, dead device's nodes
        marked dead so replicated shards re-route), 'transient' (the
        probe pass found every device answering — a link flap; bare
        re-run), or 'unsurvivable' (no device survives).

        The error names the corpse when the seam knew it
        (e.device_id); an opaque collective failure names none, so
        every mesh device is health-probed with a one-scalar transfer
        (distributed/mesh.probe_mesh_devices) — the connection-level
        health check of the reference (health_check.c) applied to mesh
        slots.  The node↔device map is read BEFORE the nodes die: the
        dead positions' nodes are exactly what must leave routing.
        Statements in flight on the old mesh object finish there; the
        next plan of every statement reads self.mesh/self.n_devices
        fresh (executor.adopt_mesh drops the compiled-plan and feed
        caches, which pinned the dead device's buffers)."""
        from .distributed.mesh import (
            mesh_device_ids,
            mesh_without,
            probe_mesh_devices,
        )

        ids = mesh_device_ids(self.mesh)
        did = getattr(e, "device_id", None)
        dead = [did] if did is not None else probe_mesh_devices(self.mesh)
        dead = [d for d in dead if d in set(ids)]
        if not dead:
            return "transient"
        # the map over the PRE-loss active nodes: positions → nodes
        dmap = self.catalog.node_device_map(self.n_devices)
        dead_pos = {i for i, d in enumerate(ids) if d in set(dead)}
        new_mesh = mesh_without(self.mesh, dead)
        for d in dead:
            self.catalog.set_device_state(d, "dead")
        if new_mesh is None:
            return "unsurvivable"
        for node_id, pos in dmap.items():
            if pos in dead_pos:
                self.catalog.mark_node_dead(node_id)
        self.mesh = new_mesh
        self.n_devices = int(new_mesh.devices.size)
        self.executor.adopt_mesh(new_mesh)
        return "failover"

    def _mark_failover(self, e: BaseException) -> None:
        """A failed shard read carries (table, shard_id): mark the
        placement it routed to as suspect so `catalog.active_placement`
        re-derives the retry's routing onto a surviving replica, and
        count the failover when such a replica exists."""
        from .stats import counters as sc

        shard_id = getattr(e, "shard_id", None)
        if shard_id is None:
            return
        try:
            p = self.catalog.active_placement(shard_id)
        except Exception:
            return
        if self.catalog.mark_placement_suspect(p.placement_id):
            self.stats.counters.increment(sc.FAILOVERS_TOTAL)

    def _resolve_failed_commit(self, txid: int) -> bool:
        """COMMIT died mid-2PC: resolve by the recovery rule instead of
        re-executing (the transaction state is already torn down).
        Commit record durable → roll the prepared txn forward (the
        idempotent apply replays safely over a partial first apply) and
        the statement SUCCEEDS; no record → recovery discarded the
        prepare and the original error propagates.  Returns True when
        rolled forward (transaction_recovery.c's exact rule)."""
        from .utils.cancellation import deadline_scope

        had_commit_record = self.txn_manager.has_commit_record(txid)
        try:
            # deadline-free: an expired statement deadline must not
            # abort the roll-forward mid-apply (idempotent but the
            # statement would then misreport a committed txn)
            with deadline_scope(None):
                self.txn_manager.recover()
        except Exception:
            return False
        return had_commit_record

    def create_distributed_table(self, name: str, distribution_column: str,
                                 shard_count: int | None = None,
                                 colocate_with: str | None = None):
        """Convert a (created, still-empty) table into a hash-distributed
        one — the create_distributed_table UDF analogue
        (commands/create_distributed_table.c:222)."""
        meta = self.catalog.table(name)
        if self.store.table_row_count(name) > 0:
            raise CatalogError(
                f"table {name!r} already contains data; distribute before "
                "loading (data redistribution lands with shard rebalancer)")
        schema = meta.schema
        self.catalog.drop_table(name)
        self.catalog.create_distributed_table(
            name, schema, distribution_column,
            shard_count or self.settings.get("shard_count"),
            colocate_with=colocate_with,
            replication_factor=self.settings.get(
                "shard_replication_factor"))
        self._save_catalog()

    def create_reference_table(self, name: str):
        meta = self.catalog.table(name)
        if self.store.table_row_count(name) > 0:
            raise CatalogError(f"table {name!r} already contains data")
        schema = meta.schema
        self.catalog.drop_table(name)
        self.catalog.create_reference_table(name, schema)
        self._save_catalog()

    def close(self):
        if self._warmup_thread is not None:
            self._warmup_stop.set()  # stop between adoptions
            self._warmup_thread.join(timeout=5.0)
            self._warmup_thread = None
        self.maintenance.stop()
        self.jobs.shutdown()
        self._save_catalog()
        # drain debounced warm-start persistence (caps memo rewrites
        # coalesce under compile storms; the exec-cache hotness index
        # flushes every N touches) so a clean shutdown leaves the
        # restart-survival state current on disk
        self.executor.flush_persistent()
        with self._result_cache_mu:
            handle, self._result_cache_handle = \
                self._result_cache_handle, None
        if handle is not None:
            from .serving.result_cache import release_result_cache

            release_result_cache(self.data_dir)

    # -- replication -------------------------------------------------------
    def promote_replica(self) -> int:
        """Promote this follower data_dir to leader (leader-death
        failover): roll the shipped journal forward, bump the fencing
        epoch (stamping the old leader's dir so a zombie's late ship is
        rejected), flip the role record, then run the PR-7 recovery
        machinery — 2PC recovery + the cleanup sweep — through this
        session's own managers and adopt the rolled-forward catalog.
        Returns the new epoch; this session accepts writes from the
        next statement on."""
        from .operations.cleanup import cleanup_registry_for
        from .replication import promote

        epoch = promote(self.data_dir, counters=self.stats.counters,
                        store=self.store)
        self.txn_manager.recover()
        cleanup_registry_for(self.data_dir).sweep(self.store,
                                                  self.catalog)
        self.catalog.maybe_reload(
            os.path.join(self.data_dir, "catalog.json"))
        return epoch

    # -- change data capture ----------------------------------------------
    def change_events(self, table: str | None = None,
                      from_lsn: int = 0) -> list[dict]:
        """Committed logical changes with lsn > from_lsn (the change-feed
        subscription read; ref: cdc/cdc_decoder.c)."""
        return self.store.change_log.read(table, from_lsn)

    def change_rows(self, event: dict):
        """Materialize one event's row payload: (values, validity)."""
        from .cdc.feed import rows_for

        return rows_for(self.store, event)

    # -- statement dispatch ------------------------------------------------
    # statement shapes a follower must refuse (every mutation belongs
    # on the leader; the journal is the only way data reaches a replica)
    _REPLICA_WRITE_STMTS = (
        "InsertValues", "InsertSelect", "Update", "Delete", "Merge",
        "CopyFrom", "CreateTable", "DropTable", "AlterTable",
        "CreateView", "DropView", "CreateSequence", "DropSequence")
    # admin UDFs that mutate catalog/data — equally refused on followers
    _REPLICA_WRITE_UDFS = frozenset({
        "create_distributed_table", "create_reference_table",
        "citus_add_node", "citus_remove_node", "citus_disable_node",
        "citus_activate_node", "rebalance_table_shards",
        "citus_move_shard_placement", "citus_split_shard_by_split_points",
        "isolate_tenant_to_node", "citus_rebalance_start",
        "citus_rebalance_mesh", "citus_drain_device",
        "citus_promote_node", "citus_create_restore_point", "nextval"})

    def _replica_gate(self, stmt: ast.Statement) -> None:
        """Follower-session statement gate: refuse writes cleanly, then
        drain any shipped batches and bound the VISIBLE staleness
        before a read plans (replication/applier.ensure_fresh)."""
        if not self.replication.is_follower():
            return
        from .errors import ReadOnlyReplica
        from .replication import ensure_fresh

        if type(stmt).__name__ in self._REPLICA_WRITE_STMTS:
            raise ReadOnlyReplica(
                f"cannot execute {type(stmt).__name__} on a read "
                "replica — writes belong on the leader "
                f"({(self.replication.state() or {}).get('leader_dir')})")
        if isinstance(stmt, ast.Select) and not stmt.from_items and \
                len(stmt.items) == 1 and \
                isinstance(stmt.items[0].expr, ast.FuncCall) and \
                stmt.items[0].expr.name in self._REPLICA_WRITE_UDFS:
            raise ReadOnlyReplica(
                f"cannot execute {stmt.items[0].expr.name}() on a read "
                "replica — cluster mutations belong on the leader")
        fresh = ensure_fresh(
            self.data_dir,
            self.settings.get("replica_max_staleness_lsn"),
            counters=self.stats.counters, store=self.store)
        self._replica_stale_tls.last = fresh
        # an applied batch may have shipped DDL: adopt the leader's
        # catalog before planning (never mid-transaction — the open
        # txn pinned its snapshot)
        if fresh["applied"] and self.txn_manager.current is None:
            self.catalog.maybe_reload(
                os.path.join(self.data_dir, "catalog.json"))

    def _execute_statement(self, stmt: ast.Statement):
        from .stats.tracing import trace_span

        with trace_span("gate"):
            self._replica_gate(stmt)
        if isinstance(stmt, ast.Select):
            udf = self._try_udf(stmt)
            if udf is not None:
                return udf
            return self._execute_select(stmt)
        if isinstance(stmt, ast.SetOp):
            return self._execute_setop(stmt)
        if isinstance(stmt, ast.CreateTable):
            return self._execute_create_table(stmt)
        if isinstance(stmt, ast.CreateSequence):
            self.catalog.create_sequence(stmt.name, stmt.start,
                                         stmt.increment)
            self._save_catalog()
            return None
        if isinstance(stmt, ast.DropSequence):
            self.catalog.drop_sequence(stmt.name, stmt.if_exists)
            self._save_catalog()
            return None
        if isinstance(stmt, ast.CreateView):
            # validate the body against the CURRENT catalog before
            # persisting (parse already checked syntax)
            body = parse(stmt.sql)[0]
            if not isinstance(body, (ast.Select, ast.SetOp)):
                raise PlanningError("a view body must be a SELECT")
            if stmt.columns and isinstance(body, ast.Select) and \
                    len(stmt.columns) != len(body.items):
                raise PlanningError(
                    f"view {stmt.name!r} declares {len(stmt.columns)} "
                    f"columns but its SELECT has {len(body.items)}")
            self.catalog.create_view(stmt.name, stmt.sql, stmt.columns,
                                     stmt.or_replace)
            self._save_catalog()
            return None
        if isinstance(stmt, ast.DropView):
            self.catalog.drop_view(stmt.name, stmt.if_exists)
            self._save_catalog()
            return None
        if isinstance(stmt, ast.AlterTable):
            return self._execute_alter_table(stmt)
        if isinstance(stmt, ast.DropTable):
            return self._execute_drop_table(stmt)
        if isinstance(stmt, ast.InsertValues):
            return self._execute_insert_values(stmt)
        if isinstance(stmt, ast.InsertSelect):
            return self._execute_insert_select(stmt)
        if isinstance(stmt, (ast.Update, ast.Delete, ast.Merge)):
            return self._execute_dml(stmt)
        if isinstance(stmt, ast.CopyFrom):
            from .ingest.copy_from import copy_from

            return copy_from(self, stmt)
        if isinstance(stmt, ast.Explain):
            return self._execute_explain(stmt)
        if isinstance(stmt, ast.TransactionStmt):
            return self._execute_transaction_stmt(stmt)
        if isinstance(stmt, ast.Prepare):
            if stmt.name in self._prepared:  # PG raises here too
                raise PlanningError(
                    f"prepared statement {stmt.name!r} already exists")
            self._prepared[stmt.name] = stmt.statement
            return None
        if isinstance(stmt, ast.ExecutePrepared):
            return self._execute_prepared(stmt)
        if isinstance(stmt, ast.Deallocate):
            if stmt.name == "all":
                self._prepared.clear()
            elif self._prepared.pop(stmt.name, None) is None:
                raise PlanningError(
                    f"prepared statement {stmt.name!r} does not exist")
            return None
        if isinstance(stmt, ast.SetVariable):
            self.settings.set(stmt.name, stmt.value)
            return None
        if isinstance(stmt, ast.ShowVariable):
            from .executor.runner import ResultSet

            if stmt.name == "all":
                items = sorted(self.settings.show_all().items())
                return ResultSet(["name", "setting"],
                                 {"name": [k for k, _ in items],
                                  "setting": [str(v) for _, v in items]},
                                 len(items))
            v = self.settings.get(stmt.name)
            return ResultSet(["setting"], {"setting": [str(v)]}, 1)
        raise UnsupportedQueryError(
            f"unsupported statement {type(stmt).__name__}")

    # -- UDF surface -------------------------------------------------------
    def _try_udf(self, sel: ast.Select):
        if sel.from_items or len(sel.items) != 1:
            return None
        e = sel.items[0].expr
        if not isinstance(e, ast.FuncCall) or e.name not in _UDFS:
            return None
        args = []
        for a in e.args:
            if not isinstance(a, ast.Literal):
                raise PlanningError(f"{e.name}: arguments must be literals")
            args.append(a.value)
        from .executor.runner import ResultSet

        if e.name == "create_distributed_table":
            shard_count = int(args[2]) if len(args) > 2 else None
            self.create_distributed_table(str(args[0]), str(args[1]),
                                          shard_count)
        elif e.name == "create_reference_table":
            self.create_reference_table(str(args[0]))
        elif e.name == "citus_add_node":
            self.catalog.add_node(str(args[0]))
            self._save_catalog()
        elif e.name == "citus_remove_node":
            self.catalog.remove_node(str(args[0]))
            self._save_catalog()
        elif e.name == "citus_disable_node":
            self.catalog.disable_node(str(args[0]))
            self._save_catalog()
        elif e.name == "citus_activate_node":
            self.catalog.activate_node(str(args[0]))
            self._save_catalog()
        elif e.name == "rebalance_table_shards":
            from .operations.rebalancer import rebalance_table_shards

            moves = rebalance_table_shards(
                self.catalog, self.store,
                self.settings.get("rebalance_threshold"),
                self.settings.get("rebalance_improvement_threshold"),
                progress=self.stats.progress)
            self._save_catalog()
            return ResultSet(["moves"], {"moves": [len(moves)]}, 1)
        elif e.name == "citus_move_shard_placement":
            from .operations.shard_transfer import move_shard_placement

            move_shard_placement(self.catalog, self.store, int(args[0]),
                                 str(args[1]))
            self._save_catalog()
        elif e.name == "citus_split_shard_by_split_points":
            from .operations.shard_split import split_shard_by_split_points

            points = [int(p) for p in str(args[1]).split(",")]
            children = split_shard_by_split_points(self, int(args[0]),
                                                   points)
            return ResultSet(["new_shard_ids"],
                             {"new_shard_ids":
                              [",".join(map(str, children))]}, 1)
        elif e.name == "isolate_tenant_to_node":
            from .operations.shard_split import isolate_tenant_to_node

            tenant = args[1]
            new_shard = isolate_tenant_to_node(self, str(args[0]), tenant)
            return ResultSet(["shard_id"], {"shard_id": [new_shard]}, 1)
        elif e.name == "citus_cleanup_orphaned_resources":
            from .operations.cleanup import cleanup_registry_for

            n = cleanup_registry_for(self.data_dir).sweep(self.store,
                                                           self.catalog)
            return ResultSet(["cleaned"], {"cleaned": [n]}, 1)
        elif e.name == "citus_rebalance_start":
            job_id = self._start_background_rebalance()
            return ResultSet(["job_id"], {"job_id": [job_id]}, 1)
        elif e.name in ("citus_rebalance_wait", "citus_job_wait"):
            job_id = int(args[0]) if args else self._last_rebalance_job
            if job_id == 0:  # nothing was scheduled (already balanced)
                return ResultSet(["status"], {"status": ["done"]}, 1)
            status = self.jobs.wait(job_id)
            return ResultSet(["status"], {"status": [status.value]}, 1)
        elif e.name == "citus_job_cancel":
            self.jobs.cancel(int(args[0]))
        elif e.name == "citus_job_list":
            jobs = self.jobs.jobs()
            return ResultSet(
                ["job_id", "description", "status", "tasks"],
                {"job_id": [j.job_id for j in jobs],
                 "description": [j.description for j in jobs],
                 "status": [j.status.value for j in jobs],
                 "tasks": [len(j.tasks) for j in jobs]}, len(jobs))
        elif e.name == "citus_check_cluster_node_health":
            # health_check.c analogue: one probe row per node (device +
            # storage reachability from the controller)
            from .operations.health import check_cluster_health

            rows = check_cluster_health(self)
            return ResultSet(
                ["node_name", "is_active", "healthy"],
                {"node_name": [r[0] for r in rows],
                 "is_active": [r[1] for r in rows],
                 "healthy": [r[2] for r in rows]}, len(rows))
        elif e.name == "citus_check_cluster":
            # storage scrub behind a UDF (amcheck/pg_checksums analogue,
            # run as a background job): verify every placement copy,
            # quarantine + re-replicate corrupt ones, GC crash debris.
            # Optional arg: temp-file age floor in seconds (default:
            # scrub_temp_max_age_s).
            from .operations.scrubber import scrub_session

            age = float(args[0]) if args else None
            rep = scrub_session(self, temp_max_age_s=age)
            return ResultSet(
                ["stripes_verified", "masks_verified", "corrupt_copies",
                 "quarantined", "repaired", "unrepairable",
                 "temps_removed", "replica_dirs_removed"],
                {"stripes_verified": [rep.stripes_verified],
                 "masks_verified": [rep.masks_verified],
                 "corrupt_copies": [rep.corrupt_copies],
                 "quarantined": [rep.quarantined],
                 "repaired": [rep.repaired],
                 "unrepairable": [rep.unrepairable],
                 "temps_removed": [rep.temps_removed],
                 "replica_dirs_removed": [rep.replica_dirs_removed]}, 1)
        elif e.name == "citus_promote_node":
            # node_promotion.c analogue: demote a dead node's placements
            # so every shard's surviving replica becomes its primary
            from .operations.health import promote_node_replicas

            n = promote_node_replicas(self, str(args[0]))
            return ResultSet(["placements_demoted"],
                             {"placements_demoted": [n]}, 1)
        elif e.name == "nextval":
            v, _inc = self.catalog.sequence_nextval(str(args[0]))
            self._save_catalog()
            return ResultSet(["nextval"], {"nextval": [v]}, 1)
        elif e.name == "currval":
            v = self.catalog.sequence_currval(str(args[0]))
            return ResultSet(["currval"], {"currval": [v]}, 1)
        elif e.name == "citus_get_node_clock":
            from .transaction.clock import global_clock

            return ResultSet(["clock"], {"clock": [global_clock.now()]}, 1)
        elif e.name == "citus_tables":
            # the citus_tables view (ref: sql UDF surface, SURVEY §1.1)
            names = sorted(self.catalog.tables)
            kinds, dcols, colo, sizes, shards = [], [], [], [], []
            for t in names:
                m = self.catalog.table(t)
                kinds.append(m.method.value)
                dcols.append(m.distribution_column or "")
                colo.append(m.colocation_id)
                tshards = self.catalog.table_shards(t)
                shards.append(len(tshards))
                sizes.append(sum(
                    self.store.shard_size_bytes(t, s.shard_id)
                    for s in tshards))
            return ResultSet(
                ["table_name", "citus_table_type", "distribution_column",
                 "colocation_id", "shard_count", "table_size_bytes"],
                {"table_name": names, "citus_table_type": kinds,
                 "distribution_column": dcols, "colocation_id": colo,
                 "shard_count": shards, "table_size_bytes": sizes},
                len(names))
        elif e.name == "citus_shards":
            # the citus_shards view: one row per shard with placement
            rows: list[tuple] = []
            tables = ([str(args[0])] if args
                      else sorted(self.catalog.tables))
            for t in tables:
                for s in self.catalog.table_shards(t):
                    p = self.catalog.active_placement(s.shard_id)
                    rows.append((
                        t, s.shard_id, s.min_value, s.max_value,
                        f"device:{p.node_id}" if p else "",
                        self.store.shard_size_bytes(t, s.shard_id),
                        self.store.shard_row_count(t, s.shard_id)))
            cols = list(zip(*rows)) if rows else [[]] * 7
            return ResultSet(
                ["table_name", "shard_id", "min_value", "max_value",
                 "node", "size_bytes", "live_rows"],
                {"table_name": list(cols[0]), "shard_id": list(cols[1]),
                 "min_value": list(cols[2]), "max_value": list(cols[3]),
                 "node": list(cols[4]), "size_bytes": list(cols[5]),
                 "live_rows": list(cols[6])}, len(rows))
        elif e.name == "citus_change_feed":
            table = str(args[0]) if args else None
            from_lsn = int(args[1]) if len(args) > 1 else 0
            events = self.change_events(table, from_lsn)
            return ResultSet(
                ["lsn", "kind", "shard_id", "file", "rows"],
                {"lsn": [ev["lsn"] for ev in events],
                 "kind": [ev["kind"] for ev in events],
                 "shard_id": [ev["shard_id"] for ev in events],
                 "file": [ev["file"] for ev in events],
                 "rows": [ev.get("rows", ev.get("count", 0))
                          for ev in events]}, len(events))
        elif e.name == "citus_create_restore_point":
            from .operations.restore_point import create_restore_point

            name = create_restore_point(self, str(args[0]))
            return ResultSet(["restore_point"], {"restore_point": [name]}, 1)
        elif e.name == "citus_replication_ship":
            # leader-side: stage one batch for every registered
            # follower (the explicit counterpart of the maintenance
            # daemon's replication_ship_interval_ms duty)
            from .replication import ship_all

            rows = ship_all(self.data_dir,
                            counters=self.stats.counters)
            cols = {"follower": [r["follower"] for r in rows],
                    "status": [r["status"] for r in rows],
                    "batch_seq": [r.get("batch_seq", 0) for r in rows],
                    "files": [r.get("files", 0) for r in rows],
                    "bytes": [r.get("bytes", 0) for r in rows]}
            return ResultSet(list(cols), cols, len(rows))
        elif e.name == "citus_promote_replica":
            epoch = self.promote_replica()
            return ResultSet(["epoch"], {"epoch": [epoch]}, 1)
        elif e.name == "citus_stat_replication":
            # per-peer lag in LSNS AND BYTES — the bounded-VISIBLE-
            # staleness surface (ref: pg_stat_replication +
            # citus_get_node_clock).  Leaders report one row per
            # registered follower; followers report one row about
            # their own cursor vs their leader's journal tail.
            from .replication import (
                journal_tail_lsn,
                load_cursor,
                staleness,
            )

            state = self.replication.state()
            peers, roles, applied, lead, lag_l, lag_b, epochs = \
                [], [], [], [], [], [], []
            if state and state.get("role") == "leader":
                leader_lsn = journal_tail_lsn(self.data_dir)
                try:
                    jbytes = os.path.getsize(os.path.join(
                        self.data_dir, "cdc_changes.jsonl"))
                except OSError:
                    jbytes = 0
                for fdir in state.get("followers", []):
                    cur = load_cursor(fdir)
                    a = int(cur["applied_lsn"]) if cur else 0
                    fb = int(cur["journal_size"]) if cur else 0
                    peers.append(fdir)
                    roles.append("follower")
                    applied.append(a)
                    lead.append(leader_lsn)
                    lag_l.append(max(0, leader_lsn - a))
                    lag_b.append(max(0, jbytes - fb))
                    epochs.append(int(cur["epoch"]) if cur
                                  else int(state["epoch"]))
            elif state and state.get("role") == "follower":
                s = staleness(self.data_dir)
                cur = load_cursor(self.data_dir)
                peers.append(s["leader_dir"] or "")
                roles.append("leader")
                applied.append(s["applied_lsn"])
                lead.append(s["leader_lsn"])
                lag_l.append(s["lag_lsn"])
                lag_b.append(s["lag_bytes"])
                epochs.append(int(cur["epoch"]) if cur
                              else int(state["epoch"]))
            cols = {"peer": peers, "peer_role": roles,
                    "applied_lsn": applied, "leader_lsn": lead,
                    "lag_lsn": lag_l, "lag_bytes": lag_b,
                    "epoch": epochs}
            return ResultSet(list(cols), cols, len(peers))
        elif e.name == "citus_stat_counters":
            snap = self.stats.counters.snapshot()
            names = sorted(snap)
            return ResultSet(["name", "value"],
                             {"name": names,
                              "value": [snap[n] for n in names]}, len(names))
        elif e.name == "citus_stat_counters_reset":
            self.stats.counters.reset()
        elif e.name == "citus_stat_statements":
            entries = self.stats.queries.entries()
            return ResultSet(
                ["query", "calls", "total_time_ms", "rows"],
                {"query": [s.query for s in entries],
                 "calls": [s.calls for s in entries],
                 "total_time_ms": [round(s.total_time_ms, 3)
                                   for s in entries],
                 "rows": [s.rows for s in entries]}, len(entries))
        elif e.name == "citus_stat_statements_reset":
            self.stats.queries.reset()
        elif e.name == "citus_stat_latency":
            # per-statement-class latency histograms from the span
            # flight recorder: DDSketch buckets (α ≈ 1% relative
            # error), so the quantiles are honest without raw samples
            lrows = self.stats.tracing.latency_rows()
            lcols = ["statement_class", "calls", "mean_ms", "p50_ms",
                     "p95_ms", "p99_ms", "max_ms"]
            return ResultSet(
                lcols, {c: [r[c] for r in lrows] for c in lcols},
                len(lrows))
        elif e.name == "citus_stat_latency_reset":
            self.stats.tracing.reset_latency()
        elif e.name == "citus_stat_tenants":
            entries = self.stats.tenants.entries()
            return ResultSet(
                ["table_name", "tenant_attribute", "query_count",
                 "total_time_ms"],
                {"table_name": [s.table for s in entries],
                 "tenant_attribute": [s.tenant for s in entries],
                 "query_count": [s.query_count for s in entries],
                 "total_time_ms": [round(s.total_time_ms, 3)
                                   for s in entries]}, len(entries))
        elif e.name == "citus_stat_activity":
            entries = self.stats.activity.entries()
            # per-statement cache activity: live executor totals minus
            # the snapshot taken when the statement started (0 for
            # entries tracked before a baseline existed)
            live = (self.executor.plan_cache.hits,
                    self.executor.plan_cache.misses,
                    self.executor.feed_cache.hits,
                    self.executor.feed_cache.misses)

            def delta(a, i):
                if a.cache_base is None:
                    return 0
                return max(0, live[i] - a.cache_base[i])

            # live/peak device bytes are the data_dir-shared accountant's
            # measured ledger at snapshot time (sessions share the
            # device, so the columns repeat per row like slots_total)
            hbm_live = self.executor.accountant.live_bytes()
            hbm_peak = self.executor.accountant.peak_bytes
            return ResultSet(
                ["global_pid", "query", "state", "wait_state",
                 "queued_ms", "retries", "read_repairs",
                 "plan_cache_hits", "plan_cache_misses",
                 "feed_cache_hits", "feed_cache_misses",
                 "hbm_live_bytes", "hbm_peak_bytes"],
                {"global_pid": [a.gpid for a in entries],
                 "query": [a.query for a in entries],
                 "state": [a.state for a in entries],
                 "wait_state": [a.wait_state for a in entries],
                 "queued_ms": [round(a.queued_ms, 3) for a in entries],
                 "retries": [a.retries for a in entries],
                 "read_repairs": [a.read_repairs for a in entries],
                 "plan_cache_hits": [delta(a, 0) for a in entries],
                 "plan_cache_misses": [delta(a, 1) for a in entries],
                 "feed_cache_hits": [delta(a, 2) for a in entries],
                 "feed_cache_misses": [delta(a, 3) for a in entries],
                 "hbm_live_bytes": [hbm_live] * len(entries),
                 "hbm_peak_bytes": [hbm_peak] * len(entries)},
                len(entries))
        elif e.name == "citus_stat_wlm":
            # workload-manager snapshot: gate occupancy + one row per
            # (priority class, tenant) the shared governor has seen
            snap = self.wlm.snapshot()
            rows = snap["tenants"] or [
                {"priority": "*", "tenant": "*", "queued": 0,
                 "running": 0, "admitted_total": 0, "shed_total": 0,
                 "weight": 0}]
            return ResultSet(
                ["priority", "tenant", "queued", "running",
                 "admitted_total", "shed_total", "weight",
                 "slots_in_use", "slots_total", "feed_bytes_admitted",
                 "requests_total", "timedout_total", "canceled_total",
                 "queue_wait_ms_total"],
                {"priority": [r["priority"] for r in rows],
                 "tenant": [r["tenant"] for r in rows],
                 "queued": [r["queued"] for r in rows],
                 "running": [r["running"] for r in rows],
                 "admitted_total": [r["admitted_total"] for r in rows],
                 "shed_total": [r["shed_total"] for r in rows],
                 "weight": [r["weight"] for r in rows],
                 "slots_in_use": [snap["slots_in_use"]] * len(rows),
                 "slots_total": [snap["slots_total"]] * len(rows),
                 "feed_bytes_admitted":
                     [snap["feed_bytes_admitted"]] * len(rows),
                 "requests_total": [snap["requests_total"]] * len(rows),
                 "timedout_total": [snap["timedout_total"]] * len(rows),
                 "canceled_total": [snap["canceled_total"]] * len(rows),
                 "queue_wait_ms_total":
                     [snap["queue_wait_ms_total"]] * len(rows)},
                len(rows))
        elif e.name == "citus_stat_serving":
            # serving-layer snapshot: the shared micro-batcher's ledger
            # totals + the result cache's traffic for this data_dir
            # (one row; per-session folds live in citus_stat_counters)
            from .serving.batcher import batcher_for
            from .serving.result_cache import result_cache_for

            b = batcher_for(self.data_dir).snapshot()
            c = result_cache_for(self.data_dir).snapshot()
            cols = {
                "requests_total": b["requests_total"],
                "answered_total": b["answered_total"],
                "errored_total": b["errored_total"],
                "fallback_total": b["fallback_total"],
                "batch_dispatch_total": b["batch_dispatch_total"],
                "batched_lookups_total": b["batched_lookups_total"],
                "max_batch_seen": b["max_batch_seen"],
                "avg_batch_occupancy": b["avg_batch_occupancy"],
                "queue_depth": b["queue_depth"],
                "cache_entries": c["entries"],
                "cache_bytes": c["bytes"],
                "cache_hits_total": c["hits_total"],
                "cache_misses_total": c["misses_total"],
                "cache_invalidations_total": c["invalidations_total"],
                "cache_last_lsn": c["last_lsn"],
            }
            return ResultSet(list(cols),
                             {k: [v] for k, v in cols.items()}, 1)
        elif e.name == "citus_stat_memory":
            # device-memory snapshot: the shared accountant's measured
            # ledger (one per data_dir), this executor's degradation
            # state, and the backend allocator's own stats where the
            # platform exposes them (the cross-check; CPU test meshes
            # report none)
            from .executor.hbm import DeviceMemoryAccountant
            from .stats import counters as sc

            snap = self.executor.accountant.snapshot()
            csnap = self.stats.counters.snapshot()
            dev = DeviceMemoryAccountant.device_memory_stats()
            cols = dict(snap)
            cols["budget_bytes"] = \
                self.executor.accountant.budget_bytes(self.settings)
            cols["oom_events_total"] = csnap.get(sc.OOM_EVENTS_TOTAL, 0)
            cols["cache_evictions_total"] = \
                csnap.get(sc.CACHE_EVICTIONS_TOTAL, 0)
            cols["stream_batch_shrinks_total"] = \
                csnap.get(sc.STREAM_BATCH_SHRINKS_TOTAL, 0)
            cols["spill_passes_total"] = \
                csnap.get(sc.SPILL_PASSES_TOTAL, 0)
            cols["degradation_batch_shrink"] = \
                self.executor.oom.batch_shrink
            cols["degradation_force_stream"] = \
                self.executor.oom.force_stream
            cols["degradation_multipass_k"] = \
                self.executor.oom.multipass_k
            cols["device_bytes_in_use"] = (
                sum(d["bytes_in_use"] for d in dev) if dev else None)
            cols["device_bytes_limit"] = (
                min(d["bytes_limit"] for d in dev) if dev else None)
            cols["device_peak_bytes_in_use"] = (
                max(d["peak_bytes_in_use"] for d in dev) if dev else None)
            return ResultSet(list(cols),
                             {k: [v] for k, v in cols.items()}, 1)
        elif e.name == "citus_stat_mesh":
            # mesh snapshot: device count/platform, the catalog's
            # node↔device map (the fact every shard feed routes
            # through), cross-device shuffle volume and the measured
            # per-device HBM ledger — the one-stop view of whether the
            # cluster dimension is actually being used
            import json as _json

            import jax as _jax

            from .stats import counters as sc

            acc = self.executor.accountant
            by_dev = acc.live_bytes_by_device()
            dmap = self.catalog.node_device_map(self.n_devices)
            csnap = self.stats.counters.snapshot()
            # per-device health (active | suspect | draining | dead):
            # the ledger records non-active states by jax device id;
            # devices outside this session's (possibly shrunken) mesh
            # with no recorded state show as 'unused'
            from .distributed.mesh import mesh_device_ids

            ledger = self.catalog.device_states()
            in_mesh = set(mesh_device_ids(self.mesh))
            states = {d.id: ledger.get(
                d.id, "active" if d.id in in_mesh else "unused")
                for d in _jax.devices()}
            cols = {
                "devices": self.n_devices,
                "platform": str(_jax.default_backend()),
                "nodes": len(self.catalog.active_nodes()),
                "dead_nodes": len(self.catalog.dead_nodes()),
                "node_device_map": _json.dumps(
                    {str(k): v for k, v in sorted(dmap.items())}),
                "device_states": _json.dumps(
                    {str(k): v for k, v in sorted(states.items())}),
                "shuffle_bytes_total": csnap.get(
                    sc.SHUFFLE_BYTES_TOTAL, 0),
                "device_lost_total": csnap.get(sc.DEVICE_LOST_TOTAL, 0),
                "mesh_failovers_total": csnap.get(
                    sc.MESH_FAILOVERS_TOTAL, 0),
                "queries_rescued_total": csnap.get(
                    sc.QUERIES_RESCUED_TOTAL, 0),
                "live_bytes_by_device": _json.dumps(by_dev),
                "live_bytes_hot_device": max(by_dev, default=0),
            }
            return ResultSet(list(cols),
                             {k: [v] for k, v in cols.items()}, 1)
        elif e.name == "citus_rebalance_mesh":
            # grow the node set onto this session's mesh width and
            # spread shard placements over the new nodes (1→N scale-out
            # without reloading; operations/rebalancer.py)
            from .operations.rebalancer import rebalance_mesh

            added, moves = rebalance_mesh(
                self.catalog, self.store, self.n_devices,
                self.settings.get("rebalance_threshold"),
                progress=self.stats.progress)
            self._save_catalog()
            return ResultSet(
                ["nodes_added", "shards_moved"],
                {"nodes_added": [len(added)],
                 "shards_moved": [len(moves)]}, 1)
        elif e.name == "citus_drain_device":
            # elastic shrink, one device at a time: migrate every
            # placement off the nodes mapped to mesh device index i,
            # then take those nodes out of rotation — the device keeps
            # its mesh slot but feeds zero rows from the next plan on
            # (operations/rebalancer.py drain_device; the
            # citus_drain_node analogue for mesh slots).  In-flight
            # statements finish on their old placements (stripes stay
            # on disk); new plans route around the drained device.
            from .operations.rebalancer import drain_device

            moved, drained_nodes = drain_device(self, int(args[0]))
            self._save_catalog()
            return ResultSet(
                ["placements_moved", "nodes_drained"],
                {"placements_moved": [moved],
                 "nodes_drained": [drained_nodes]}, 1)
        elif e.name == "get_rebalance_progress":
            mons = self.stats.progress.all()
            return ResultSet(
                ["operation", "target", "progress", "total", "detail"],
                {"operation": [m.operation for m in mons],
                 "target": [m.target for m in mons],
                 "progress": [m.done_steps for m in mons],
                 "total": [m.total_steps for m in mons],
                 "detail": [m.detail for m in mons]}, len(mons))
        return ResultSet(["ok"], {"ok": [True]}, 1)

    _last_rebalance_job = 0

    def _start_background_rebalance(self) -> int:
        """citus_rebalance_start analogue: plan the moves, run them as a
        dependency-chained background job with live progress
        (utils/background_jobs.c + shard_rebalancer.c:1165)."""
        from .operations.rebalancer import plan_rebalance
        from .operations.shard_transfer import move_shard_placement

        moves = plan_rebalance(
            self.catalog, self.store,
            self.settings.get("rebalance_threshold"),
            self.settings.get("rebalance_improvement_threshold"))
        if not moves:
            return 0
        mon = self.stats.progress.create("rebalance", "background",
                                         len(moves))

        def make_move(mv):
            def run():
                target = self.catalog.nodes[mv.target_node]
                move_shard_placement(self.catalog, self.store,
                                     mv.shard_id, target.name)
                self._save_catalog()
                mon.advance(1, f"moved shard {mv.shard_id}")
            return run

        # parallelize across nodes under a per-node concurrency cap of 1:
        # a move depends only on the LAST earlier move touching either of
        # its nodes (the reference's per-node task caps,
        # citus.max_background_task_executors_per_node,
        # utils/background_jobs.c)
        tasks = []
        last_on_node: dict[int, int] = {}
        for i, mv in enumerate(moves):
            # mv.source_node is the planner's SIMULATED source — correct
            # even when one shard group moves twice in a plan (the live
            # catalog only mutates as the background moves execute)
            src = mv.source_node
            deps = sorted({last_on_node[n]
                           for n in (src, mv.target_node)
                           if n in last_on_node})
            tasks.append((make_move(mv), f"move shard {mv.shard_id}",
                          deps))
            last_on_node[src] = i
            last_on_node[mv.target_node] = i
        tasks.append((mon.finish, "finalize", list(range(len(moves)))))
        job_id = self.jobs.submit_job("rebalance", tasks)
        self._last_rebalance_job = job_id
        return job_id

    # -- DDL ---------------------------------------------------------------
    def _execute_create_table(self, stmt: ast.CreateTable):
        if self.catalog.has_table(stmt.name):
            if stmt.if_not_exists:
                return None
            raise CatalogError(f"table {stmt.name!r} already exists")
        cols = tuple(ColumnDef(c.name, sql_type_to_datatype(c.type_name),
                               nullable=not c.not_null)
                     for c in stmt.columns)
        self.catalog.create_local_table(stmt.name, TableSchema(cols))
        self._save_catalog()
        return None

    def _execute_alter_table(self, stmt: ast.AlterTable):
        """ALTER TABLE ADD/DROP/RENAME COLUMN as manifest-level schema
        evolution: stripes are immutable; columns added later read as
        NULL from older stripes, dropped columns simply leave the schema
        (reference: commands/alter_table.c — there a full table rewrite
        or catalog-only change depending on the clause)."""
        from .stats import counters as sc

        meta = self.catalog.table(stmt.table)
        schema = meta.schema
        if stmt.action == "add_column":
            if schema.has_column(stmt.column.name):
                if stmt.if_not_exists:
                    return None
                raise CatalogError(
                    f"column {stmt.column.name!r} already exists")
            new_col = ColumnDef(stmt.column.name,
                                sql_type_to_datatype(stmt.column.type_name),
                                nullable=not stmt.column.not_null)
            if stmt.column.not_null and \
                    self.store.table_row_count(stmt.table) > 0:
                raise CatalogError(
                    "cannot add a NOT NULL column to a non-empty table "
                    "(existing rows would hold NULL)")
            # guard against resurrecting a dropped/renamed-away column's
            # on-disk data under the new name
            self.store.register_column(stmt.table, new_col.name)
            new_schema = TableSchema(schema.columns + (new_col,))
        elif stmt.action == "drop_column":
            if not schema.has_column(stmt.column_name):
                if stmt.if_exists:
                    return None
                raise CatalogError(
                    f"column {stmt.column_name!r} does not exist")
            if meta.method == DistributionMethod.HASH and \
                    stmt.column_name == meta.distribution_column:
                raise CatalogError(
                    "cannot drop the distribution column")
            new_schema = TableSchema(tuple(
                c for c in schema.columns if c.name != stmt.column_name))
            if not new_schema.columns:
                raise CatalogError("cannot drop the last column")
            self.store.retire_column(stmt.table, stmt.column_name)
        elif stmt.action == "rename_column":
            if not schema.has_column(stmt.column_name):
                raise CatalogError(
                    f"column {stmt.column_name!r} does not exist")
            if schema.has_column(stmt.new_name):
                raise CatalogError(
                    f"column {stmt.new_name!r} already exists")
            if meta.method == DistributionMethod.HASH and \
                    stmt.column_name == meta.distribution_column:
                meta.distribution_column = stmt.new_name
            new_schema = TableSchema(tuple(
                ColumnDef(stmt.new_name if c.name == stmt.column_name
                          else c.name, c.dtype, nullable=c.nullable)
                for c in schema.columns))
            # stripes keep the old on-disk name; the store records the
            # mapping so reads/writes translate
            self.store.rename_column(stmt.table, stmt.column_name,
                                     stmt.new_name)
        else:
            raise UnsupportedQueryError(
                f"ALTER TABLE {stmt.action} is not supported")
        meta.schema = new_schema
        self.catalog._bump()
        self.store.bump_data_version(stmt.table)
        self._save_catalog()
        self.stats.counters.increment(sc.DDL_COMMANDS)
        return None

    def _execute_drop_table(self, stmt: ast.DropTable):
        if not self.catalog.has_table(stmt.name):
            if stmt.if_exists:
                return None
            raise CatalogError(f"table {stmt.name!r} does not exist")
        self.catalog.drop_table(stmt.name)
        self.store.drop_table_storage(stmt.name)
        self._save_catalog()
        return None

    # -- transactions ------------------------------------------------------
    def _execute_transaction_stmt(self, stmt: ast.TransactionStmt):
        if stmt.kind == "begin":
            self.txn_manager.begin()
            return None
        txn = self.txn_manager.current
        txid = txn.txid if txn is not None else None
        try:
            if stmt.kind == "commit":
                self.txn_manager.commit()
            else:
                self.txn_manager.rollback()
        finally:
            if txid is not None:
                self.locks.release_all(txid)
        return None

    def _apply_dml(self, table: str, deletes, pending) -> None:
        """Route a DML effect set: stage into the open transaction
        (visible via the read overlay, durable at COMMIT) or apply
        immediately in autocommit."""
        txn = self.txn_manager.current
        if txn is not None:
            txn.stage_dml(table, deletes, list(pending))
        else:
            self.store.apply_dml(table, deletes, list(pending))

    @contextlib.contextmanager
    def _dml_locks(self, table: str, shards_fn):
        """Exclusive (table, shard) locks around a DML read-modify-apply
        window (AcquireExecutorShardLocksForExecution analogue,
        executor/distributed_execution_locks.c).  Transaction locks are
        held to COMMIT/ROLLBACK (2PL); autocommit locks release at
        statement end.  The deadlock victim's transaction rolls back
        automatically, like the reference canceling the youngest backend.

        `shards_fn` re-derives the target shard list from the CURRENT
        catalog: a concurrent shard split commits its catalog while we
        wait on the parent's lock, and writing via the pre-wait routing
        would land rows in the dropped parent (lost).  The loop adopts
        the on-disk catalog after acquiring and re-derives until stable;
        locks are only ever ADDED (never released mid-transaction — 2PL),
        stale ones release with the rest at statement/transaction end.
        Yields the stable shard list."""
        from .transaction.clock import global_clock
        from .transaction.locks import DeadlockDetectedError

        txn = self.txn_manager.current
        txid = txn.txid if txn is not None else global_clock.now()
        try:
            while True:
                version = self.catalog.version
                shards = shards_fn()
                for sid in sorted(s.shard_id for s in shards):
                    self.locks.acquire(txid, (table, sid))
                self.catalog.maybe_reload(
                    os.path.join(self.data_dir, "catalog.json"))
                if self.catalog.version == version:
                    break
            # see the latest committed state from sessions sharing this
            # data_dir (manifest cache may predate the lock wait)
            self.store.refresh(table)
            yield shards
        except DeadlockDetectedError:
            if txn is not None and self.txn_manager.current is txn:
                self.txn_manager.rollback()
                self.locks.release_all(txid)
            raise
        finally:
            if txn is None:
                self.locks.release_all(txid)

    # -- DML ---------------------------------------------------------------
    def _execute_insert_values(self, stmt: ast.InsertValues):
        from .ingest.copy_from import insert_rows

        meta = self.catalog.table(stmt.table)
        columns = stmt.columns or tuple(meta.schema.names)

        def is_nextval(e):
            return (isinstance(e, ast.FuncCall) and e.name == "nextval"
                    and len(e.args) == 1
                    and isinstance(e.args[0], ast.Literal))

        # sequence values: allocate each sequence's whole range in ONE
        # catalog bump (the per-node range allocation the reference does
        # via worker sequence propagation, commands/sequence.c)
        seq_counts: dict[str, int] = {}
        for row in stmt.rows:
            for e in row:
                if is_nextval(e):
                    name = str(e.args[0].value)
                    seq_counts[name] = seq_counts.get(name, 0) + 1
        seq_iters: dict[str, object] = {}
        if seq_counts:
            for name, cnt in seq_counts.items():
                first, step = self.catalog.sequence_nextval(name, cnt)
                seq_iters[name] = iter(
                    range(first, first + step * cnt, step))
            self._save_catalog()

        rows = []
        for row in stmt.rows:
            if len(row) != len(columns):
                raise PlanningError("INSERT row arity mismatch")
            values = []
            for e in row:
                if is_nextval(e):
                    values.append(next(seq_iters[str(e.args[0].value)]))
                    continue
                if not isinstance(e, ast.Literal):
                    raise PlanningError("INSERT values must be literals")
                if e.type_hint == "date":
                    from .types import date_to_days

                    values.append(date_to_days(str(e.value)))
                else:
                    values.append(e.value)
            rows.append(values)
        return insert_rows(self, stmt.table, list(columns), rows)

    def _execute_insert_select(self, stmt: ast.InsertSelect):
        """Array-path INSERT..SELECT (colocated pushdown / repartition
        modes, executor/insert_select.py); falls back to the row-based
        pull-to-coordinator mode only for shapes the raw path rejects."""
        from .executor.insert_select import execute_insert_select

        if isinstance(stmt.query, ast.SetOp):
            # compound source: materialize the set operation, then insert
            # from the temp (recursive-planning route)
            cleanup: list[str] = []
            try:
                sel = self._setop_select(stmt.query, cleanup, {})
                return self._execute_insert_select(
                    dc_replace(stmt, query=sel))
            finally:
                for t in cleanup:
                    self._drop_temp(t)
        try:
            result, _mode = execute_insert_select(self, stmt)
            return result
        except (PlanningError, UnsupportedQueryError):
            from .ingest.copy_from import insert_rows
            from .stats import counters as sc

            result = self._execute_select(stmt.query)
            meta = self.catalog.table(stmt.table)
            columns = list(stmt.columns or meta.schema.names)
            rows = [list(r) for r in result.rows()]
            self.stats.counters.increment(sc.INSERT_SELECT_PULL)
            return insert_rows(self, stmt.table, columns, rows)

    def _execute_dml(self, stmt):
        """UPDATE / DELETE / MERGE — router-planned modify commands
        (CreateModifyPlan / merge_planner analogues).  Subqueries in the
        WHERE clause go through recursive planning first, like SELECT."""
        from .executor.dml import execute_delete, execute_merge, execute_update

        cleanup: list[str] = []
        try:
            if isinstance(stmt, (ast.Update, ast.Delete)) and \
                    stmt.where is not None:
                stmt = dc_replace(stmt, where=self._rewrite_expr(
                    stmt.where, cleanup, {}))
            if isinstance(stmt, ast.Update):
                return execute_update(self, stmt)
            if isinstance(stmt, ast.Delete):
                return execute_delete(self, stmt)
            return execute_merge(self, stmt)
        finally:
            for t in cleanup:
                self._drop_temp(t)

    # -- SELECT ------------------------------------------------------------
    def _serving_cache(self):
        """The shared per-data_dir result cache, or None when serving is
        off, the byte budget is zero, or this session is inside an open
        transaction (staged overlay rows are session-private — neither
        a fill nor a hit may cross the transaction boundary)."""
        if self.txn_manager.current is not None:
            return None
        if not self.settings.get("serving_enabled") or \
                self.settings.get("serving_result_cache_bytes") <= 0:
            return None
        if self._result_cache_handle is None:
            from .serving.result_cache import acquire_result_cache

            with self._result_cache_mu:
                if self._result_cache_handle is None:
                    self._result_cache_handle = acquire_result_cache(
                        self.data_dir)
        return self._result_cache_handle

    def _execute_select(self, sel: ast.Select, params: tuple = ()):
        from .stats import counters as sc

        # serving result cache: a repeated read statement serves from
        # the shared LRU, provably as-of the latest journaled LSN for
        # every table it reads (CDC-driven invalidation + the manifest-
        # identity backstop — serving/result_cache.py, ROADMAP item 3)
        from .stats.tracing import trace_span

        fill = None
        cache = self._serving_cache()
        if cache is not None:
            from .serving.result_cache import cache_key

            with trace_span("serving.cache_lookup"):
                keyed = cache_key(sel, params, self.catalog,
                                  self.settings, _UDFS)
                if keyed is not None:
                    key, tables = keyed
                    hit, d_inv = cache.lookup(
                        key, self.store.manifest_stat_sig)
                    if d_inv:  # this statement's poll did the dropping
                        self.stats.counters.increment(
                            sc.SERVING_CACHE_INVALIDATIONS_TOTAL, d_inv)
                    if hit is not None:
                        self.stats.counters.increment(
                            sc.SERVING_CACHE_HITS_TOTAL)
                        # fresh metadata, shared (immutable) column
                        # arrays: a cached answer did no device work
                        # of its own
                        return dc_replace(hit, retries=0,
                                          device_rows_scanned=0,
                                          streamed_batches=0)
                    self.stats.counters.increment(
                        sc.SERVING_CACHE_MISSES_TOTAL)
                    # freshness tokens captured BEFORE execution: a
                    # write landing mid-execution invalidates this
                    # fill (epoch) or the entry itself (manifest
                    # identity re-check)
                    fill = (key, tables,
                            {t: self.store.manifest_stat_sig(t)
                             for t in tables},
                            cache.fill_token())
        plan, cleanup = self._plan_select(sel, params)
        with trace_span("route"):
            self._count_plan_shape(plan)
        try:
            result = self.executor.execute_plan(plan)
        finally:
            for t in cleanup:
                self._drop_temp(t)
        if fill is not None:
            key, tables, sigs, token = fill
            cache.put(key, result, tables, sigs, token,
                      self.settings.get("serving_result_cache_bytes"))
        return result

    # -- PREPARE / EXECUTE -------------------------------------------------
    def _execute_prepared(self, stmt: "ast.ExecutePrepared"):
        """EXECUTE name(args): SELECTs bind args as BParam placeholders so
        the compiled mesh program is generic over the values (one compile
        serves every EXECUTE — the reference's cached shard plans,
        planner/local_plan_cache.c); other statement kinds substitute the
        literals into the AST (no device compile to reuse there)."""
        target = self._prepared.get(stmt.name)
        if target is None:
            raise PlanningError(
                f"prepared statement {stmt.name!r} does not exist")
        for a in stmt.args:
            if not isinstance(a, ast.Literal):
                raise PlanningError("EXECUTE arguments must be literals")
        if isinstance(target, ast.Select):
            return self._execute_select(target, params=stmt.args)
        return self._execute_statement(
            _substitute_params(target, stmt.args))

    def _execute_subselect(self, sel: ast.Select):
        """Nested (recursive-planning / MERGE-source) execution: counts as
        a subplan, not as user query traffic."""
        from .stats.tracing import trace_span

        with trace_span("subplan"):
            return self._run_subplan(sel)

    def _run_subplan(self, sel: ast.Select):
        from .stats import counters as sc

        self.stats.counters.increment(sc.SUBPLANS_EXECUTED)
        plan, cleanup = self._plan_select(sel)
        try:
            return self.executor.execute_plan(plan)
        finally:
            for t in cleanup:
                self._drop_temp(t)

    def _count_plan_shape(self, plan: QueryPlan) -> None:
        from .executor.feed import walk_plan
        from .planner.plan import JoinNode, ScanNode
        from .stats import counters as sc

        scans = [n for n in walk_plan(plan.root) if isinstance(n, ScanNode)]
        repartition = any(
            isinstance(n, JoinNode) and n.strategy.startswith("repart")
            for n in walk_plan(plan.root))
        single_shard = all(n.pruned_shards is not None
                           and len(n.pruned_shards) <= 1 for n in scans)
        if repartition:
            self.stats.counters.increment(sc.QUERIES_REPARTITION)
        if single_shard and scans:
            self.stats.counters.increment(sc.QUERIES_SINGLE_SHARD)
        else:
            self.stats.counters.increment(sc.QUERIES_MULTI_SHARD)

    def _plan_select(self, sel: ast.Select,
                     params: tuple = ()) -> tuple[QueryPlan, list[str]]:
        from .stats.tracing import trace_span

        cleanup: list[str] = []
        with trace_span("plan"):
            prev = getattr(self._params_tls, "value", ())
            self._params_tls.value = params
            try:
                sel = self._recursive_plan(sel, cleanup)
            finally:
                self._params_tls.value = prev
            binder = Binder(self.catalog, _StoreDicts(self.store),
                            params=params, counters=self.stats.counters)
            bound = binder.bind_select(sel)
            planner = DistributedPlanner(
                self.catalog, _StoreStats(self.store), self.n_devices,
                self.settings.get("enable_repartition_joins"),
                dicts=_StoreDicts(self.store))
            plan = planner.plan(bound)
        if self.settings.get("log_distributed_plans"):
            import sys

            for line in format_plan(plan, self.catalog, self.settings):
                print(line, file=sys.stderr)
        return plan, cleanup

    def _execute_explain(self, stmt: ast.Explain):
        from .executor.runner import ResultSet

        target = stmt.statement
        params: tuple = ()
        if isinstance(target, ast.ExecutePrepared):
            # EXPLAIN EXECUTE name(args): show the generic plan
            prepared = self._prepared.get(target.name)
            if prepared is None:
                raise PlanningError(
                    f"prepared statement {target.name!r} does not exist")
            if not isinstance(prepared, ast.Select):
                raise UnsupportedQueryError(
                    "EXPLAIN EXECUTE supports prepared SELECTs only")
            params = target.args
            target = prepared
        if not isinstance(target, ast.Select):
            raise UnsupportedQueryError("EXPLAIN supports SELECT only")
        plan, cleanup = self._plan_select(target, params)
        try:
            lines = format_plan(plan, self.catalog, self.settings)
            if stmt.analyze:
                import time

                from .stats import counters as sc

                from .storage import integrity as _integrity

                snap0 = self.stats.counters.snapshot()
                skipped0 = snap0.get(sc.CHUNKS_SKIPPED, 0)
                pc, fc = self.executor.plan_cache, self.executor.feed_cache
                cache0 = (pc.hits, pc.misses, fc.hits, fc.misses)
                ibase0 = _integrity.snapshot()
                t0 = time.perf_counter()
                result = self.executor.execute_plan(plan)
                elapsed = time.perf_counter() - t0
                lines.append(f"Execution Time: {elapsed * 1000:.2f} ms")
                # per-phase wall-clock attribution from this
                # statement's own span trace (the EXPLAIN ANALYZE
                # statement is the traced unit; its plan/feed/compile/
                # dispatch spans are already closed at this point)
                from .stats.tracing import (
                    current_root,
                    format_timing_line,
                )

                troot = current_root()
                if troot is not None:
                    lines.append(f"{explain_tag('Timing')}: "
                                 + format_timing_line(troot))
                else:
                    # no trace for THIS statement: trace_enabled off,
                    # or the sampling knobs skipped its tree — saying
                    # just "off" would mislead an operator of a live
                    # (sampled) system
                    lines.append(
                        f"{explain_tag('Timing')}: "
                        f"total={elapsed * 1000:.2f}ms "
                        "(no trace: tracing off or sampled out)")
                lines.append(f"Rows: {result.row_count}"
                             + (f" (capacity retries: {result.retries})"
                                if result.retries else ""))
                skipped = self.stats.counters.snapshot().get(
                    sc.CHUNKS_SKIPPED, 0) - skipped0
                if skipped:
                    lines.append(
                        f"{explain_tag('Chunks Skipped')}: {skipped}")
                if result.device_rows_scanned:
                    lines.append(
                        f"{explain_tag('Device Rows Scanned')}: "
                        f"{result.device_rows_scanned}")
                if result.streamed_batches:
                    lines.append(
                        f"{explain_tag('Streamed Execution')}: "
                        f"{result.streamed_batches} batches")
                # mesh trip: per-device rows in/out and the statement's
                # static all_to_all volume (counter delta, the Chunks
                # Skipped pattern) — whether the cluster dimension did
                # real work is auditable from one EXPLAIN ANALYZE
                d_shuf = self.stats.counters.snapshot().get(
                    sc.SHUFFLE_BYTES_TOTAL, 0) - snap0.get(
                    sc.SHUFFLE_BYTES_TOTAL, 0)
                rows_in = result.device_rows_in
                rows_out = result.device_rows
                lines.append(
                    f"{explain_tag('Mesh')}: devices={self.n_devices} "
                    f"rows_in={rows_in if rows_in is not None else 'n/a'}"
                    f" rows_out="
                    f"{rows_out if rows_out is not None else 'n/a'} "
                    f"all_to_all_bytes={d_shuf}")
                # this statement's deltas (the Chunks Skipped pattern),
                # plus session totals clearly labeled as such — a clean
                # statement in a battle-scarred session must not read
                # as if IT hit the failures
                snap = self.stats.counters.snapshot()
                d_r = snap.get(sc.RETRIES_TOTAL, 0) - \
                    snap0.get(sc.RETRIES_TOTAL, 0)
                d_f = snap.get(sc.FAILOVERS_TOTAL, 0) - \
                    snap0.get(sc.FAILOVERS_TOTAL, 0)
                # storage integrity: what THIS execution verified /
                # repaired (deltas of the module-wide accounting), plus
                # session totals like the Resilience line
                idelta = _integrity.delta(ibase0)
                # this statement's integrity traffic folds into the
                # session counters only after _execute_admitted returns
                # (execute()'s finally), so add it here — the totals
                # must include the statement being explained
                sv_total = (snap.get(sc.STRIPES_VERIFIED_TOTAL, 0)
                            + idelta["stripes_verified"])
                rr_total = (snap.get(sc.READ_REPAIRS_TOTAL, 0)
                            + idelta["read_repairs"])
                lines.append(
                    f"{explain_tag('Integrity')}: stripes verified="
                    f"{idelta['stripes_verified']} read repairs="
                    f"{idelta['read_repairs']} corruption detected="
                    f"{idelta['corruption_detected']} (session totals: "
                    f"stripes_verified_total={sv_total} "
                    f"read_repairs_total={rr_total})")
                # device-memory trip: this statement's OOM/degradation
                # deltas (the Chunks Skipped pattern) + the shared
                # accountant's measured ledger so memory pressure is
                # auditable from one EXPLAIN ANALYZE
                d_oom = snap.get(sc.OOM_EVENTS_TOTAL, 0) - \
                    snap0.get(sc.OOM_EVENTS_TOTAL, 0)
                d_ev = snap.get(sc.CACHE_EVICTIONS_TOTAL, 0) - \
                    snap0.get(sc.CACHE_EVICTIONS_TOTAL, 0)
                d_sp = snap.get(sc.SPILL_PASSES_TOTAL, 0) - \
                    snap0.get(sc.SPILL_PASSES_TOTAL, 0)
                msnap = self.executor.accountant.snapshot()
                lines.append(
                    f"{explain_tag('Memory')}: "
                    f"oom_events={d_oom} cache_evictions={d_ev} "
                    f"spill_passes={d_sp} "
                    f"live={msnap['live_bytes']} "
                    f"peak={msnap['peak_bytes']} "
                    f"(session totals: oom_events_total="
                    f"{snap.get(sc.OOM_EVENTS_TOTAL, 0)} "
                    "stream_batch_shrinks_total="
                    f"{snap.get(sc.STREAM_BATCH_SHRINKS_TOTAL, 0)} "
                    "spill_passes_total="
                    f"{snap.get(sc.SPILL_PASSES_TOTAL, 0)})")
                d_dl = snap.get(sc.DEVICE_LOST_TOTAL, 0) - \
                    snap0.get(sc.DEVICE_LOST_TOTAL, 0)
                d_mf = snap.get(sc.MESH_FAILOVERS_TOTAL, 0) - \
                    snap0.get(sc.MESH_FAILOVERS_TOTAL, 0)
                lines.append(
                    f"{explain_tag('Resilience')}: "
                    f"retries={d_r} failovers={d_f} "
                    f"devices_lost={d_dl} mesh_failovers={d_mf} "
                    "(session totals: retries_total="
                    f"{snap.get(sc.RETRIES_TOTAL, 0)} failovers_total="
                    f"{snap.get(sc.FAILOVERS_TOTAL, 0)} timeouts_total="
                    f"{snap.get(sc.TIMEOUTS_TOTAL, 0)} "
                    "faults_injected_total="
                    f"{snap.get(sc.FAULTS_INJECTED_TOTAL, 0)} "
                    "device_lost_total="
                    f"{snap.get(sc.DEVICE_LOST_TOTAL, 0)} "
                    "mesh_failovers_total="
                    f"{snap.get(sc.MESH_FAILOVERS_TOTAL, 0)} "
                    "queries_rescued_total="
                    f"{snap.get(sc.QUERIES_RESCUED_TOTAL, 0)})")
                # this statement's plan/feed-cache traffic (the
                # counters live on PlanCache/FeedCache; deltas follow
                # the Chunks Skipped pattern), plus session totals so
                # warm-vs-cold is auditable from one EXPLAIN ANALYZE
                # the executable-cache hit state rides the same line:
                # exec-cache hits are restart-survival loads (a compile
                # skipped by deserializing a persisted executable),
                # deduped are compiles another session led
                d_ech = snap.get(sc.EXEC_CACHE_HITS_TOTAL, 0) - \
                    snap0.get(sc.EXEC_CACHE_HITS_TOTAL, 0)
                d_ecm = snap.get(sc.EXEC_CACHE_MISSES_TOTAL, 0) - \
                    snap0.get(sc.EXEC_CACHE_MISSES_TOTAL, 0)
                d_ecr = snap.get(sc.EXEC_CACHE_REJECTS_TOTAL, 0) - \
                    snap0.get(sc.EXEC_CACHE_REJECTS_TOTAL, 0)
                d_dd = snap.get(sc.COMPILES_DEDUPED_TOTAL, 0) - \
                    snap0.get(sc.COMPILES_DEDUPED_TOTAL, 0)
                lines.append(
                    f"{explain_tag('Caches')}: plan-cache hits="
                    f"{pc.hits - cache0[0]} misses="
                    f"{pc.misses - cache0[1]}  feed-cache hits="
                    f"{fc.hits - cache0[2]} misses="
                    f"{fc.misses - cache0[3]}  exec-cache hits="
                    f"{d_ech} misses={d_ecm} rejects={d_ecr} "
                    f"deduped={d_dd} (session totals: plan "
                    f"{pc.hits}/{pc.misses}, feed {fc.hits}/{fc.misses}"
                    f" hits/misses, feed invalidations="
                    f"{fc.invalidations}, exec-cache "
                    f"{snap.get(sc.EXEC_CACHE_HITS_TOTAL, 0)}/"
                    f"{snap.get(sc.EXEC_CACHE_MISSES_TOTAL, 0)} "
                    "hits/misses, warmup_compiles_total="
                    f"{snap.get(sc.WARMUP_COMPILES_TOTAL, 0)})")
                # this statement's trip through the admission gate (the
                # EXPLAIN ANALYZE statement itself was the admitted
                # unit), plus session totals like the Resilience line
                info = getattr(self._wlm_tls, "last", None)
                w_adm = snap.get(sc.WLM_ADMITTED_TOTAL, 0)
                w_q = snap.get(sc.WLM_QUEUED_TOTAL, 0)
                w_s = snap.get(sc.WLM_SHED_TOTAL, 0)
                if info is None:
                    lines.append(
                        f"{explain_tag('Workload')}: "
                        "exempt (fast-path/utility or wlm "
                        "disabled) (session totals: wlm_admitted_total="
                        f"{w_adm} wlm_queued_total={w_q} "
                        f"wlm_shed_total={w_s})")
                else:
                    lines.append(
                        f"{explain_tag('Workload')}: "
                        f"class={info['priority']} "
                        f"tenant={info['tenant']} "
                        f"queued_ms={info['queued_ms']:.1f} "
                        f"slots={info['slots_in_use']}/"
                        f"{info['slots_total']} "
                        f"feed_bytes={info['feed_bytes']} "
                        f"(session totals: wlm_admitted_total={w_adm} "
                        f"wlm_queued_total={w_q} wlm_shed_total={w_s})")
                # serving layer: this statement's micro-batch trip
                # (counter deltas, Chunks Skipped pattern) + whether its
                # result is cache-resident, + the shared layer's batch
                # occupancy so the amortization is auditable inline
                if not self.settings.get("serving_enabled"):
                    lines.append(f"{explain_tag('Serving')}: off")
                else:
                    from .serving.batcher import batcher_for

                    bsnap = batcher_for(self.data_dir).snapshot()
                    d_bl = snap.get(sc.SERVING_BATCHED_LOOKUPS_TOTAL, 0) \
                        - snap0.get(sc.SERVING_BATCHED_LOOKUPS_TOTAL, 0)
                    d_bd = snap.get(sc.SERVING_BATCH_DISPATCH_TOTAL, 0) \
                        - snap0.get(sc.SERVING_BATCH_DISPATCH_TOTAL, 0)
                    rcache = self._serving_cache()
                    cstate = "off"
                    if rcache is not None:
                        from .serving.result_cache import cache_key

                        keyed = cache_key(target, params, self.catalog,
                                          self.settings, _UDFS)
                        if keyed is None:
                            cstate = "uncacheable"
                        elif rcache.probe(keyed[0]):
                            cstate = "cached"
                        else:
                            cstate = "uncached"
                    ch = snap.get(sc.SERVING_CACHE_HITS_TOTAL, 0)
                    cm = snap.get(sc.SERVING_CACHE_MISSES_TOTAL, 0)
                    lines.append(
                        f"{explain_tag('Serving')}: "
                        f"batched lookups={d_bl} dispatches led={d_bd} "
                        f"result-cache={cstate} (layer: avg batch "
                        f"occupancy={bsnap['avg_batch_occupancy']} "
                        f"max_batch_seen={bsnap['max_batch_seen']}; "
                        f"session totals: cache hits={ch} misses={cm})")
                # replication: this session's role and, on a follower,
                # the staleness the read gate saw for THIS statement
                # (never silently old rows — the lag is auditable here)
                rstate = self.replication.state()
                if rstate is not None:
                    role = rstate.get("role")
                    if role == "follower":
                        gate = getattr(self._replica_stale_tls, "last",
                                       None) or {}
                        lines.append(
                            f"{explain_tag('Replication')}: "
                            f"role=follower epoch={rstate['epoch']} "
                            f"applied_lsn={gate.get('applied_lsn', 0)} "
                            f"lag_lsn={gate.get('lag_lsn', 0)} "
                            f"lag_bytes={gate.get('lag_bytes', 0)} "
                            "(bound: replica_max_staleness_lsn="
                            f"{self.settings.get('replica_max_staleness_lsn')})")
                    else:
                        lines.append(
                            f"{explain_tag('Replication')}: "
                            f"role=leader epoch={rstate['epoch']} "
                            f"followers={len(rstate.get('followers', []))}")
            return ResultSet(["QUERY PLAN"], {"QUERY PLAN": lines},
                             len(lines))
        finally:
            for t in cleanup:
                self._drop_temp(t)

    # -- recursive planning ------------------------------------------------
    def _sub_params(self, node):
        """Substitute EXECUTE args into a subquery AST before it runs as
        a subplan (subplans execute ahead of outer binding, so $n must
        resolve here; the OUTER query's params stay symbolic for the
        generic plan)."""
        args = getattr(self._params_tls, "value", ())
        return _substitute_params(node, args) if args else node

    def _recursive_plan(self, sel: ast.Select, cleanup: list[str],
                        cte_scope: dict[str, str] | None = None) -> ast.Select:
        from .planner.decorrelate import decorrelate_select

        cte_scope = dict(cte_scope or {})
        for cte in sel.ctes:
            temp = self._query_to_temp(cte.query, cleanup, cte_scope,
                                       cte.column_names)
            cte_scope[cte.name] = temp

        def columns_of(name: str):
            name = cte_scope.get(name, name)
            if not self.catalog.has_table(name):
                return None
            return frozenset(
                c.name for c in self.catalog.table(name).schema.columns)

        sel = decorrelate_select(sel, columns_of)
        sel = self._rewrite_approx_percentile(sel, cleanup, cte_scope)
        from .planner.decorrelate import rewrite_multi_distinct

        def column_nullable(ref: ast.ColumnRef):
            """Can this plain column ref hold NULLs?  Schema nullability
            refined by the EXACT manifest null-count rollup (a nullable
            column whose committed data has zero NULLs is safe to join
            on).  None = unresolvable/ambiguous."""
            found = None
            for fi in sel.from_items:
                if not isinstance(fi, ast.TableRef):
                    continue
                name = cte_scope.get(fi.name, fi.name)
                if ref.table is not None and \
                        (fi.alias or fi.name) != ref.table:
                    continue
                if not self.catalog.has_table(name):
                    continue
                schema = self.catalog.table(name).schema
                if schema.has_column(ref.name):
                    if found is not None:
                        return None  # ambiguous
                    nullable = schema.column(ref.name).nullable
                    if nullable:
                        has = self.store.column_has_nulls(name, ref.name)
                        nullable = True if has is None else has
                    found = nullable
            return found

        sel = rewrite_multi_distinct(sel, column_nullable)
        new_from = tuple(self._rewrite_from(fi, cleanup, cte_scope)
                         for fi in sel.from_items)
        rewrite = lambda e: self._rewrite_expr(e, cleanup, cte_scope)  # noqa: E731
        new_semis = tuple(
            ast.SemiJoin(sj.join_type,
                         self._rewrite_from(sj.item, cleanup, cte_scope),
                         rewrite(sj.condition))
            for sj in sel.semi_joins)
        return ast.Select(
            items=tuple(ast.SelectItem(rewrite(i.expr), i.alias)
                        for i in sel.items),
            from_items=new_from,
            where=rewrite(sel.where) if sel.where is not None else None,
            group_by=tuple(rewrite(g) for g in sel.group_by),
            having=rewrite(sel.having) if sel.having is not None else None,
            order_by=tuple(ast.OrderItem(rewrite(o.expr), o.descending,
                                         o.nulls_first)
                           for o in sel.order_by),
            limit=sel.limit, offset=sel.offset, distinct=sel.distinct,
            ctes=(), semi_joins=new_semis)

    def _rewrite_from(self, fi: ast.FromItem, cleanup, cte_scope):
        if isinstance(fi, ast.TableRef):
            if fi.name in cte_scope:
                return ast.TableRef(cte_scope[fi.name],
                                    fi.alias or fi.name)
            view = self.catalog.views.get(fi.name)
            if view is not None:
                # expand like a derived table: materialize the view body
                # (fresh scope — view bodies bind to base tables, never
                # to the referencing statement's CTEs).  A thread-local
                # stack guards against self/mutually-recursive views
                # (creatable because CREATE VIEW only parses the body)
                stack = getattr(self._view_tls, "stack", None)
                if stack is None:
                    stack = self._view_tls.stack = []
                if fi.name in stack:
                    raise PlanningError(
                        f"infinite recursion detected in view "
                        f"{fi.name!r}")
                stack.append(fi.name)
                try:
                    body = parse(view["sql"])[0]
                    temp = self._query_to_temp(body, cleanup, {},
                                               tuple(view["columns"]))
                finally:
                    stack.pop()
                return ast.TableRef(temp, fi.alias or fi.name)
            return fi
        if isinstance(fi, ast.SubqueryRef):
            temp = self._query_to_temp(fi.query, cleanup, cte_scope)
            return ast.TableRef(temp, fi.alias)
        if isinstance(fi, ast.Join):
            return ast.Join(fi.join_type,
                            self._rewrite_from(fi.left, cleanup, cte_scope),
                            self._rewrite_from(fi.right, cleanup, cte_scope),
                            (self._rewrite_expr(fi.condition, cleanup,
                                                cte_scope)
                             if fi.condition is not None else None),
                            fi.using_cols)
        return fi

    def _rewrite_approx_percentile(self, sel: ast.Select, cleanup,
                                   cte_scope) -> ast.Select:
        """approx_percentile(col, q) → DDSketch bucket pre-pass.

        The device runs ``group by (G…, dd_bucket(col)) → count(*)``
        over the same FROM/WHERE — the log-domain buckets ARE the
        mergeable quantile sketch (per-shard counts add through the
        ordinary aggregate split, the way HLL registers merge by max),
        with a RELATIVE error bound α = (γ-1)/(γ+1) ≈ 1% that one
        outlier cannot degrade (ops/sketches.py).  The host folds the
        per-(group, bucket) counts into quantile values:

        * global: the value replaces the call as a constant wrapped in
          max() — one row, NULL over an empty input.
        * GROUP BY: per-group values materialize as a temp reference
          table (g…, pctl) joined back into the query on the group
          keys; the call becomes max(pctl) over the (unique-per-group)
          joined column.

        Reference: percentile → worker tdigest + coordinator merge,
        multi_logical_optimizer.c:2046."""
        from .planner.decorrelate import _map_children
        from .ops.sketches import dd_quantile

        calls = [n for it in sel.items for n in ast.walk_expr(it.expr)
                 if isinstance(n, ast.FuncCall)
                 and n.name == "approx_percentile"]
        if not calls:
            return sel
        if sel.distinct:
            raise UnsupportedQueryError(
                "approx_percentile cannot combine with SELECT DISTINCT")
        group_keys = list(sel.group_by)
        for g in group_keys:
            if not isinstance(g, ast.ColumnRef):
                raise UnsupportedQueryError(
                    "approx_percentile with GROUP BY requires plain "
                    "column group keys")
        parsed: list[tuple[ast.FuncCall, ast.ColumnRef, float]] = []
        for call in calls:
            if call.window is not None or call.distinct or \
                    len(call.args) != 2:
                raise UnsupportedQueryError(
                    "approx_percentile(column, quantile) expects two "
                    "arguments")
            col, qlit = call.args
            if not (isinstance(qlit, ast.Literal)
                    and isinstance(qlit.value, (int, float))
                    and 0.0 <= float(qlit.value) <= 1.0):
                raise UnsupportedQueryError(
                    "approx_percentile quantile must be a literal in "
                    "[0, 1]")
            if not isinstance(col, ast.ColumnRef):
                raise UnsupportedQueryError(
                    "approx_percentile argument must be a plain column")
            parsed.append((call, col, float(qlit.value)))

        repl: dict[ast.FuncCall, ast.Expr] = {}
        extra_from: list[ast.FromItem] = []
        extra_where: list[ast.Expr] = []
        # one pre-pass per distinct sketched column; every quantile over
        # that column reads the same (group, bucket) counts
        by_col: dict[ast.ColumnRef, list[tuple[ast.FuncCall, float]]] = {}
        for call, col, q in parsed:
            by_col.setdefault(col, []).append((call, q))
        for col, wants in by_col.items():
            bucket = ast.FuncCall("__dd_bucket", (col,))
            g_items = tuple(ast.SelectItem(g, f"g{i}")
                            for i, g in enumerate(group_keys))
            hist = ast.Select(
                items=g_items + (
                    ast.SelectItem(bucket, "hb"),
                    ast.SelectItem(
                        ast.FuncCall("count", (), star=True), "c")),
                from_items=sel.from_items, where=sel.where,
                group_by=tuple(group_keys) + (bucket,),
                # decorrelated EXISTS filters must apply here too
                semi_joins=sel.semi_joins)
            inner = self._recursive_plan(hist, cleanup, cte_scope)
            result = self._execute_subselect(self._sub_params(inner))
            nk = len(group_keys)
            # NULL column values form a NULL bucket group: percentile
            # ignores NULLs (PG semantics), so drop it
            rows = [r for r in result.rows() if r[nk] is not None]
            if not group_keys:
                keys = np.asarray([r[0] for r in rows], dtype=np.int64)
                cnts = np.asarray([r[1] for r in rows], dtype=np.int64)
                for call, q in wants:
                    repl[call] = ast.FuncCall(
                        "max", (ast.Literal(dd_quantile(keys, cnts, q)),))
                continue
            # grouped: fold per group tuple.  Groups whose sketched
            # column is ALL NULL appear only in the dropped NULL-bucket
            # rows — they must still produce an output row (with a NULL
            # percentile, PG semantics), so collect group tuples from
            # the UNFILTERED result
            per_group: dict[tuple, list[tuple[int, int]]] = {}
            for r in rows:
                per_group.setdefault(tuple(r[:nk]), []).append(
                    (int(r[nk]), int(r[nk + 1])))
            gtuples = []
            seen_g = set()
            for r in result.rows():
                g = tuple(r[:nk])
                if g not in seen_g:
                    seen_g.add(g)
                    gtuples.append(g)
            pctls: list[list] = []  # per want, per group tuple
            for call, q in wants:
                vals = []
                for g in gtuples:
                    pairs = per_group.get(g)
                    if not pairs:
                        vals.append(None)  # all-NULL group
                        continue
                    keys = np.asarray([k for k, _ in pairs],
                                      dtype=np.int64)
                    cnts = np.asarray([c for _, c in pairs],
                                      dtype=np.int64)
                    vals.append(dd_quantile(keys, cnts, q))
                pctls.append(vals)
            key_dts = [_result_dtype(result, i) for i in range(nk)]
            if DataType.STRING in key_dts:
                # string group keys can't ride the temp join (cross-
                # table string equality needs dictionary alignment);
                # inline a CASE over the observed group values instead
                if len(gtuples) > 1000:
                    raise UnsupportedQueryError(
                        "approx_percentile with string GROUP BY keys "
                        "supports at most 1000 groups")
                for j, (call, _q) in enumerate(wants):
                    whens = []
                    for gi, g in enumerate(gtuples):
                        conds = []
                        for i, gk in enumerate(group_keys):
                            v = g[i]
                            conds.append(
                                ast.IsNull(gk) if v is None
                                else ast.BinaryOp(
                                    "=", gk, _value_to_literal(
                                        v, key_dts[i])))
                        cond = conds[0]
                        for c in conds[1:]:
                            cond = ast.BinaryOp("AND", cond, c)
                        whens.append((cond,
                                      ast.Literal(pctls[j][gi])))
                    repl[call] = ast.FuncCall(
                        "max", (ast.CaseWhen(tuple(whens), None),))
                continue
            # numeric/date keys: materialize a temp reference table and
            # join it back on the group keys
            temp_cols: dict[str, object] = {}
            temp_names: list[str] = []
            temp_dtypes: dict[str, object] = {}
            for i in range(nk):
                nmi = f"__pg{i}"
                temp_names.append(nmi)
                temp_cols[nmi] = np.asarray([g[i] for g in gtuples],
                                            dtype=object)
                temp_dtypes[nmi] = key_dts[i]
            for j, (call, q) in enumerate(wants):
                nmj = f"__pctl{len(extra_from)}_{j}"
                temp_names.append(nmj)
                temp_cols[nmj] = np.asarray(pctls[j], dtype=object)
                temp_dtypes[nmj] = DataType.FLOAT64
            from .executor.runner import ResultSet

            shim = ResultSet(temp_names, temp_cols, len(gtuples),
                             dtypes=temp_dtypes)
            temp = self._store_result(shim, cleanup)
            alias = f"__pctl_t{len(extra_from)}"
            extra_from.append(ast.TableRef(temp, alias))
            for i, g in enumerate(group_keys):
                tcol = ast.ColumnRef(f"__pg{i}", table=alias)
                eq = ast.BinaryOp("=", g, tcol)
                if any(gt[i] is None for gt in gtuples):
                    # NULL group keys group together (PG semantics) but
                    # never compare equal — match them explicitly
                    eq = ast.BinaryOp(
                        "OR", eq,
                        ast.BinaryOp("AND", ast.IsNull(g),
                                     ast.IsNull(tcol)))
                extra_where.append(eq)
            for j, (call, _q) in enumerate(wants):
                repl[call] = ast.FuncCall(
                    "max",
                    (ast.ColumnRef(f"__pctl{len(extra_from) - 1}_{j}",
                                   table=alias),))

        def sub(e: ast.Expr) -> ast.Expr:
            if isinstance(e, ast.FuncCall) and e in repl:
                return repl[e]
            return _map_children(e, sub)

        where = sel.where
        for c in extra_where:
            where = c if where is None else ast.BinaryOp("AND", where, c)
        return dc_replace(
            sel,
            items=tuple(ast.SelectItem(sub(it.expr), it.alias)
                        for it in sel.items),
            from_items=sel.from_items + tuple(extra_from),
            where=where)

    def _subquery_select(self, q, cleanup, cte_scope) -> ast.Select:
        """Expression-subquery body → plain Select (compound bodies
        materialize to a temp first)."""
        if isinstance(q, ast.SetOp):
            temp = self._query_to_temp(q, cleanup, cte_scope)
            return ast.Select(items=(ast.SelectItem(ast.Star()),),
                              from_items=(ast.TableRef(temp),))
        return q

    def _rewrite_expr(self, e: ast.Expr, cleanup, cte_scope) -> ast.Expr:
        if isinstance(e, ast.ScalarSubquery):
            inner = self._recursive_plan(
                self._subquery_select(e.query, cleanup, cte_scope),
                cleanup, cte_scope)
            result = self._execute_subselect(self._sub_params(inner))
            if result.row_count > 1:
                raise ExecutionError(
                    "scalar subquery returned more than one row")
            if result.row_count == 0:
                return ast.Literal(None)
            dt = _result_dtype(result, 0)
            return _value_to_literal(result.rows()[0][0], dt)
        if isinstance(e, ast.InSubquery):
            inner = self._recursive_plan(
                self._subquery_select(e.query, cleanup, cte_scope),
                cleanup, cte_scope)
            result = self._execute_subselect(self._sub_params(inner))
            dt = _result_dtype(result, 0)
            raw = [r[0] for r in result.rows()]
            has_null = any(v is None for v in raw)
            values = tuple(_value_to_literal(v, dt) for v in raw
                           if v is not None)
            operand = self._rewrite_expr(e.operand, cleanup, cte_scope)
            if e.negated:
                # x NOT IN (..., NULL) is never TRUE (SQL three-valued)
                if has_null:
                    return ast.Literal(False)
                if not values:
                    return ast.Literal(True)  # NOT IN (empty) holds
                return ast.InList(operand, values, True)
            if not values:
                return ast.Literal(False)
            # positive IN: dropping NULLs is exact under WHERE semantics
            # (x IN (..., NULL) is TRUE or NULL, never FALSE-turned-TRUE)
            return ast.InList(operand, values, False)
        if isinstance(e, ast.Exists):
            inner = self._recursive_plan(
                self._subquery_select(e.query, cleanup, cte_scope),
                cleanup, cte_scope)
            limited = dc_replace(self._sub_params(inner), limit=1)
            result = self._execute_subselect(limited)
            found = result.row_count > 0
            return ast.Literal(found != e.negated)
        # structural recursion: window specs carry expressions that the
        # generic mapper doesn't descend into
        if isinstance(e, ast.FuncCall) and e.window is not None:
            window = ast.WindowSpec(
                tuple(self._rewrite_expr(p, cleanup, cte_scope)
                      for p in e.window.partition_by),
                tuple((self._rewrite_expr(o, cleanup, cte_scope), d)
                      for o, d in e.window.order_by))
            return ast.FuncCall(e.name,
                                tuple(self._rewrite_expr(a, cleanup,
                                                         cte_scope)
                                      for a in e.args),
                                e.distinct, e.star, window)
        # everything else (BinaryOp/UnaryOp/IsNull/Between/InList/Like/
        # Cast/Extract/Substring/CaseWhen/FuncCall/leaves) maps through
        # the shared structural rebuilder — hand-rolled per-node copies
        # kept missing node kinds, leaving nested subqueries unplanned
        # (IsNull/Cast/Extract/Substring all had the bug)
        from .planner.decorrelate import _map_children

        return _map_children(
            e, lambda c: self._rewrite_expr(c, cleanup, cte_scope))

    def _materialize(self, sel: ast.Select, cleanup: list[str],
                     column_names: tuple[str, ...] = ()) -> str:
        """Execute a subquery and store its rows as a temp reference table
        (the intermediate-result broadcast analogue)."""
        from .stats.tracing import trace_span

        with trace_span("subplan"):
            result = self._run_subplan(sel)
            return self._store_result(result, cleanup, column_names)

    def _store_result(self, result, cleanup: list[str],
                      column_names: tuple[str, ...] = ()) -> str:
        """ResultSet (or shim with column_names/columns/row_count/dtypes)
        → temp reference table, its rows held in memory by the store."""
        from .stats import counters as sc
        from .stats.tracing import trace_span

        with trace_span("subplan.store"):
            # itertools.count is GIL-atomic — concurrent query threads must
            # not mint the same intermediate-table name.  The name is what
            # the catalog, the store and the feed cache know the rows by,
            # and never recurs; plan fingerprints know them by their schema
            # (planner/bind.py BoundRel.identity), which does
            name = f"{INTERMEDIATE_PREFIX}{next(self._temp_counter)}"
            names = (list(column_names) if column_names
                     else result.column_names)
            n_rows = result.row_count
            # listed before anything carries the name: whichever step
            # below fails, the caller's `finally` drops what exists by then
            cleanup.append(name)
            cols = []
            arrays = {}
            dicts = {}
            with trace_span("subplan.store.type", rows=n_rows,
                            cols=len(names)):
                for out_name, col_name in zip(result.column_names, names):
                    data = result.columns[out_name]
                    rdt = _result_dtype(result, out_name)
                    if rdt == DataType.DATE:
                        # keep DATE columns as day numbers in the temp table
                        # (the combine phase formatted them to ISO text)
                        from .types import date_to_days

                        arr = np.array(
                            [None if x is None else date_to_days(str(x))
                             for x in data], dtype=object)
                        dtype, dvals = DataType.DATE, None
                    else:
                        dtype, arr, dvals = _infer_column(data, n_rows)
                    cols.append(ColumnDef(col_name, dtype))
                    arrays[col_name] = arr
                    if dvals is not None:
                        dicts[col_name] = dvals
                if n_rows > 0:
                    # validity from the object arrays (None = NULL); a
                    # string column's typed form is its codes, below
                    validity = {c: (~_none_mask(a) if a.dtype == object
                                    else np.ones(n_rows, dtype=bool))
                                for c, a in arrays.items()}
                    arrays = {c: a if c in dicts else _object_to_typed(a)
                              for c, a in arrays.items()}
            if n_rows > 0 and dicts:
                with trace_span("subplan.store.intern"):
                    for col_name, values in dicts.items():
                        d = self.store.dictionary(name, col_name)
                        arrays[col_name] = d.intern_array(values)
            with trace_span("subplan.store.append") as append:
                self.catalog.create_reference_table(
                    name, TableSchema(tuple(cols)))
                if n_rows > 0:
                    shard = self.catalog.table_shards(name)[0]
                    # the store holds the typed arrays until _drop_temp
                    # and the outer statement's feed is built from them:
                    # no stripe, no manifest file, no change-feed event
                    record = self.store.hold_resident(
                        name, shard.shard_id, arrays, validity)
                    if append is not None:
                        append.meta = {"bytes": record["bytes"]}
                    self.stats.counters.increment(
                        sc.INTERMEDIATE_ROWS_TOTAL, n_rows)
                    self.stats.counters.increment(
                        sc.INTERMEDIATE_BYTES_TOTAL, record["bytes"])
                self.stats.counters.increment(
                    sc.INTERMEDIATE_RESIDENT_TOTAL)
            return name

    # -- set operations ----------------------------------------------------
    def _execute_setop(self, stmt: "ast.SetOp"):
        """UNION [ALL] / INTERSECT / EXCEPT via recursive materialization
        (the reference routes set operations it cannot push down through
        recursive planning the same way, recursive_planning.c set-op
        handling).  Both sides land in ONE combined temp table — one
        dictionary per string column, so no cross-dictionary code
        translation — and the set semantics ride the existing aggregate
        machinery: GROUP BY all columns with a side tag,
            UNION      →  the groups themselves,
            INTERSECT  →  HAVING min(__side) = 0 AND max(__side) = 1,
            EXCEPT     →  HAVING max(__side) = 0.
        SQL set-op NULL semantics (NULLs compare equal) fall out of GROUP
        BY's NULL grouping for free."""
        cleanup: list[str] = []
        try:
            final = self._setop_select(stmt, cleanup, {})
            plan, inner_cleanup = self._plan_select(final)
            cleanup.extend(inner_cleanup)
            self._count_plan_shape(plan)
            return self.executor.execute_plan(plan)
        finally:
            for t in cleanup:
                self._drop_temp(t)

    def _setop_select(self, stmt: "ast.SetOp", cleanup: list[str],
                      cte_scope: dict[str, str]) -> ast.Select:
        """SetOp tree → a plain Select over the combined temp table."""
        cte_scope = dict(cte_scope)
        for cte in stmt.ctes:
            temp = self._query_to_temp(cte.query, cleanup, cte_scope,
                                       cte.column_names)
            cte_scope[cte.name] = temp
        if stmt.all and stmt.op != "union":
            raise UnsupportedQueryError(
                f"{stmt.op.upper()} ALL is not supported (bag semantics "
                "need per-group multiplicity matching)")
        left = self._setop_result(stmt.left, cleanup, cte_scope)
        right = self._setop_result(stmt.right, cleanup, cte_scope)
        if len(left.column_names) != len(right.column_names):
            raise PlanningError(
                f"each {stmt.op.upper()} side must have the same number "
                f"of columns ({len(left.column_names)} vs "
                f"{len(right.column_names)})")
        tag = not (stmt.op == "union" and stmt.all)
        combined = self._store_result(
            _concat_results(left, right, tag), cleanup)
        names = [c for c in self.catalog.table(combined).schema.names
                 if c != "__side"]
        refs = tuple(ast.ColumnRef(n) for n in names)
        items = tuple(ast.SelectItem(r, n) for r, n in zip(refs, names))
        having = None
        group_by: tuple = ()
        if stmt.op == "union" and not stmt.all:
            group_by = refs
        elif stmt.op == "intersect":
            group_by = refs
            side = ast.ColumnRef("__side")
            having = ast.BinaryOp(
                "AND",
                ast.BinaryOp("=", ast.FuncCall("min", (side,)),
                             ast.Literal(0)),
                ast.BinaryOp("=", ast.FuncCall("max", (side,)),
                             ast.Literal(1)))
        elif stmt.op == "except":
            group_by = refs
            having = ast.BinaryOp("=", ast.FuncCall(
                "max", (ast.ColumnRef("__side"),)), ast.Literal(0))
        return ast.Select(items=items,
                          from_items=(ast.TableRef(combined),),
                          group_by=group_by, having=having,
                          order_by=stmt.order_by, limit=stmt.limit,
                          offset=stmt.offset)

    def _setop_result(self, q, cleanup: list[str], cte_scope):
        """One set-op side → executed ResultSet."""
        if isinstance(q, ast.SetOp):
            return self._execute_subselect(
                self._setop_select(q, cleanup, cte_scope))
        inner = self._recursive_plan(q, cleanup, cte_scope)
        return self._execute_subselect(self._sub_params(inner))

    def _query_to_temp(self, q, cleanup: list[str], cte_scope,
                       column_names: tuple[str, ...] = ()) -> str:
        """Select | SetOp → temp reference table (CTE/derived-table
        bodies may be compound queries)."""
        if isinstance(q, ast.SetOp):
            sel = self._setop_select(q, cleanup, cte_scope)
            return self._materialize(sel, cleanup, column_names)
        inner = self._recursive_plan(q, cleanup, cte_scope)
        return self._materialize(self._sub_params(inner), cleanup,
                                 column_names)

    def _drop_temp(self, name: str):
        from .stats.tracing import trace_span

        with trace_span("subplan.drop"):
            try:
                self.catalog.drop_table(name)
            except CatalogError:
                pass
            self.store.drop_table_storage(name)
            # the name never recurs, so its feed can never hit again: free
            # the device bytes now and not when the LRU reaches them
            self.executor.feed_cache.invalidate_table(name)

    def _save_catalog(self):
        self.catalog.save(os.path.join(self.data_dir, "catalog.json"))


def _concat_results(left, right, tag: bool):
    """Two ResultSets → one combined result (columns matched by
    POSITION, names from the left side), plus an int __side column (0 =
    left, 1 = right) when `tag`.  Feeds _store_result for set-operation
    temps."""
    from .executor.runner import ResultSet

    n = left.row_count + right.row_count
    names = list(left.column_names)
    cols: dict[str, object] = {}
    dtypes: dict[str, DataType] = {}
    for lname, rname in zip(names, right.column_names):
        lv = list(left.columns[lname])
        rv = list(right.columns[rname])
        cols[lname] = np.asarray(lv + rv, dtype=object)
        ldt = _result_dtype(left, lname)
        rdt = _result_dtype(right, rname)
        if ldt is not None and ldt == rdt:
            dtypes[lname] = ldt
        elif ldt is not None and rdt is not None:
            # PG: "UNION types X and Y cannot be matched".  Numeric
            # widths widen (int/float mixes); everything else —
            # DATE/non-DATE, STRING/numeric, BOOL/numeric — is an error
            # rather than a silently mixed-type object column (r4
            # advisor finding)
            numeric = {DataType.INT32, DataType.INT64,
                       DataType.FLOAT32, DataType.FLOAT64}
            if not (ldt in numeric and rdt in numeric):
                raise PlanningError(
                    f"set-operation column {lname!r} mixes "
                    f"{ldt.value} and {rdt.value} — types cannot be "
                    "matched")
            dtypes[lname] = (
                DataType.FLOAT64
                if DataType.FLOAT64 in (ldt, rdt)
                or DataType.FLOAT32 in (ldt, rdt) else DataType.INT64)
    if tag:
        names.append("__side")
        cols["__side"] = np.concatenate(
            [np.zeros(left.row_count, dtype=np.int64),
             np.ones(right.row_count, dtype=np.int64)])
        dtypes["__side"] = DataType.INT64
    return ResultSet(names, cols, n, dtypes=dtypes)


def _result_dtype(result, col: int | str):
    if result.dtypes is None:
        return None
    if isinstance(col, int):
        col = result.column_names[col]
    return result.dtypes.get(col)


def _value_to_literal(v, dtype=None) -> ast.Literal:
    if v is None:
        return ast.Literal(None)
    if dtype == DataType.DATE:
        # the combine phase formatted DATE to ISO text; fold back to the
        # storage representation (days since epoch) so comparisons against
        # DATE columns bind as integers
        from .types import date_to_days

        return ast.Literal(date_to_days(str(v)))
    if isinstance(v, (np.integer,)):
        return ast.Literal(int(v))
    if isinstance(v, (np.floating,)):
        return ast.Literal(float(v))
    if isinstance(v, (np.bool_, bool)):
        return ast.Literal(bool(v))
    if isinstance(v, str):
        return ast.Literal(v)
    if isinstance(v, (int, float)):
        return ast.Literal(v)
    raise ExecutionError(f"cannot inline value of type {type(v).__name__}")


def _infer_column(data, n: int):
    """Result column → (DataType, array, dict_values | None)."""
    arr = np.asarray(data)
    if arr.dtype == object:
        non_null = [x for x in data if x is not None]
        if non_null and isinstance(non_null[0], str):
            return DataType.STRING, np.asarray(data, dtype=object), list(data)
        typed = np.array([0 if x is None else x for x in data])
        dt = _np_to_datatype(typed.dtype)
        return dt, np.asarray(data, dtype=object), None
    return _np_to_datatype(arr.dtype), arr, None


def _np_to_datatype(dt) -> DataType:
    if dt == np.int32:
        return DataType.INT32
    if np.issubdtype(dt, np.integer):
        return DataType.INT64
    if dt == np.float32:
        return DataType.FLOAT32
    if np.issubdtype(dt, np.floating):
        return DataType.FLOAT64
    if dt == np.bool_:
        return DataType.BOOL
    return DataType.FLOAT64


def _none_mask(arr) -> np.ndarray:
    return np.array([x is None for x in arr], dtype=bool)


def _object_to_typed(arr: np.ndarray) -> np.ndarray:
    if arr.dtype != object:
        return arr
    return np.array([0 if x is None else x for x in arr])


def _substitute_params(node, args: tuple):
    """Replace ast.Param nodes with the EXECUTE argument literals across
    an arbitrary (frozen-dataclass) statement tree — the non-SELECT
    prepared-execution path (INSERT/UPDATE/DELETE have no compiled device
    program to keep generic)."""
    import dataclasses

    if isinstance(node, ast.Param):
        if node.index >= len(args):
            raise PlanningError(
                f"parameter ${node.index + 1} has no value")
        return args[node.index]
    if dataclasses.is_dataclass(node) and not isinstance(node, type):
        changes = {}
        for f in dataclasses.fields(node):
            old = getattr(node, f.name)
            new = _substitute_params(old, args)
            if new is not old:
                changes[f.name] = new
        return dataclasses.replace(node, **changes) if changes else node
    if isinstance(node, tuple):
        subst = tuple(_substitute_params(x, args) for x in node)
        return subst if any(a is not b for a, b in zip(subst, node)) \
            else node
    if isinstance(node, list):
        return [_substitute_params(x, args) for x in node]
    return node
