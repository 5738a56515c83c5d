"""Distributed execution driver: capacities, retry loop, host combine.

The coordinator-side finish: gathers device outputs, evaluates the combine
phase (host_select / HAVING / ORDER BY / LIMIT — the combine_query of
planner/combine_query_planner.c), decodes dictionary strings, and returns a
ResultSet.  Overflowed static buffers trigger recompile-with-doubled-caps
(bounded retries), the executor's answer to data-dependent cardinalities.
"""

from __future__ import annotations

import math
import threading
from dataclasses import dataclass

import numpy as np
from jax.sharding import Mesh

from ..catalog import Catalog
from ..config import Settings
from ..errors import (
    CapacityOverflowError,
    DeviceMemoryExhausted,
    ExecutionError,
    PlanningError,
)
from ..planner import expr as ir
from ..planner.plan import (
    AggregateNode,
    JoinNode,
    ProjectNode,
    QueryPlan,
    ScanNode,
    WindowNode,
)
from ..storage import TableStore
from ..storage.dictionary import resolve_decode
from ..types import DataType, days_to_date
from .cache import (
    FeedCache,
    PlanCache,
    caps_signature,
    feeds_signature,
    node_fingerprint,
)
from .compiler import (
    Capacities,
    PlanCompiler,
    _round_cap,
    flatten_feed_arrays,
    unpack_outputs,
)
from .exprs import ColumnSource, evaluate, predicate_mask
from .feed import build_feeds, walk_plan

MAX_RETRIES = 4

# What a compaction pass must save to be installed.  It pays one sort of
# the positions at the uncompacted size (0.68–0.84 ns a row on the v5e:
# PERF.md §6, my chip run, PR 30) and n_cols gathers at the compacted
# size, 6.6–7.0 ns an element (my chip runs, PR 28 to PR 30).  "Must
# shrink ≥3× to pay for itself" dates from a scatter at 5.9 ns a row in
# the sort's place and is not re-derived from the sort's price yet
# (PERF.md §7).
COMPACTION_MIN_SHRINK = 3


def compaction_pays(k: int, n: int) -> bool:
    """Whether compacting n slots down to k is taken as worth the pass:
    asked by capacity planning for scan, join and bucketed-grid outputs;
    feedback tightening holds the same kinds to the same ratio
    (TIGHTEN_THRESHOLD)."""
    return k * COMPACTION_MIN_SHRINK < n


# degradation ladder bounds: each batch-shrink rung halves the stream
# batch (one memoized recompile per level); beyond this the rung is
# spent and the ladder moves on
MAX_BATCH_SHRINK = 64


@dataclass
class OomState:
    """Sticky (per-executor) outcome of the OOM degradation ladder —
    memoized so a statement that needed rungs does not re-discover
    them (and re-pay the OOM + recompile) on every execution.

    * ``batch_shrink`` — divisor applied to the stream batch_cap;
    * ``force_stream`` — stream even when the feeds fit the configured
      budget (a real OOM proved the effective ceiling lower);
    * ``multipass_k`` — split the build side into K host-resident
      passes (executor/multipass.py)."""

    batch_shrink: int = 1
    force_stream: bool = False
    multipass_k: int = 1


@dataclass
class ResultSet:
    column_names: list[str]
    columns: dict[str, np.ndarray | list]
    row_count: int
    # output SQL types by column name (None where a producer has no type
    # info, e.g. UDF results); lets consumers round-trip DATE values that
    # the combine phase formatted to ISO strings
    dtypes: dict[str, DataType] | None = None
    # execution metadata (EXPLAIN ANALYZE / stats counters read these)
    retries: int = 0
    device_rows_scanned: int = 0
    # rows each mesh device fed INTO the program (per-device sums over
    # the sharded scan feeds; None when unknown) — the Mesh: line's
    # rows-in column
    device_rows_in: list[int] | None = None
    fast_path: bool = False   # executed host-side via the fast-path router
    streamed_batches: int = 0  # >0 ⇒ executed via the stream pipeline
    spill_passes: int = 0     # >0 ⇒ executed via multi-pass partitioning
    # per-column NULL masks (raw mode keeps typed arrays + mask instead of
    # objectified None entries); None when columns carry None directly
    null_masks: dict[str, np.ndarray] | None = None
    # raw mode: STRING columns hold dictionary codes for this source
    # (output name → (table, column) whose dictionary decodes them)
    decode_map: dict[str, tuple[str, str]] | None = None
    # raw mode: surviving-row count per device, rows in device-major
    # order — lets a colocated INSERT..SELECT slice per-device blocks
    # without re-hashing.  None when HAVING/ORDER/LIMIT disturbed the
    # device order.
    device_rows: list[int] | None = None

    def rows(self) -> list[tuple]:
        cols = [self.columns[n] for n in self.column_names]
        return [tuple(c[i] for c in cols) for i in range(self.row_count)]

    def __len__(self):
        return self.row_count


class Executor:
    def __init__(self, catalog: Catalog, store: TableStore,
                 settings: Settings, mesh: Mesh, counters=None):
        self.catalog = catalog
        self.store = store
        self.settings = settings
        self.mesh = mesh
        self.counters = counters
        self.plan_cache = PlanCache(
            settings.get("max_cached_plans"))
        self.feed_cache = FeedCache(
            settings.get("max_cached_feed_bytes"))
        # fingerprint → plan-walk-order-keyed capacities that last
        # succeeded: a query whose first run needed overflow/dense
        # retries starts warm runs from the converged sizes instead of
        # re-paying the retry executions.  Keyed by walk INDEX, not node
        # id — every execution builds a fresh QueryPlan instance.
        # Persisted under the data dir: a NEW session starts from the
        # converged/tightened sizes instead of re-paying the feedback
        # recompile (a stale entry self-heals via overflow-retry)
        self._caps_memo: dict = self._load_caps_memo()
        # fingerprints already tightened by capacity feedback: tighten at
        # most ONCE per plan shape, or generic (prepared) plans would
        # recompile on every parameter value's slightly different actuals
        self._tightened_fps: set = set()
        # caps-memo persistence debounce state: under a compile storm
        # every memoization used to rewrite the whole memo file
        # (O(N²) bytes) — writes now coalesce and flush_persistent()
        # drains the remainder at session close
        self._memo_dirty = 0
        self._memo_last_write = 0.0
        self._memo_writes = 0  # rewrite count (regression-tested)
        # concurrent execute() threads share this executor: the memo
        # dict is iterated while being written (_memoize_caps), which
        # CPython turns into "dict changed size during iteration"
        self._caps_lock = threading.Lock()
        # device-memory accountant: ONE per data_dir (sessions share
        # the device) — every placement this executor makes flows
        # through it, and the OOM degradation ladder consults its
        # measured ledger (executor/hbm.py)
        from .hbm import accountant_for

        self.accountant = accountant_for(store.data_dir)
        self.accountant.register_evictable(self.feed_cache)
        # persistent executable cache + single-flight compile gate:
        # ONE per data_dir (sessions share the device and the disk) —
        # a restart loads serialized executables instead of recompiling
        # and N sessions racing a cold shape produce ONE compile
        # (executor/execcache.py; gated by `exec_cache_enabled`)
        from .execcache import exec_cache_for

        self.exec_cache = exec_cache_for(store.data_dir)
        # scan-pipeline phase accounting (executor/scanpipe.py): the
        # bench drivers reset + read this to stamp prefetch/decode/
        # transfer walls and the bytes-on-wire ratio into the artifact
        from .scanpipe import ScanPhaseStats

        self.scan_stats = ScanPhaseStats()
        self.oom = OomState()
        # per-thread plan of the in-flight statement: the degradation
        # ladder peeks at it to skip rungs that cannot help this shape
        self._oom_tls = threading.local()

    # ------------------------------------------------------------------
    def execute_plan(self, plan: QueryPlan, raw: bool = False) -> ResultSet:
        from ..stats.tracing import trace_span
        from .fastpath import try_execute_fast_path

        # cross-session read-committed visibility: another session over
        # this data_dir may have committed since our manifest was cached
        # (one stat() per scanned table; writers refresh under the DML
        # lock, this is the readers' counterpart)
        with trace_span("route"):
            for node in walk_plan(plan.root):
                if isinstance(node, ScanNode):
                    self.store.refresh_if_stale(node.rel.table)

        # the degradation ladder peeks at the in-flight plan to decide
        # which rungs can help this statement's shape
        self._oom_tls.plan = plan
        fast = try_execute_fast_path(self, plan, raw)
        if fast is not None:
            return fast
        if self.oom.multipass_k > 1:
            from .multipass import try_execute_multipass

            mp = try_execute_multipass(self, plan, raw,
                                       self.oom.multipass_k)
            if mp is not None:
                return mp
        from .stream import try_execute_streamed

        streamed = try_execute_streamed(self, plan, raw)
        if streamed is not None:
            return streamed
        compute_dtype = np.dtype(self.settings.get("compute_dtype"))
        packed, out_meta, caps, retries, feeds, tallies = \
            self._run_resident(plan, compute_dtype)
        self.count_picks(plan, caps, tallies)
        with trace_span("combine"):
            cols, nulls, valid = unpack_outputs(packed, out_meta)
            result = self._host_combine(plan, cols, nulls, valid, raw)
        result.retries = retries
        # result-transfer volume in row slots (n_dev·cap, or n_dev·k under
        # device top-k pushdown) — EXPLAIN ANALYZE / stats surface this
        result.device_rows_scanned = int(np.asarray(valid).size)
        result.device_rows_in = feed_device_rows(feeds, plan.n_devices)
        return result

    # ------------------------------------------------------------------
    def _run_resident(self, plan: QueryPlan, compute_dtype,
                      no_cache_nodes=frozenset()):
        """Resident-feed execution core: build feeds, resolve the
        capacity memo, run the overflow-retry loop.  Shared by
        execute_plan and the multipass pass driver."""
        from ..stats.tracing import trace_span

        with trace_span("feed"):
            feeds = build_feeds(plan, self.catalog, self.store,
                                self.mesh, compute_dtype,
                                cache=self.feed_cache,
                                counters=self.counters,
                                accountant=self.accountant,
                                no_cache_nodes=no_cache_nodes,
                                stats=self.scan_stats)
        with trace_span("caps"):
            # device_topk + its ORDER BY keys are traced into the program
            topk_sig = (plan.device_topk, tuple(
                (repr(e), d, nf) for e, d, nf in plan.host_order_by)
                if plan.device_topk is not None else ())
            orp = plan.output_repart
            orp_sig = (None if orp is None
                       else (orp[0], orp[1], orp[2], repr(orp[3])))
            # group_by_kernel changes which CAPACITY TABLES exist
            # (the bucketed grid's agg_out vs sort-path buffers), so
            # converged sizes memoized under one mode must not be
            # replayed under another — it joins the fingerprint
            fingerprint = (node_fingerprint(plan.root), plan.n_devices,
                           str(compute_dtype), feeds_signature(plan, feeds),
                           topk_sig, orp_sig,
                           self.settings.get("group_by_kernel"))
            with self._caps_lock:
                memo = self._caps_memo.get(fingerprint)
            caps = (self._caps_from_order(plan, memo) if memo is not None
                    else self._initial_capacities(plan, feeds))
        packed, out_meta, caps, retries, tallies = self.run_with_retry(
            plan, feeds, caps, fingerprint, compute_dtype)
        return packed, out_meta, caps, retries, feeds, tallies

    # ------------------------------------------------------------------
    def execute_pass(self, plan: QueryPlan, split_nid: int):
        """One multipass pass (executor/multipass.py): run the pruned
        plan via the stream pipeline when it still exceeds the budget,
        else resident, and return its flattened pre-combine parts as
        (parts, rows_scanned, retries, streamed_batches).  The split
        scan's per-pass feed bypasses the device cache — resident-
        caching every pass's partition would defeat the pass."""
        from .stream import _flatten_batch, try_execute_streamed

        streamed = try_execute_streamed(self, plan, raw=True,
                                        return_parts=True,
                                        no_cache_nodes=frozenset(
                                            {split_nid}))
        if streamed is not None:
            parts, scanned, retries, batches, caps, tallies = streamed
            if caps is not None:
                self.count_picks(plan, caps, tallies)
            return parts, scanned, retries, batches
        compute_dtype = np.dtype(self.settings.get("compute_dtype"))
        packed, out_meta, caps, retries, _feeds, tallies = \
            self._run_resident(plan, compute_dtype,
                               no_cache_nodes=frozenset({split_nid}))
        self.count_picks(plan, caps, tallies)
        cols, nulls, valid = unpack_outputs(packed, out_meta)
        scanned = int(np.asarray(valid).size)
        return [_flatten_batch(cols, nulls, valid)], scanned, retries, 0

    # ------------------------------------------------------------------
    def run_with_retry(self, plan: QueryPlan, feeds, caps: Capacities,
                       fingerprint, compute_dtype, allow_tighten=True):
        """Compile (or fetch cached) + execute + overflow-retry loop.

        Shared by the resident-feed path and the streamed (batched)
        path.  Returns (packed, out_meta, converged_caps, retries);
        converged capacities are memoized under `fingerprint` whenever a
        retry occurred so later executions start warm.

        Capacity feedback (the adaptive-executor move,
        adaptive_executor.c:962, done the static-shape way): a clean
        execution whose recorded stage actuals sit far below their
        buffers tightens the capacities to actual×slack, recompiles
        once, and memoizes — warm executions then run with near-actual
        buffers even where the planner's estimate was 10× off (join
        selectivities over correlated columns are statically
        unestimable).  An over-tightened buffer (data changed) simply
        overflows and regrows through the normal retry path."""
        from ..stats.tracing import trace_span
        from ..utils.cancellation import check_cancel

        limit = self.settings.get("max_plan_buffer_bytes")
        group_kernel = self.settings.get("group_by_kernel")
        retries = 0
        tightened = False
        while True:
            check_cancel()  # overflow-retry iterations are cancel seams
            with trace_span("caps"):
                # one estimate serves the guard here and the lease below
                est = _plan_buffer_bytes(plan, caps, group_kernel)
                if limit and est > limit:
                    if self._plan_degradable(plan):
                        # eligible over-limit plans route into the OOM
                        # degradation ladder (stream / multi-pass)
                        # instead of erroring — the guard becomes a
                        # pre-allocation OOM signal
                        raise DeviceMemoryExhausted(
                            f"RESOURCE_EXHAUSTED (guard): plan needs "
                            f"~{est / 1e9:.1f} GB of device buffers "
                            f"(max_plan_buffer_bytes = "
                            f"{limit / 1e9:.1f} GB) — degrading")
                    raise PlanningError(
                        f"plan needs ~{est / 1e9:.1f} GB of device "
                        f"buffers (max_plan_buffer_bytes = "
                        f"{limit / 1e9:.1f} GB) — usually a cartesian "
                        "or extreme-fanout join; rewrite the query or "
                        "raise the limit")
                key = fingerprint + (caps_signature(plan, caps),)
                entry = self.plan_cache.get(key)
            if entry is None:
                from ..utils.faultinjection import fault_point

                # named seam: a failure while tracing/compiling must
                # leave the plan cache without a half-built entry
                fault_point("executor.plan_cache_fill")
                entry = self._compile_or_load(plan, feeds, caps,
                                              compute_dtype, group_kernel,
                                              key)
                self.plan_cache.put(key, entry)
                fn, out_meta, stage_keys, shuffle_bytes, tallies = entry
                feed_arrays = flatten_feed_arrays(plan, feeds,
                                                  compute_dtype)
            else:
                fn, out_meta, stage_keys, shuffle_bytes, tallies = entry
                with trace_span("compile", cache="hit"):
                    feed_arrays = flatten_feed_arrays(plan, feeds,
                                                      compute_dtype)
            # two device→host transfers total: the bit-packed output block
            # and the overflow counters (each transfer pays a full round
            # trip on remote-attached TPUs)
            import jax

            from ..distributed.mesh import (
                is_device_loss,
                mesh_device_check,
                mesh_device_ids,
            )
            from ..errors import DeviceLostError
            from .hbm import is_resource_exhausted

            # XLA allocates the program's static intermediates where
            # Python cannot see them — the lease makes the estimate
            # visible to the measured ledger (and to an armed MemSim)
            # for exactly the execution window
            est_per_dev = est // max(1, plan.n_devices)

            def _dispatch():
                # mesh seams: a device dying mid-collective kills the
                # dispatch; a device dying between dispatch and the
                # device→host pull poisons the fetch.  Both are named
                # fault points AND MeshSim checkpoints, so the whole
                # kill-mid-query failover path is drivable on a CPU
                # test mesh (distributed/mesh.py)
                dev_ids = mesh_device_ids(self.mesh)
                with trace_span("mesh.dispatch"):
                    fault_point("mesh.collective")
                    mesh_device_check("mesh.collective", dev_ids)
                    out = fn(*feed_arrays)
                with trace_span("mesh.fetch"):
                    fault_point("mesh.fetch")
                    mesh_device_check("mesh.fetch", dev_ids)
                    # cut where the program ends, for a statement that
                    # is traced: what idles the device under `wait` is
                    # the launch (and the host's wake-up), under `pull`
                    # the copy of the two blocks.  The copies are queued
                    # behind the program BEFORE the host waits for its
                    # end, as device_get alone would: waiting first
                    # starts them a host wake-up later (0.13 ms a
                    # statement on the chip, PERF.md §6, PR 37)
                    with trace_span("mesh.fetch.wait") as wait:
                        if wait is not None:
                            for arr in out:
                                arr.copy_to_host_async()
                            jax.block_until_ready(out)
                    with trace_span("mesh.fetch.pull") as pull:
                        packed, overflow = jax.device_get(out)
                        if pull is not None:
                            pull.meta = {"bytes": packed.nbytes
                                         + overflow.nbytes}
                    return packed, overflow

            from ..utils.faultinjection import fault_point

            try:
                with self.accountant.lease("plan", est_per_dev):
                    packed, overflow = _dispatch()
            except jax.errors.JaxRuntimeError as e:
                if is_resource_exhausted(e):
                    # the canonical accelerator failure: classify it so
                    # the session retry envelope degrades-then-retries
                    # instead of dying (errors.DeviceMemoryExhausted)
                    self.accountant.note_oom()
                    raise DeviceMemoryExhausted(
                        f"device allocator OOM executing plan "
                        f"(~{est_per_dev} intermediate bytes/device): "
                        f"{e}") from e
                if is_device_loss(e):
                    # a device (or its ICI link) died under the
                    # compiled program: classify it so the session
                    # retry envelope shrinks the mesh and fails over
                    # instead of dying (errors.DeviceLostError; the
                    # session's probe pass identifies WHICH device)
                    raise DeviceLostError(
                        f"device loss executing plan: {e}",
                        seam="mesh.collective") from e
                # remote-attached compile services flake transiently on
                # long compilations (connection drops mid-response); one
                # clean retry re-issues the compile.  Anything else, or a
                # second failure, propagates.
                if "remote_compile" not in str(e):
                    raise
                with self.accountant.lease("plan", est_per_dev):
                    packed, overflow = _dispatch()
            with trace_span("settle"):
                # a row a device: [overflow, dense_oob, *stage actuals,
                # *(fullest bucket, rows sent) of each recorded exchange]
                ov = np.asarray(overflow).reshape(plan.n_devices, -1)
                if self.counters is not None:
                    from ..stats import counters as sc

                    self.counters.increment(
                        sc.FETCH_BYTES_TOTAL,
                        packed.nbytes + overflow.nbytes)
                exchanges = ov[:, 2 + len(stage_keys):]
                ov = ov[:, :2 + len(stage_keys)]
                cap_overflow = int(ov[:, 0].sum())
                dense_oob = int(ov[:, 1].sum())
                if cap_overflow == 0 and dense_oob == 0:
                    first_tighten = False
                    if allow_tighten and not tightened and \
                            self.settings.get("enable_capacity_feedback"):
                        with self._caps_lock:
                            if fingerprint not in self._tightened_fps:
                                if len(self._tightened_fps) > 512:
                                    self._tightened_fps.clear()
                                self._tightened_fps.add(fingerprint)
                                first_tighten = True
                    if first_tighten:
                        tight = self._tighten_caps(
                            plan, caps, stage_keys,
                            ov[:, 2:].max(axis=0) if len(stage_keys) else [])
                        if tight is not None:
                            caps = tight
                            tightened = True
                            self._memoize_caps(fingerprint, plan, caps)
                            continue  # recompile tight + re-execute
                    if retries or tightened:
                        self._memoize_caps(fingerprint, plan, caps)
                    if self.counters is not None and shuffle_bytes:
                        # TRACED all_to_all volume of the converged
                        # execution (PlanCompiler counts the exchange
                        # stages that actually exist — the psum-directory
                        # pushdown compiles shuffles away; stream paths
                        # pass here per batch, so the counter scales with
                        # what actually crossed the mesh), and what its
                        # recorded exchanges sent: the fullest bucket of
                        # each over the mesh, and the rows of all
                        self.counters.increment(sc.SHUFFLE_BYTES_TOTAL,
                                                shuffle_bytes)
                        self.counters.increment(
                            sc.REPARTITION_HOT_BUCKET_ROWS_TOTAL,
                            int(exchanges[:, 0::2].max(axis=0).sum()))
                        self.counters.increment(
                            sc.REPARTITION_ROWS_TOTAL,
                            int(exchanges[:, 1::2].sum()))
                    return packed, out_meta, caps, retries, tallies
            retries += 1
            from ..utils.faultinjection import fault_point

            # named seam: a failure while growing capacities must leave
            # the plan cache / capacity memo consistent (the retry loop
            # is the count-then-emit recovery path)
            fault_point("executor.overflow_retry")
            if retries >= MAX_RETRIES:
                raise CapacityOverflowError(
                    f"buffer overflow persisted after {retries} retries "
                    f"({cap_overflow + dense_oob} rows dropped)",
                    cap_overflow + dense_oob, 0)
            if dense_oob:
                # statistics-planned dense structures (join directories,
                # dense agg grids) saw out-of-range keys: stats were
                # stale — recompile on the general paths.  Merge with the
                # current capacities so growth from earlier overflow
                # retries isn't thrown away (each wasted cycle would
                # burn one of MAX_RETRIES)
                fresh = self._initial_capacities(plan, feeds,
                                                 dense_off=True)
                caps = Capacities(
                    {k: max(v, caps.repartition.get(k, 0))
                     for k, v in fresh.repartition.items()},
                    {k: max(v, caps.join_out.get(k, 0))
                     for k, v in fresh.join_out.items()},
                    {k: max(v, caps.agg_out.get(k, 0))
                     for k, v in fresh.agg_out.items()},
                    dense_off=True,
                    scan_out={k: max(v, caps.scan_out.get(k, 0))
                              for k, v in fresh.scan_out.items()},
                    output_repart=max(fresh.output_repart or 0,
                                      caps.output_repart or 0) or None)
            if cap_overflow:
                caps = caps.grown(cap_overflow)
            # overflow-regrow bounded by the accountant: a regrow whose
            # buffers can no longer fit the remaining device budget
            # would retry straight into a guaranteed OOM — degrade
            # (stream / multi-pass) instead of burning the retries
            budget = self.accountant.budget_bytes(self.settings)
            if budget:
                need = _plan_buffer_bytes(plan, caps, group_kernel) \
                    // max(1, plan.n_devices)
                room = budget - self.accountant.pressure_bytes()
                if need > room and self._plan_degradable(plan):
                    raise DeviceMemoryExhausted(
                        f"RESOURCE_EXHAUSTED (regrow guard): capacity "
                        f"regrow needs ~{need} bytes/device but only "
                        f"~{room} remain of the {budget}-byte device "
                        "budget — degrading instead of retrying into "
                        "a guaranteed OOM")

    # ------------------------------------------------------------------
    def _compile_or_load(self, plan: QueryPlan, feeds, caps: Capacities,
                         compute_dtype, group_kernel, key) -> tuple:
        """Plan-cache miss resolution, restart-survivable.  The whole
        resolve — disk load AND compile — runs single-flight through
        the per-data_dir gate: N sessions hitting a cold shape produce
        ONE deserialization (the PystachIO one-load-per-replica move)
        or, when the disk has nothing, ONE compile; followers wait
        under their own deadline/cancel budget and adopt the leader's
        executable.  Inside the flight the order is:

        1. the persistent executable cache (``exec_cache_enabled``):
           load-don't-compile — a deserialized AOT executable replaces
           trace + XLA compile (corrupt/skewed entries are detected
           and fall through);
        2. the compile itself, AOT (lower + compile, so the finished
           executable is serializable), persisted through the io seam.

        Returns the plan-cache entry ``(fn, out_meta, stage_keys,
        shuffle_bytes, tallies)``."""
        from ..stats import counters as sc
        from ..stats.tracing import trace_span

        use_cache = self.settings.get("exec_cache_enabled")
        ec = self.exec_cache

        def compile_fn():
            with trace_span("compile", cache="miss"):
                compiler = PlanCompiler(plan, self.mesh, feeds,
                                        caps, compute_dtype,
                                        group_kernel=group_kernel)
                fn, feed_arrays, out_meta, stage_keys = \
                    compiler.build()
                # AOT: compile NOW (not lazily at first dispatch) so
                # the executable exists to serialize and to hand to
                # deduped followers
                fn = fn.lower(*feed_arrays).compile()
            ec.note_compile()  # actual-compile ledger (dedup asserts)
            entry = (fn, out_meta, stage_keys, compiler.shuffle_bytes,
                     compiler.tallies)
            if use_cache:
                ec.store(key, self.mesh, *entry)
            return entry

        if not use_cache:
            return compile_fn()

        def resolve_fn():
            with trace_span("compile.cache_load"):
                entry, status = ec.load(key, self.mesh)
            if self.counters is not None:
                if status == "hit":
                    self.counters.increment(sc.EXEC_CACHE_HITS_TOTAL)
                elif status == "reject":
                    # detected rot/skew: recorded, then recompiled
                    self.counters.increment(sc.EXEC_CACHE_REJECTS_TOTAL)
                else:
                    self.counters.increment(sc.EXEC_CACHE_MISSES_TOTAL)
            if entry is not None:
                return entry
            return compile_fn()

        entry, deduped = ec.gate.run(key, resolve_fn)
        if deduped and self.counters is not None:
            self.counters.increment(sc.COMPILES_DEDUPED_TOTAL)
        return entry

    # ------------------------------------------------------------------
    def warmup_from_cache(self, deadline: float, top_n: int,
                          stop=None) -> int:
        """Warm-before-admit: pre-adopt the persisted cache's hottest
        executables into this executor's plan cache before the WLM
        admits non-exempt traffic (Session starts this on a warmup
        thread; the admission hold auto-expires at `deadline`).  Runs
        until the entries or the monotonic `deadline` run out —
        overrun or a fault degrades gracefully to lazy loading, never
        blocks admission forever.  Returns executables adopted."""
        import time as _time

        from ..stats import counters as sc
        from ..stats.tracing import trace_span
        from ..utils.faultinjection import fault_point

        loaded = 0
        for h in self.exec_cache.top_hashes(max(0, top_n)):
            if _time.monotonic() >= deadline or \
                    (stop is not None and stop.is_set()):
                # budget spent or the owning session is closing (the
                # admission hold must not outlive it): lazy from here
                break
            try:
                fault_point("wlm.warmup")
                with trace_span("wlm.warmup"):
                    key, entry = self.exec_cache.load_hash(h, self.mesh)
            except Exception:  # graftlint: ignore[swallowed-fault-seam] — not swallowed into silence: a warmup failure (injected or real) degrades to lazy compile by design; the admission hold releases in the caller's finally
                break
            if entry is None:
                continue  # skewed/corrupt entry: lazy path rejects too
            self.plan_cache.put(key, entry)
            loaded += 1
            if self.counters is not None:
                self.counters.increment(sc.WARMUP_COMPILES_TOTAL)
        return loaded

    # ------------------------------------------------------------------
    def adopt_mesh(self, mesh: Mesh) -> None:
        """Swap in a (usually shrunken) mesh after device loss or an
        elastic resize — the session's mesh-degrade path calls this
        after rebuilding the mesh from survivors.  Compiled executables
        and cache-resident feeds reference the dead device's buffers,
        so both caches drop wholesale (plans re-key on the new
        n_devices anyway; the caps memo keys on n_devices too, so
        converged sizes for other widths stay warm).  Statements
        already in flight on the old mesh object finish there — fake
        and surviving real devices keep answering for them — and their
        next retry re-plans onto this mesh."""
        self.mesh = mesh
        self.plan_cache.clear()
        self.feed_cache.clear()
        self.accountant.resize_mesh(mesh.devices.size)

    # ------------------------------------------------------------------
    def _plan_degradable(self, plan: QueryPlan) -> bool:
        """Can the degradation ladder shrink this plan's footprint?
        (executor/multipass.py owns the shape rules; windows and
        cartesian blowups stay clean immediate rejects.)"""
        from .multipass import ladder_degradable

        return ladder_degradable(
            plan, self.catalog, self.store, plan.n_devices,
            np.dtype(self.settings.get("compute_dtype")))

    # ------------------------------------------------------------------
    def degrade_for_oom(self, step: int, nbytes: int | None = None
                        ) -> str | None:
        """Apply the next rung of the OOM degradation ladder; returns
        the rung name, or None when no rung can help (the session then
        surfaces a clean ResourceExhausted).  `step` is the statement's
        1-based OOM count — monotone, so repeated OOMs walk DOWN the
        ladder instead of cycling on one rung; `nbytes` is the failed
        allocation's size when known (bounds the eviction target).

        Rungs, cheapest first:
          1. evict feed/result caches coldest-first (free HBM, nothing
             recompiles);
          2. halve the stream batch_cap (one memoized recompile);
          3. force the stream path even under the resident ceiling;
          4+. multi-pass partitioned execution, K doubling per rung.
        EVERY rung re-runs the eviction first — a retry re-fills the
        device cache, and stale cached feeds riding into a shrunk/
        streamed re-run would eat exactly the headroom the rung just
        created.  Batch-shrink/force/multipass state is sticky on the
        executor — memoized, so later statements start from the
        converged shape."""
        evicted = self._evict_for_oom(nbytes)
        if step <= 1:
            if evicted:
                return "evict_caches"
            step = 2  # nothing to evict: spend the escalation rung now
        plan = getattr(self._oom_tls, "plan", None)
        can_stream = False
        can_multipass = False
        if plan is not None:
            from .multipass import multipass_candidate
            from .stream import stream_candidates

            can_stream = bool(stream_candidates(plan, self.catalog))
            can_multipass = multipass_candidate(
                plan, self.catalog, self.store, plan.n_devices,
                np.dtype(self.settings.get("compute_dtype"))) is not None
        max_passes = self.settings.get("oom_max_spill_passes")
        i = step - 2  # escalation ladder position (0-based)
        while True:
            if i == 0:
                if can_stream and self.oom.batch_shrink < MAX_BATCH_SHRINK:
                    self.oom.batch_shrink *= 2
                    if self.counters is not None:
                        from ..stats import counters as sc

                        self.counters.increment(
                            sc.STREAM_BATCH_SHRINKS_TOTAL)
                    return "shrink_stream_batch"
            elif i == 1:
                if can_stream and not self.oom.force_stream:
                    self.oom.force_stream = True
                    return "force_stream"
            else:
                if can_multipass and self.oom.multipass_k < max_passes:
                    self.oom.multipass_k = min(
                        max_passes, max(2, self.oom.multipass_k * 2))
                    return "multipass"
                return None
            i += 1

    def _evict_for_oom(self, nbytes: int | None = None) -> int:
        """Rung 1: drop cache-resident device arrays coldest-first —
        across EVERY session's FeedCache on this data_dir (the device
        is shared; another session's cache pins HBM just the same).
        Frees at least 4× the failed allocation when its size is known
        (headroom for the retry's sibling feeds), everything
        otherwise.  Returns DEVICE cache entries evicted — only those
        mark the rung successful (a retry is pointless unless HBM was
        actually freed)."""
        # err.nbytes is PER-DEVICE; CachedFeed.nbytes (what eviction
        # counts down) is the host array total across all devices —
        # scale the target or sharded feeds under-evict by n_devices
        n_dev = max(1, self.mesh.devices.size)
        target = nbytes * 4 * n_dev if nbytes else None
        evicted = self.accountant.evict_evictable(target)
        if evicted and self.counters is not None:
            from ..stats import counters as sc

            self.counters.increment(sc.CACHE_EVICTIONS_TOTAL, evicted)
        # best-effort: finished result sets are host bytes, but a
        # memory-pressured data_dir should not keep serving caches
        # warm either; never resurrects a released registry entry and
        # never counts toward the rung's success
        from ..serving.result_cache import peek_result_cache

        rcache = peek_result_cache(self.store.data_dir)
        if rcache is not None and len(rcache):
            rcache.clear()
        return evicted

    # ------------------------------------------------------------------
    def count_picks(self, plan: QueryPlan, caps: Capacities,
                    tallies: tuple[int, int, int, int]) -> None:
        """groupby_bucketed_total, lookup_sorted_total,
        lookup_dense_total and broadcast_joins_total: each bumped once
        per executed STATEMENT whose converged plan ran the bucketed
        dense-grid group-by (by its number of such aggregates), at
        least one sort-and-scan lookup join, at least one dense
        directory lookup join, or broadcast joins (by their number);
        lookup_sorted_joins_total and lookup_dense_joins_total count
        the same fused lookup joins by their number on each arm —
        callers invoke this after their retry loop settles (the
        streamed path calls it once after the batch loop, not per
        batch), and a dense_oob fallback onto the general paths
        (caps.dense_off) correctly counts no pick (its broadcast joins
        stay broadcast joins).  deferred_columns_total,
        deferred_gathers_total, lookup_probe_slots_total and
        agg_bucket_slots_total take `tallies`, the four counts the
        converged program's compiler recorded at trace time
        (PlanCompiler.tallies; they ride in the plan-cache entry)."""
        if self.counters is None:
            return
        from ..stats import counters as sc

        carried, gathered, probe_slots, bucket_slots = tallies
        if carried:
            self.counters.increment(sc.DEFERRED_COLUMNS_TOTAL, carried)
            self.counters.increment(sc.DEFERRED_GATHERS_TOTAL, gathered)
        if probe_slots:
            self.counters.increment(sc.LOOKUP_PROBE_SLOTS_TOTAL,
                                    probe_slots)
        if bucket_slots:
            self.counters.increment(sc.AGG_BUCKET_SLOTS_TOTAL,
                                    bucket_slots)
        group_kernel = self.settings.get("group_by_kernel")
        nodes = list(walk_plan(plan.root))
        nbk = sum(1 for nd in nodes
                  if isinstance(nd, AggregateNode)
                  and PlanCompiler.agg_bucket_shape(
                      nd, group_kernel, caps.dense_off))
        if nbk:
            self.counters.increment(sc.GROUPBY_BUCKETED_TOTAL, nbk)
        # a join under the aggregate pushdown is probed through _bounds
        # and never fuses its lookup
        pushed = {id(nd.input) for nd in nodes
                  if isinstance(nd, AggregateNode)
                  and PlanCompiler.agg_pushdown_shape(nd)}
        joins = [nd for nd in nodes if isinstance(nd, JoinNode)]
        fused = [nd for nd in joins if id(nd) not in pushed]
        n_sorted = sum(PlanCompiler.sorted_lookup_shape(nd, caps.dense_off)
                       for nd in fused)
        if n_sorted:
            self.counters.increment(sc.LOOKUP_SORTED_TOTAL)
            self.counters.increment(sc.LOOKUP_SORTED_JOINS_TOTAL, n_sorted)
        n_dense = sum(PlanCompiler.dense_lookup_shape(nd, caps.dense_off)
                      for nd in fused)
        if n_dense:
            self.counters.increment(sc.LOOKUP_DENSE_TOTAL)
            self.counters.increment(sc.LOOKUP_DENSE_JOINS_TOTAL, n_dense)
        nbc = sum(1 for nd in joins if nd.strategy == "broadcast")
        if nbc:
            self.counters.increment(sc.BROADCAST_JOINS_TOTAL, nbc)

    # ------------------------------------------------------------------
    CAPS_MEMO_VERSION = 8  # bump when capacity semantics change

    def _memo_path(self) -> str:
        import os

        return os.path.join(self.store.data_dir, "caps_memo.json")

    # the memo is plain tuples/dicts of ints, strings, bools and Nones —
    # JSON round-trips it (lists→tuples, int keys re-parsed) without the
    # arbitrary-code-execution hazard pickle.load would add to a SHARED
    # data_dir (every other persisted artifact here is JSON for the same
    # reason).  ONE codec, shared with the executable cache's key
    # encoding (executor/execcache.py) — the two used to be copies and
    # diverged on numpy-scalar coercion, which made memo persistence
    # silently fail (TypeError swallowed below) for fingerprints
    # carrying np.int64 key extents.
    @staticmethod
    def _memo_to_json(obj):
        from .execcache import key_to_json

        return key_to_json(obj)

    @staticmethod
    def _memo_from_json(obj):
        from .execcache import key_from_json

        return key_from_json(obj)

    def _load_caps_memo(self) -> dict:
        import json as _json

        try:
            with open(self._memo_path()) as f:
                obj = _json.load(f)
            if obj.get("version") == self.CAPS_MEMO_VERSION:
                return {self._memo_from_json(k): self._memo_from_json(v)
                        for k, v in obj["memo"]}
        except (OSError, ValueError, KeyError, TypeError,
                AttributeError):
            # unreadable/corrupt memo file (incl. valid JSON that is
            # not an object — obj.get raises AttributeError): start cold
            pass
        return {}

    # memo bounds + rewrite debounce: overflow evicts the OLDEST HALF
    # (a full clear() forgot every converged shape at once — a
    # self-inflicted cold start), and the whole-file rewrite coalesces
    # under a compile storm (every memoization used to rewrite O(N)
    # bytes — O(N²) across a storm).  A lone memoization past the idle
    # window still writes immediately; close() drains the remainder
    # via flush_persistent().
    CAPS_MEMO_MAX = 512
    CAPS_MEMO_FLUSH_EVERY = 8
    CAPS_MEMO_FLUSH_IDLE_S = 0.25

    def _memoize_caps(self, fingerprint, plan: QueryPlan,
                      caps: Capacities) -> None:
        self._caps_memo_insert(fingerprint,
                               self._caps_to_order(plan, caps))

    def _caps_memo_insert(self, fingerprint, ordered) -> None:
        import time as _time

        with self._caps_lock:
            if fingerprint not in self._caps_memo and \
                    len(self._caps_memo) >= self.CAPS_MEMO_MAX:
                # evict the oldest half (dict insertion order): the
                # newest converged shapes — the live working set under
                # a storm — stay warm
                for k in list(self._caps_memo)[
                        :len(self._caps_memo) // 2]:
                    del self._caps_memo[k]
            # LRU, not insertion-order: a re-memoized hot shape must
            # move to the young end or the overflow above would evict
            # it as "oldest" despite being actively refreshed
            self._caps_memo.pop(fingerprint, None)
            self._caps_memo[fingerprint] = ordered
            self._memo_dirty += 1
            now = _time.monotonic()
            if self._memo_dirty < self.CAPS_MEMO_FLUSH_EVERY and \
                    now - self._memo_last_write < \
                    self.CAPS_MEMO_FLUSH_IDLE_S:
                return  # coalesce: a later insert or close() flushes
        self._flush_caps_memo()

    def _flush_caps_memo(self) -> None:
        import contextlib
        import os
        import time as _time

        from ..utils.io import atomic_write_json

        # snapshot under the lock (concurrent statements memoize while
        # this thread serializes the items), write the file outside it
        with self._caps_lock:
            if not self._memo_dirty:
                return
            self._memo_dirty = 0
            self._memo_last_write = _time.monotonic()
            payload = [[self._memo_to_json(k), self._memo_to_json(v)]
                       for k, v in self._caps_memo.items()]
        try:
            atomic_write_json(
                self._memo_path(),
                {"version": self.CAPS_MEMO_VERSION,
                 "memo": payload})
            self._memo_writes += 1
            # complete the pkl→json migration: the pickle predecessor
            # must not linger in a shared data_dir
            with contextlib.suppress(OSError):
                os.unlink(os.path.join(self.store.data_dir,
                                       "caps_memo.pkl"))
        except (OSError, TypeError, ValueError):
            pass  # persistence is best-effort; in-memory memo suffices

    def flush_persistent(self) -> None:
        """Drain debounced persistence (caps memo, exec-cache hotness
        index) — Session.close() calls this so a clean shutdown leaves
        the warm-start state current on disk."""
        self._flush_caps_memo()
        self.exec_cache.flush_index()

    # ------------------------------------------------------------------
    # feedback sizing: actual×slack, with headroom so equal-sized reruns
    # never re-overflow; only shrink when the win is material (a
    # recompile costs real time on remote-attached chips).  The
    # threshold is PER KIND: repartition/agg_out are pure buffer sizes
    # (tightening is free — smaller shuffles and slices), but
    # scan_out/join_out tightening can INTRODUCE a compaction pass, so
    # those shrink only by compaction_pays' ratio.
    TIGHTEN_SLACK = 1.3
    # agg_grid = the bucketed grid's live-group count: it shares the
    # agg_out capacity table but shrinking it INSTALLS a compaction
    # pass over the slot grid, so it pays the compaction economics
    TIGHTEN_THRESHOLD = {"repartition": 0.85, "agg_out": 0.85,
                         "scan_out": 1.0 / COMPACTION_MIN_SHRINK,
                         "join_out": 1.0 / COMPACTION_MIN_SHRINK,
                         "agg_grid": 1.0 / COMPACTION_MIN_SHRINK}

    def _tighten_caps(self, plan: QueryPlan, caps: Capacities,
                      stage_keys, actuals) -> Capacities | None:
        """Shrink buffers whose recorded actual row counts sit far below
        their current size.  stage_keys entries are (walk_index, kind,
        width); actuals is the per-stage max over devices.  Returns the
        tightened Capacities, or None when nothing material changed."""
        from .cache import plan_order

        rev = {i: nid for nid, i in plan_order(plan).items()}
        new = {"repartition": dict(caps.repartition),
               "join_out": dict(caps.join_out),
               "agg_out": dict(caps.agg_out),
               "scan_out": dict(caps.scan_out)}
        changed = False
        for (widx, kind, width), actual in zip(stage_keys, actuals):
            nid = rev.get(widx)
            if nid is None:
                continue
            table = new["agg_out" if kind == "agg_grid" else kind]
            cur = table.get(nid, width)
            t = _round_cap(int(int(actual) * self.TIGHTEN_SLACK) + 128)
            if t < cur * self.TIGHTEN_THRESHOLD[kind]:
                table[nid] = t
                changed = True
        if not changed:
            return None
        return Capacities(new["repartition"], new["join_out"],
                          new["agg_out"], caps.dense_off,
                          new["scan_out"], caps.output_repart)

    # ------------------------------------------------------------------
    @staticmethod
    def _caps_to_order(plan: QueryPlan, caps: Capacities) -> tuple:
        """id(node)-keyed Capacities → plan-walk-index-keyed tuple
        (node ids are per-plan-instance; walk order is structural)."""
        from .cache import plan_order

        order = plan_order(plan)
        return ({order[k]: v for k, v in caps.repartition.items()},
                {order[k]: v for k, v in caps.join_out.items()},
                {order[k]: v for k, v in caps.agg_out.items()},
                caps.dense_off,
                {order[k]: v for k, v in caps.scan_out.items()},
                caps.output_repart)

    @staticmethod
    def _caps_from_order(plan: QueryPlan, memo: tuple) -> Capacities:
        from .cache import plan_order

        rev = {i: nid for nid, i in plan_order(plan).items()}
        repart, join_out, agg_out, dense_off, scan_out, output_repart = \
            memo

        def by_node(table: dict) -> dict:
            return {rev[i]: v for i, v in table.items()}

        return Capacities(by_node(repart), by_node(join_out),
                          by_node(agg_out), dense_off, by_node(scan_out),
                          output_repart)

    def _initial_capacities(self, plan: QueryPlan, feeds,
                            dense_off: bool = False) -> Capacities:
        """Propagate static per-device capacities bottom-up."""
        repart_factor = self.settings.get("repartition_capacity_factor")
        join_factor = self.settings.get("join_output_capacity_factor")
        group_factor = self.settings.get("agg_group_capacity_factor")
        group_kernel = self.settings.get("group_by_kernel")
        n_dev = plan.n_devices
        repart: dict[int, int] = {}
        join_out: dict[int, int] = {}
        agg_out: dict[int, int] = {}
        scan_out: dict[int, int] = {}

        def cap_of(node, skip_emit: bool = False) -> int:
            """skip_emit: the node's OWN output buffer is never
            allocated (aggregate pushdown consumes the join without pair
            emission) — register child + repartition capacities only."""
            if isinstance(node, ScanNode):
                base = feeds[id(node)].capacity
                if node.filter is None:
                    return base
                # selective scans compact survivors so downstream buffers
                # size by the filtered estimate, not the table (1.5×
                # slack over the uniform-assumption estimate; an
                # under-estimate overflows and retries doubled, and the
                # converged sizes are memoized per plan fingerprint)
                est = max(1, node.est_rows)
                per_dev = (est if not feeds[id(node)].sharded
                           else -(-est // n_dev))
                k = _round_cap(int(per_dev * 1.5) + 512)
                if compaction_pays(k, base):
                    scan_out[id(node)] = k
                    return k
                return base
            if isinstance(node, ProjectNode):
                return cap_of(node.input)
            if isinstance(node, JoinNode):
                lcap = cap_of(node.left)
                rcap = cap_of(node.right)
                if node.strategy == "repart_right":
                    repart[id(node)] = _round_cap(int(rcap * repart_factor))
                    rcap = n_dev * repart[id(node)]
                elif node.strategy == "repart_left":
                    repart[id(node)] = _round_cap(int(lcap * repart_factor))
                    lcap = n_dev * repart[id(node)]
                elif node.strategy == "repart_both":
                    repart[id(node)] = _round_cap(
                        int(max(lcap, rcap) * repart_factor))
                    lcap = n_dev * repart[id(node)]
                    rcap = n_dev * repart[id(node)]
                if node.join_type in ("semi", "anti"):
                    # output rows ARE probe rows (no emission buffer);
                    # only a cross-side residual needs a candidate-pair
                    # expansion buffer
                    if node.residual is not None:
                        join_out[id(node)] = _round_cap(int(
                            lcap * join_factor
                            * max(1.0, node.est_expansion)) + 128)
                    return lcap
                if skip_emit:
                    # aggregate pushdown consumes the join through
                    # _bounds (no fused lookup, no pair emission): no
                    # emission buffer exists
                    return max(lcap, rcap)
                if getattr(node, "fuse_lookup", False) and not dense_off \
                        and node.left_keys:
                    # fused PK lookup: one output slot per probe row; a
                    # selective build side (FK match fraction < 1)
                    # additionally compacts the output so downstream
                    # aggregates/joins size by the join estimate
                    out = (rcap if node.join_type == "inner"
                           and node.build_side == "left" else lcap)
                    if node.join_type == "inner" and node.residual is None:
                        est = max(1, node.est_rows)
                        k = _round_cap(int(-(-est // n_dev) * 1.5) + 512)
                        if compaction_pays(k, out):
                            out = k
                    join_out[id(node)] = out
                    return out
                if not node.left_keys:
                    # cartesian: output is the full product (the gathered
                    # build side is n_dev shards wide)
                    if node.strategy == "cartesian_gather":
                        rcap = rcap * n_dev
                    out = _round_cap(lcap * rcap)
                else:
                    # probe side is the left/outer side; est_expansion
                    # scales for many-to-many fan-out
                    out = _round_cap(int(
                        lcap * join_factor
                        * max(1.0, node.est_expansion)) + 128)
                    if node.join_type in ("left", "full"):
                        # unmatched probe rows add up to lcap extra slots
                        out = _round_cap(out + lcap)
                join_out[id(node)] = out
                if node.join_type in ("right", "full"):
                    # the unmatched-build segment appends rcap fixed slots
                    out = out + rcap
                return out
            if isinstance(node, WindowNode):
                in_cap = cap_of(node.input)
                if node.combine != "repartition":
                    return in_cap
                if node.partition_by:
                    repart[id(node)] = _round_cap(
                        int(in_cap * repart_factor))
                else:
                    # one global partition: every row on one device
                    repart[id(node)] = _round_cap(
                        int(in_cap * n_dev * repart_factor))
                return n_dev * repart[id(node)]
            if isinstance(node, AggregateNode):
                if node.combine == "global" and \
                        isinstance(node.input, JoinNode) and \
                        PlanCompiler.agg_pushdown_shape(node):
                    cap_of(node.input, skip_emit=True)
                    return 1
                in_cap = cap_of(node.input)
                if node.combine == "global":
                    return 1
                if node.dense_keys is not None and not dense_off and \
                        node.combine in ("local", "repartition"):
                    return node.dense_total  # fixed dense-grid output
                if PlanCompiler.agg_bucket_shape(node, group_kernel,
                                                 dense_off):
                    # bucketed dense grid: the pack is sized by in_cap
                    # alone (ops.groupby.group_pack_shape), so it has
                    # no capacity here; the [bucket_total] output grid
                    # compacts to the estimated group count where
                    # compaction pays
                    out = node.bucket_total
                    est_g = node.est_groups
                    if est_g:
                        k = _round_cap(
                            min(out, int(est_g * group_factor) + 16))
                        if compaction_pays(k, out):
                            agg_out[id(node)] = k
                            out = k
                    return out
                est_g = node.est_groups
                if est_g:
                    # group-count estimate bounds every aggregate buffer:
                    # a 4-group Q1 stops shipping input-sized arrays
                    # through the shuffle and back to the host
                    agg_cap = _round_cap(
                        min(in_cap, int(est_g * group_factor) + 16))
                    agg_out[id(node)] = agg_cap
                    if node.combine == "repartition":
                        # worst case: every group hashes to one target
                        repart[id(node)] = agg_cap
                    return agg_cap
                if node.combine == "repartition":
                    repart[id(node)] = _round_cap(int(in_cap * repart_factor))
                    return n_dev * repart[id(node)]
                return in_cap
            raise ExecutionError(f"unknown node {type(node).__name__}")

        root_cap = cap_of(plan.root)
        out_rp = None
        if plan.output_repart is not None:
            # balanced-hash expectation with headroom; skew overflows
            # and regrows through the normal retry path
            out_rp = _round_cap(
                int(-(-root_cap // n_dev) * repart_factor) + 256)
        return Capacities(repart, join_out, agg_out, dense_off, scan_out,
                          out_rp)

    # ------------------------------------------------------------------
    def _host_combine(self, plan: QueryPlan, cols, nulls, valid,
                      raw: bool = False) -> ResultSet:
        valid_2d = np.asarray(valid)
        device_rows = (valid_2d.sum(axis=1).astype(int).tolist()
                       if valid_2d.ndim == 2 else None)
        valid_np = valid_2d.reshape(-1)
        flat_cols: dict[str, np.ndarray] = {}
        flat_nulls: dict[str, np.ndarray] = {}
        for cid in cols:
            arr = np.asarray(cols[cid]).reshape(-1)
            flat_cols[cid] = arr[valid_np]
            nmask = np.asarray(nulls[cid]).reshape(-1)
            flat_nulls[cid] = nmask[valid_np]
        src = ColumnSource(flat_cols, flat_nulls)
        n = int(valid_np.sum())

        # HAVING
        if plan.host_having is not None:
            mask = np.broadcast_to(np.asarray(
                predicate_mask(plan.host_having, src, np)), (n,))
            flat_cols = {c: a[mask] for c, a in flat_cols.items()}
            flat_nulls = {c: a[mask] for c, a in flat_nulls.items()}
            src = ColumnSource(flat_cols, flat_nulls)
            n = int(mask.sum())
            device_rows = None  # filtered: per-device counts are stale

        # select outputs
        out_cols: dict[str, object] = {}
        out_nulls: dict[str, np.ndarray] = {}
        out_dtypes: dict[str, DataType] = {}
        decode_map: dict[str, tuple[str, str]] = {}
        names: list[str] = []
        for e, name in plan.host_select:
            v, nmask = evaluate(e, src, np)
            v = np.broadcast_to(np.asarray(v), (n,)).copy()
            nmask = (np.zeros(n, dtype=bool) if nmask is None
                     else np.broadcast_to(np.asarray(nmask), (n,)).copy())
            out_name = self._unique_name(name, names)
            names.append(out_name)
            out_cols[out_name] = v
            out_nulls[out_name] = nmask
            out_dtypes[out_name] = e.dtype
            # decode dictionary strings / format dates (vectorized —
            # result sets can be SF100-sized); raw mode keeps codes/day
            # numbers typed so bulk consumers (INSERT..SELECT) skip the
            # decode→re-encode round trip
            if raw:
                if isinstance(e, ir.BCol) and e.cid in plan.decode:
                    decode_map[out_name] = plan.decode[e.cid]
            elif isinstance(e, ir.BCol) and e.cid in plan.decode:
                d = resolve_decode(self.store, plan.decode[e.cid])
                out_cols[out_name] = _decode_strings(d, v, nmask)
            elif e.dtype == DataType.DATE:
                out_cols[out_name] = _format_dates(v, nmask)

        # ORDER BY (host): exact multi-key sort via factorize + lexsort.
        # Values factorize through np.unique (ascending codes — exact for
        # any dtype incl. decoded strings); DESC negates codes; NULL
        # placement follows PG defaults (NULLS LAST for ASC, FIRST for DESC)
        if plan.host_order_by and n > 0:
            device_rows = None  # re-sorted: device-major order destroyed
            order_src = ColumnSource(flat_cols, flat_nulls)
            lex_keys = []  # built primary-first, reversed for np.lexsort
            for e, desc, nulls_first in plan.host_order_by:
                v, nmask = evaluate(e, order_src, np)
                v = np.broadcast_to(np.asarray(v), (n,))
                nmask = (np.zeros(n, dtype=bool) if nmask is None
                         else np.broadcast_to(np.asarray(nmask), (n,)))
                if isinstance(e, ir.BCol) and e.cid in plan.decode:
                    d = resolve_decode(self.store, plan.decode[e.cid])
                    lut = np.asarray(d.values + [""], dtype=object)
                    codes = np.asarray(v).astype(np.int64)
                    oob = (codes < 0) | (codes >= len(d))
                    v = lut[np.where(oob, len(d), codes)].astype(str)
                _, codes = np.unique(v, return_inverse=True)
                codes = codes.astype(np.int64)
                if desc:
                    codes = -codes
                nulls_last = (not nulls_first if nulls_first is not None
                              else not desc)
                null_key = nmask if nulls_last else ~nmask
                # per item: null placement outranks the value code
                lex_keys.append(null_key.astype(np.int8))
                lex_keys.append(codes)
            order = np.lexsort(tuple(reversed(lex_keys)))
            for c in names:
                out_cols[c] = out_cols[c][order]
                out_nulls[c] = out_nulls[c][order]
        # OFFSET / LIMIT
        lo = plan.offset or 0
        hi = n if plan.limit is None else min(n, lo + plan.limit)
        if lo or hi < n:
            for c in names:
                out_cols[c] = out_cols[c][lo:hi]
                out_nulls[c] = out_nulls[c][lo:hi]
            device_rows = None  # sliced: per-device counts are stale
        final_n = max(0, hi - lo)

        if raw:
            return ResultSet(names, out_cols, final_n, dtypes=out_dtypes,
                             null_masks=out_nulls, decode_map=decode_map,
                             device_rows=device_rows)
        # surface NULLs as None in object columns
        for c in names:
            if out_nulls[c].any():
                col = np.asarray(out_cols[c], dtype=object)
                col[out_nulls[c]] = None
                out_cols[c] = col
        return ResultSet(names, out_cols, final_n, dtypes=out_dtypes,
                         device_rows=device_rows)

    @staticmethod
    def _unique_name(name: str, taken: list[str]) -> str:
        if name not in taken:
            return name
        i = 1
        while f"{name}_{i}" in taken:
            i += 1
        return f"{name}_{i}"


def feed_device_rows(feeds, n_dev: int) -> list[int] | None:
    """Per-device rows-in across the sharded scan feeds (the Mesh:
    line's input column); None when no feed carries per-device counts
    (pure reference-table plans)."""
    totals = [0] * n_dev
    seen = False
    for f in feeds.values():
        dr = getattr(f, "dev_rows", None)
        if dr is None:
            continue
        seen = True
        for d, r in enumerate(dr[:n_dev]):
            totals[d] += int(r)
    return totals if seen else None


def _plan_buffer_bytes(plan: QueryPlan, caps: Capacities,
                       group_kernel: str) -> int:
    """Worst single-buffer estimate for a capacity assignment: each
    join/repartition/aggregate buffer holds its node's output columns at
    the static capacity, per device.  Guards against executing plans
    whose shapes could never fit (a 2G-slot cartesian output would
    otherwise OOM — or segfault — the backend allocator)."""
    nodes = {id(n): n for n in walk_plan(plan.root)}
    worst = 0
    for table, factor in ((caps.join_out, 1), (caps.repartition,
                                               plan.n_devices),
                          (caps.agg_out, 1), (caps.scan_out, 1)):
        for nid, cap in table.items():
            node = nodes.get(nid)
            ncols = len(node.out_columns) if node is not None else 4
            worst = max(worst,
                        cap * factor * (ncols + 2) * 8 * plan.n_devices)
    from ..ops.groupby import group_bucket_count, group_pack_shape

    for node in nodes.values():
        if not (isinstance(node, AggregateNode)
                and PlanCompiler.agg_bucket_shape(node, group_kernel,
                                                  caps.dense_off)):
            continue
        # bucketed group-by: the chunked pack per value column
        # (int64-worst, per device) — its input's slots and one chunk a
        # tile more, whatever the key's distribution (a bare scan's
        # feed is no buffer of this estimate, as everywhere here) — AND
        # the [bucket_total]-slot result grid (results + companions +
        # key reconstruction), which at the 2^24 slot cap is the
        # largest buffer this path allocates when no agg_out
        # compaction applies
        in_cap = max(table.get(id(node.input), 0) for table in
                     (caps.join_out, caps.scan_out, caps.agg_out))
        nc, chunk = group_pack_shape(
            in_cap, group_bucket_count(node.bucket_total))
        ncols = len(node.out_columns)
        worst = max(worst,
                    nc * chunk * (ncols + 2) * 8 * plan.n_devices,
                    node.bucket_total * (ncols + 2) * 8 * plan.n_devices)
    return worst


def _decode_strings(d, codes, nmask) -> np.ndarray:
    """Vectorized dictionary decode: codes → object array (None = NULL)."""
    lut = np.asarray(d.values + [None], dtype=object)
    codes = np.asarray(codes).astype(np.int64)
    codes = np.where(nmask | (codes < 0) | (codes >= len(d)), len(d), codes)
    return lut[codes]


def _format_dates(days, nmask) -> np.ndarray:
    """Vectorized day-number → ISO date string (None = NULL)."""
    days = np.asarray(days).astype("int64")
    iso = (days.astype("datetime64[D]")).astype(str).astype(object)
    iso[np.asarray(nmask)] = None
    return iso


