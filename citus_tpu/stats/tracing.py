"""Always-on span flight recorder: per-statement span trees with
cross-thread context propagation, per-statement-class DDSketch latency
histograms, a bounded in-memory ring of recent traces, and a slow-query
log persisted through the durable-write seam.

The counters/EXPLAIN/stat-UDF surface built by earlier PRs answers
"how much" (rows, bytes, retries); nothing answered "where did the
time go" without hand-rolled timers (bench_sf100.py's phase timers,
EXPLAIN ANALYZE's single wall clock).  This module is the timing spine
connecting them — the citus_stat_statements / EXPLAIN ANALYZE pair of
the reference, grown into a flight recorder:

* **Spans** — every statement produces a tree of named spans covering
  parse → WLM queue wait → execution attempts → plan → compile (cache
  hit vs XLA compile) → feed build (the scan pipeline's prefetch /
  wire-encode / transfer / device-decode legs, per column, carried
  across the producer thread) → mesh dispatch/fetch → host combine →
  serving (door-hold, follower wait, batch probe, result-cache
  lookup) → retry backoff and OOM/mesh degradation rungs.  Span names
  live in ``SPAN_NAMES`` (the EXPLAIN_TAGS pattern) so graftlint's
  span-registry rule holds both directions.
* **Context propagation** — the active trace rides a thread-local;
  worker threads the executor already spawns (the scanpipe prefetch
  producer, the stream batch producer) adopt the statement's context
  via :func:`capture_context` / :func:`adopt_context`, which
  force-closes anything the thread leaves open (no span leaks — the
  chaos soak asserts :func:`open_span_count` == 0 post-soak).
* **Histograms** — statement wall times fold into per-statement-class
  DDSketch bucket counts (ops/sketches.py, α ≈ 1% relative error), so
  ``citus_stat_latency()`` reports honest p50/p95/p99 without storing
  raw samples.
* **Ring + slow log** — the last `trace_ring_statements` traces stay
  in memory (span count per trace capped, so an 8-session hammer
  cannot grow memory without bound); statements slower than
  `trace_slow_statement_ms` persist their full tree as JSON through
  utils/io (newest ``SLOW_TRACE_KEEP`` kept);
  ``tools/trace_summarize.py`` reads them back.
* **The profiler's clock** — every span also enters a
  ``jax.profiler.TraceAnnotation("ct:" + name)`` and leaves it where
  the span closes, so a `jax.profiler` trace of a live session shows
  the statement's phases over the device's operations on one clock.
  The root carries ``stmt=<n>`` (``Trace.stmt_id``; ring, slow log and
  profiler trace join on it) and so does every span recorded under an
  adopted context, which is on another thread's line; on the
  statement's own thread containment in time is the parent link.
  Outside a profiler session an annotation is a check of one atomic;
  a statement that is sampled out or runs with `trace_enabled` off
  builds none.  The device programs' side of the same vocabulary is
  ``STAGE_NAMES`` / :func:`stage_scope`: ``ct.<stage>`` name scopes
  in the operations' ``op_name`` metadata.
* **The collector** — one ``gc.callbacks`` hook for the process
  (:func:`install_gc_hook`): a collection of generation 1 or 2 on a
  thread with a statement open moves that session's
  ``gc_pauses_total`` / ``gc_pause_us_total`` and, on a traced
  statement, is a ``gc.pause`` span under whatever span it stalled.

Overhead: an unarmed `trace_span` is one thread-local read and a None
check; an active span is two `perf_counter` calls, one small object
and one annotation enter/exit pair.  Measured (PERF.md §6, PR 27):
an annotation enter/exit pair is 0.43 µs outside a profiler session
and 0.60 µs inside one (0.58 / 0.94 µs with `stmt`); a warm 16-span
statement costs the recorder 51.8 µs, 9.4 µs of it the annotations
(this sandbox's CPU, JAX 0.9.0; the chip's host runs the same code
slower: its `plan` span reads 1.08 ms, 0.62 ms here).  On the chip,
`tpch1.q1` at 9.3 ms a statement, six seeds a side: with no session
the change's p50 read 0.4 % under the parent's, inside the cell's 2 %
spread (the off-cost is not resolvable); with the profiler on, p50
inside the profiled stretch is 1.0 % over p50 outside it (the parent's
own: 0.5 %).  PR 37 (PERF.md §6): two spans more a statement, the
counters and the collector hook cost nothing resolvable there (p50
9.3528 against the parent's 9.3514 ms); what cost was waiting for the
program's end before its copies were queued, 0.13 ms a statement
(executor/runner.py `_dispatch` queues them first).
"""

from __future__ import annotations

import os
import threading
import time

import jax
from jax.profiler import TraceAnnotation as _Annotation

# -- span-name registry ------------------------------------------------------
# Every named span a statement can record.  Render/record sites call
# trace_span("…") / span_name("…") with the literal, so graftlint's
# span-registry rule can hold both directions (the EXPLAIN_TAGS
# contract: a name used in source must be declared here, a declared
# name must have a live record site).
SPAN_NAMES: dict[str, str] = {
    "statement": "root span: one executed statement, wall-clock",
    "parse": "lexer+parser (hot-statement memo makes repeats ~free)",
    "queue": "WLM admission: classification + slot/HBM queue wait",
    "execute": "one execution attempt under the resilience envelope",
    "gate": "session gate before a statement runs: the replica's "
            "read-only and staleness checks",
    "plan": "recursive planning + bind + distributed planning",
    "subplan": "recursive planning: one subplan (derived table, CTE, set-"
               "operation side, expression subquery) planned and executed "
               "— its own plan … combine are children",
    "subplan.store": "a subplan's rows → temp reference table: typing, "
                     "dictionary interning, the typed arrays handed to "
                     "the store, which holds them in memory",
    "subplan.store.type": "the result's columns typed: DATE text back "
                          "to days, object arrays walked for their "
                          "type, NULL masks, typed copies (meta rows, "
                          "cols)",
    "subplan.store.intern": "a string column's values interned in the "
                            "temp table's dictionary",
    "subplan.store.append": "the temp reference table created and its "
                            "rows held by the store with a stripe "
                            "record's statistics (meta bytes)",
    "subplan.feed": "under feed: an intermediate result's held arrays "
                    "padded and placed on every device, no producer "
                    "thread and no codec",
    "subplan.drop": "a temp table dropped: catalog entry, the held "
                    "rows, its resident feed",
    "route": "path choice between plan and feed: plan-shape "
             "counters, manifest staleness refresh, stream eligibility",
    "feed": "device feed build (eager, pipelined or per-batch)",
    "caps": "capacity resolution: plan fingerprint, capacity memo or "
            "initial capacities, buffer guard, plan-cache key + lookup",
    "compile": "plan-cache resolution (meta cache=hit|miss; a miss "
               "traces + XLA-compiles the mesh program)",
    "compile.cache_load": "persistent executable cache probe: meta + "
                          "CRC verify + AOT deserialize on a hit",
    "compile.single_flight_wait": "follower waiting on another "
                                  "session's in-flight compile of the "
                                  "same shape (compile dedup)",
    "wlm.warmup": "warm-before-admit: one persisted executable "
                  "adopted into the plan cache pre-admission",
    "mesh.dispatch": "compiled program dispatch + on-mesh collectives",
    "mesh.fetch": "device→host pull of outputs + overflow counters",
    "mesh.fetch.wait": "the program has been dispatched and has not "
                       "ended: its launch, its run, the host's wake-up",
    "mesh.fetch.pull": "the program has ended: the packed block and "
                       "the overflow block copied to the host (meta "
                       "bytes)",
    "settle": "after the fetch: overflow verdict, capacity feedback "
              "and memo, shuffle counter",
    "combine": "host-side combine (having/order/limit/decode)",
    "fastpath": "single-shard host execution (router fast path)",
    "scan.prefetch": "scanpipe: stripe read + host decode (producer)",
    "scan.wire_encode": "scanpipe: host wire-encode for device decode",
    "scan.transfer": "scanpipe: accounted host→device placement",
    "scan.device_decode": "scanpipe: on-mesh expand of a wire payload",
    "stream.batch": "stream path: one batched execution round",
    "stream.decode": "stream path: stripe pull + decode for a batch",
    "stream.transfer": "stream path: batch host→device placement",
    "serving.cache_lookup": "result-cache key build + lookup",
    "serving.door_hold": "micro-batch leader holding the door open",
    "serving.batch_wait": "follower waiting on a batch leader",
    "serving.batch_probe": "leader executing one coalesced batch",
    "retry.backoff": "resilience envelope backoff sleep",
    "gc.pause": "a collection of generation 1 or 2 that ran on the "
                "statement's thread, under whatever span was open "
                "(meta gen, collected)",
    "oom.degrade": "OOM ladder rung application",
    "mesh.degrade": "mesh shrink + failover after device loss",
    "replication.ship": "leader→follower batch staging (file diff + "
                        "journal segment + batch.json commit)",
    "replication.apply": "follower roll-forward of committed batches "
                         "behind the apply cursor",
    "replication.promote": "follower→leader promotion: roll forward, "
                           "fence, epoch bump, role flip",
}

# phase attribution for the EXPLAIN ANALYZE Timing line and the
# sum-to-wall contract: walking the tree, a span whose name maps here
# contributes its full duration to the phase and is NOT descended into
# (nested detail — scan.* under feed, serving.* under fastpath — stays
# in the trace but never double-counts a phase)
PHASE_OF: dict[str, str] = {
    "parse": "parse",
    "queue": "queue",
    "gate": "plan",
    "plan": "plan",
    "route": "plan",
    "feed": "feed",
    "caps": "compile",
    "compile": "compile",
    "compile.cache_load": "compile",
    "compile.single_flight_wait": "compile",
    "mesh.dispatch": "device",
    "mesh.fetch": "device",
    "settle": "combine",
    "combine": "combine",
    "fastpath": "fastpath",
    "serving.cache_lookup": "serving",
    "serving.door_hold": "serving",
    "serving.batch_wait": "serving",
    "serving.batch_probe": "serving",
    "retry.backoff": "retry",
    "oom.degrade": "degrade",
    "mesh.degrade": "degrade",
    "replication.ship": "replication",
    "replication.apply": "replication",
    "replication.promote": "replication",
}

PHASE_ORDER = ("parse", "queue", "plan", "feed", "compile", "device",
               "combine", "fastpath", "serving", "retry", "degrade",
               "replication")

# -- stage-name registry -----------------------------------------------------
# Every `ct.<stage>` name scope a device program can carry in its
# operations' op_name metadata, under the span-registry rule's two
# directions like SPAN_NAMES.  The kinds PlanCompiler._record gives
# capacity stages are names of this registry, so a scope, a capacity
# stage and chip_smoke.py's `compiled_stages` are one vocabulary.  An
# operation belongs to the innermost stage of its path; a name marked
# "sub:" only ever appears inside the stages it lists.
STAGE_NAMES: dict[str, str] = {
    "feed_unpack": "shard_map inputs → per-scan Blocks",
    "decode": "scanpipe: on-mesh expand of one wire payload",
    "for": "sub: decode — frame-of-reference expand",
    "dict": "sub: decode — dictionary gather",
    "bits": "sub: decode — packed-bit expand",
    "valid": "sub: decode — row-validity expand",
    "scan_out": "scan filter mask + compaction to the filtered size",
    "repartition": "shuffle: route, pack, all_to_all, flatten",
    "pack": "sub: repartition — pack_by_target's radix pack; "
            "agg_bucket — the sort by slot and the chunks cut from it",
    "exchange": "sub: repartition — the all_to_all",
    "unpack": "sub: repartition — flatten of the exchanged pack",
    "lookup_join": "lookup join: dense directory, or sort-and-scan over "
                   "a large extent (or the sorted-bounds fallback), and "
                   "match counting",
    "dense": "sub: lookup_join — the dense directory's build (one "
             "scatter, the duplicate check's gather) and its probe (one "
             "gather a probe row)",
    "carry": "sub: lookup_join — the scans that carry each build row's "
             "index forward to its probe rows",
    "join_range": "fused inner lookup: the build keys' range, the probe "
                  "rows inside it, their compaction and the keys' gather "
                  "at its size",
    "join_out": "join keys, pair emission / build-column gathers, "
                "residual filter, compaction",
    "expand": "sub: join_out — the general (build side not unique) "
              "path's pair emission: match ranges, prefix sum, one slot "
              "a pair",
    "compact": "sub: scan_out, join_range, join_out, agg_out — "
               "survivors' positions by one sort; the columns follow as "
               "that row index and are gathered where they are read "
               "(`deferred`)",
    "deferred": "sub: any stage that reads a column — the gather of a "
                "column that crossed a compaction or a lookup as a row "
                "index (executor/batch.py Block.take), and the "
                "composition of two such indexes",
    "agg_grid": "dense-grid group-by + psum combine",
    "agg_bucket": "bucketed dense-grid group-by",
    "agg_sort": "sort-path group-by (both levels of a repartition "
                "combine)",
    "sort": "sub: agg_sort, lookup_join, window — the key sort "
            "(lookup_join: build side, both sides together, back to "
            "probe order; window: the lexsort and the partition and peer "
            "boundaries in its order)",
    "reduce": "sub: agg_sort — boundaries + segment reductions",
    "agg_out": "group slots cut to the planned capacity",
    "agg_global": "no GROUP BY: per-device reduce + psum/pmin/pmax "
                  "(also the join-aggregate pushdown's finish)",
    "topk": "per-device ORDER BY + LIMIT",
    "window": "window functions: partition sort + segmented scans "
              "(over groups: HAVING first)",
    "scan": "sub: window — the partition and peer starts, the "
            "segmented scans, and the scatter back to row order",
    "project": "projection expressions",
    "output_pack": "outputs bit-packed into the two fetched arrays",
}


def stage_scope(name: str):
    """`with stage_scope("repartition"):` — operations traced inside
    carry `ct.repartition` in their op_name path.  Metadata only: the
    compiled program and every cache key stay what they were.
    KeyError on an unregistered stage."""
    STAGE_NAMES[name]
    return jax.named_scope("ct." + name)


# spans kept per trace: a runaway statement (thousands of stripes ×
# columns) truncates instead of growing the ring without bound
MAX_SPANS_PER_TRACE = 8192
SLOW_TRACE_KEEP = 32
SLOW_TRACE_DIR = "slow_traces"
# statement text / class stored on traces and histogram keys is
# clamped: a bulk INSERT's normalized text is megabytes of "( ?, ?, ?"
# — the ring, the slow log and citus_stat_latency() need the head,
# not the literal list (prefixes stay stable per class, so clamped
# keys still aggregate correctly)
MAX_SQL_CHARS = 400


def clamp_sql(text: str) -> str:
    """The clamped form under which a statement appears in traces and
    histogram keys (bench drivers compare against it when checking a
    trace belongs to the statement they measured)."""
    if len(text) <= MAX_SQL_CHARS:
        return text
    return text[:MAX_SQL_CHARS] + " …"


_clamp = clamp_sql


def span_name(name: str) -> str:
    """Return the name verbatim; KeyError on an unregistered span (the
    runtime backstop for the static span-registry rule)."""
    SPAN_NAMES[name]
    return name


class Span:
    """One timed region.  `children` is appended from the owning thread
    (and, under `feed`, from an adopting producer thread) — list.append
    is GIL-atomic, and readers only walk finished traces or closed
    children, so no lock rides the hot path.

    The span is its OWN context manager (`trace_span` opens it and
    pushes it; `__exit__` closes and pops): the serving scenario runs
    thousands of statements per second, so one object per span is the
    budget — a separate handle object measurably costs QPS."""

    __slots__ = ("name", "t0", "t1", "tid", "meta", "children",
                 "_stk", "_tr", "_ann")

    def __init__(self, name: str, t0: float, tid: int,
                 meta: dict | None = None, stk: list | None = None,
                 tr: "Trace | None" = None):
        self.name = name
        self.t0 = t0
        self.t1 = None
        self.tid = tid
        self.meta = meta
        # eager list: a lazy first-child init would race between the
        # statement thread and an adopted producer both appending
        # under the feed span (list.append itself is GIL-atomic)
        self.children: list[Span] = []
        self._stk = stk
        self._tr = tr
        self._ann = None

    def duration(self) -> float:
        return (self.t1 if self.t1 is not None
                else time.perf_counter()) - self.t0

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb, _pc=time.perf_counter):
        self.t1 = _pc()
        _leave(self)
        if exc_type is not None:
            m = self.meta or {}
            m["error"] = exc_type.__name__
            self.meta = m
        stack = self._stk
        # pop back to (and including) this span; anything above it was
        # opened inside the block and never closed — count the leak so
        # tests can flag it, and never corrupt the stack
        while stack and stack[-1] is not self:
            stray = stack.pop()
            if stray.t1 is None:
                stray.t1 = self.t1
            _leave(stray)
            if self._tr is not None:
                self._tr.leaked += 1
        if stack:
            stack.pop()
        return False


def _annotate(sp: Span, stmt_id: int | None) -> None:
    """Enter the span's event on the profiler's clock; `stmt_id` where
    the event's line does not say which statement it belongs to."""
    ann = (_Annotation("ct:" + sp.name) if stmt_id is None
           else _Annotation("ct:" + sp.name, stmt=stmt_id))
    ann.__enter__()
    sp._ann = ann


def _leave(sp: Span) -> None:
    ann = sp._ann
    if ann is not None:
        sp._ann = None
        ann.__exit__(None, None, None)


class Trace:
    """One statement's span tree plus bookkeeping flags."""

    __slots__ = ("sql", "cls", "root", "spans", "truncated", "leaked",
                 "wall_ms", "error", "stmt_id")

    def __init__(self, sql: str, root: Span, stmt_id: int = 0):
        self.sql = sql
        # the recorder's statement number, `stmt` of the root's event
        # in a profiler trace
        self.stmt_id = stmt_id
        self.cls: str | None = None
        self.root = root
        # `spans`/`leaked` are bumped with plain `+=` from the
        # statement thread AND adopted producer threads: a lost
        # increment under that race only softens the (8192-span)
        # truncation backstop by a few spans — to_dict() recounts
        # exactly from the tree, so the published number is never the
        # racy one
        self.spans = 1
        self.truncated = False
        self.leaked = 0
        self.wall_ms: float | None = None
        self.error: str | None = None

    def to_dict(self) -> dict:
        base = self.root.t0
        exact = 0

        def span_dict(s: Span) -> dict:
            nonlocal exact
            exact += 1
            t1 = s.t1 if s.t1 is not None else s.t0
            d = {"name": s.name,
                 "t0_ms": round((s.t0 - base) * 1000.0, 4),
                 "dur_ms": round((t1 - s.t0) * 1000.0, 4),
                 "tid": s.tid}
            if s.meta:
                d["meta"] = dict(s.meta)
            kids = sorted(s.children, key=lambda c: c.t0)
            if kids:
                d["children"] = [span_dict(c) for c in kids]
            return d

        root = span_dict(self.root)
        return {"schema": 1, "sql": self.sql, "class": self.cls,
                "stmt_id": self.stmt_id,
                "wall_ms": self.wall_ms, "spans": exact,
                "truncated": self.truncated, "leaked": self.leaked,
                "error": self.error, "root": root}


# -- thread-local context ----------------------------------------------------
_tls = threading.local()
# tid → open-span stack, registered on a thread's first span so
# open_span_count() can see every thread (the StatCounters slot
# pattern); dead threads' entries are pruned on new registrations
_stacks_lock = threading.Lock()
_stacks: dict[int, list] = {}


def _tls_state():
    st = getattr(_tls, "state", None)
    if st is None:
        # "stmt": the statement number while a context is adopted;
        # "counters": the StatCounters of the session whose statement
        # is open on this thread, traced or not, and "gc": a collection
        # in progress here (what the collector hook below reads)
        st = _tls.state = {"trace": None, "stack": [], "stmt": None,
                           "counters": None, "gc": None}
        tid = threading.get_ident()
        with _stacks_lock:
            live = {t.ident for t in threading.enumerate()}
            for dead in [t for t in _stacks if t not in live]:
                del _stacks[dead]
            _stacks[tid] = st["stack"]
    return st


def open_span_count() -> int:
    """Spans currently open across EVERY thread that ever recorded one
    — 0 whenever no statement is in flight (the post-soak no-leak
    assert, like the prefetch-charge ledger)."""
    with _stacks_lock:
        stacks = list(_stacks.values())
    return sum(len(s) for s in stacks)


class _NoopSpan:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, exc_type, exc, tb):
        return False


_NOOP = _NoopSpan()


def trace_span(name: str, _pc=time.perf_counter,
               _ident=threading.get_ident, **meta):
    """Open a named span under the current statement trace; a cheap
    no-op when no trace is active on this thread (tracing off, sampled
    out, or a non-statement thread that never adopted a context).
    The span starts NOW (at the call), is pushed immediately, and the
    `with` block's exit closes it."""
    st = getattr(_tls, "state", None)
    if st is None or st["trace"] is None or not st["stack"]:
        return _NOOP
    tr = st["trace"]
    if tr.spans >= MAX_SPANS_PER_TRACE:
        tr.truncated = True
        return _NOOP
    SPAN_NAMES[name]  # runtime backstop of the span-registry rule
    stack = st["stack"]
    sp = Span(name, _pc(), _ident(), meta or None, stack, tr)
    tr.spans += 1
    stack[-1].children.append(sp)
    stack.append(sp)
    _annotate(sp, st["stmt"])
    return sp


def capture_context():
    """Token for handing the current statement's trace to a worker
    thread (None when nothing is being traced — adopt_context then
    no-ops)."""
    st = getattr(_tls, "state", None)
    if st is None or st["trace"] is None or not st["stack"]:
        return None
    return (st["trace"], st["stack"][-1], st["counters"])


class _AdoptCtx:
    __slots__ = ("token", "prev")

    def __init__(self, token):
        self.token = token
        self.prev = None

    def __enter__(self):
        if self.token is None:
            return None
        trace, parent, counters = self.token
        st = _tls_state()
        self.prev = (st["trace"], list(st["stack"]), st["stmt"],
                     st["counters"])
        st["trace"] = trace
        st["stack"][:] = [parent]
        st["stmt"] = trace.stmt_id
        st["counters"] = counters
        return trace

    def __exit__(self, exc_type, exc, tb):
        if self.token is None:
            return False
        st = _tls_state()
        trace = self.token[0]
        # the adopting thread must close everything it opened: spans
        # still above the borrowed parent are leaks — close them with
        # an honest end time and count them
        now = time.perf_counter()
        while len(st["stack"]) > 1:
            sp = st["stack"].pop()
            if sp.t1 is None:
                sp.t1 = now
            _leave(sp)
            trace.leaked += 1
        (st["trace"], st["stack"][:], st["stmt"],
         st["counters"]) = self.prev
        return False


def adopt_context(token):
    """Adopt a captured statement context on a worker thread for the
    duration of the block: spans recorded inside nest under the span
    that was open at capture time.  Leak-proof by construction — on
    exit anything the thread left open is force-closed and counted."""
    return _AdoptCtx(token)


# -- the collector on the statement's clock -----------------------------------
# One `gc.callbacks` hook for the process, installed by the first
# TraceRecorder.  A collection of generation 1 or 2 that starts on a
# thread with a statement open adds to that session's `gc_pauses_total`
# and `gc_pause_us_total` and, where the statement is traced, records a
# `gc.pause` span under whatever span is open (so a profiler trace
# shows it as `ct:gc.pause`, and benchmark/xspans.py cuts the device's
# idle time to it).  Generation 0 returns after one comparison; a
# collection on a thread with no statement open is not counted
# (`gc.get_stats()` has the process's totals).
_gc_hook_lock = threading.Lock()


def _gc_hook(phase: str, info: dict, _pc=time.perf_counter) -> None:
    if info["generation"] == 0:
        return
    st = getattr(_tls, "state", None)
    if st is None:
        return
    if phase == "start":
        if st["counters"] is None and st["trace"] is None:
            return
        # the span opens before the clock is read and closes after: the
        # counter's microseconds lie inside the span's
        st["gc"] = (trace_span("gc.pause", gen=info["generation"]), _pc())
        return
    began = st["gc"]
    if began is None:
        return
    t1 = _pc()
    st["gc"] = None
    sp, t0 = began
    if sp is not _NOOP:
        sp.meta["collected"] = info["collected"]
        sp.__exit__(None, None, None)
    counters = st["counters"]
    if counters is not None:
        from . import counters as sc

        counters.increment(sc.GC_PAUSES_TOTAL)
        counters.increment(sc.GC_PAUSE_US_TOTAL, int((t1 - t0) * 1e6))


def install_gc_hook() -> None:
    """Idempotent; the hook stays for the life of the process."""
    import gc

    with _gc_hook_lock:
        if _gc_hook not in gc.callbacks:
            gc.callbacks.append(_gc_hook)


# -- per-class latency histograms (DDSketch) --------------------------------
class ClassHist:
    __slots__ = ("calls", "sum_ms", "max_ms", "buckets")

    def __init__(self):
        self.calls = 0
        self.sum_ms = 0.0
        self.max_ms = 0.0
        self.buckets: dict[int, int] = {}

    def record(self, ms: float) -> None:
        from ..ops.sketches import dd_bucket_scalar

        key = dd_bucket_scalar(float(ms))
        self.calls += 1
        self.sum_ms += ms
        if ms > self.max_ms:
            self.max_ms = ms
        self.buckets[key] = self.buckets.get(key, 0) + 1

    @staticmethod
    def quantile_of(buckets: dict[int, int], q: float) -> float | None:
        """Quantile from a bucket-dict SNAPSHOT — callers must pass a
        copy taken under the recorder lock (iterating the live dict
        races concurrent record() calls: torn keys/counts pairs)."""
        import numpy as np

        from ..ops.sketches import dd_quantile

        if not buckets:
            return None
        keys = np.fromiter(buckets.keys(), dtype=np.int64)
        counts = np.fromiter(buckets.values(), dtype=np.int64)
        return dd_quantile(keys, counts, q)


class _StatementHandle:
    """What begin() returns and end() consumes: the wall clock always,
    the span tree only when this statement samples in."""

    __slots__ = ("sql", "t0", "trace", "nested", "outermost")

    def __init__(self, sql, t0, trace, nested=False):
        self.sql = sql
        self.t0 = t0
        self.trace = trace
        self.nested = nested
        # this statement put its session's counters on the thread (a
        # re-entrant execute finds them there) and takes them off
        self.outermost = False


class TraceRecorder:
    """ONE per Session (it rides SessionStats).  Thread-safe: concurrent
    execute() callers each trace their own statement on their own
    thread; the ring/histograms fold under a lock once per statement."""

    def __init__(self, data_dir: str | None = None, settings=None,
                 counters=None):
        self.data_dir = data_dir
        self.settings = settings
        # the session's StatCounters: what a collection on a
        # statement's thread adds to (`_gc_hook`)
        self.counters = counters
        install_gc_hook()
        import itertools

        self._mu = threading.Lock()
        self._ring: list[Trace] = []
        self._hists: dict[str, ClassHist] = {}
        # statement numbers of the recorded trees (Trace.stmt_id)
        self._stmt_seq = itertools.count(1)
        # tick stream of the fast-class auto-degrade
        self._fast_seq = itertools.count(1)
        self._slow_seq = 0
        self.max_hist_classes = 512
        # settings-profile memo keyed by Settings.version: four
        # registry lookups per statement are measurable at serving QPS
        self._cfg_memo = None

    def _cfg(self):
        """(enabled, ring_keep, slow_ms, fast_ms, fast_every) —
        memoized per settings version (a benign race installs the
        same tuple)."""
        settings = self.settings
        if settings is None:
            return (True, 128, 0, 0.0, 1)
        c = self._cfg_memo
        if c is not None and c[0] == settings.version:
            return c[1]
        vals = (bool(settings.get("trace_enabled")),
                max(1, int(settings.get("trace_ring_statements"))),
                settings.get("trace_slow_statement_ms"),
                float(settings.get("trace_fast_statement_ms")),
                max(1, int(settings.get("trace_fast_sample_every"))))
        self._cfg_memo = (settings.version, vals)
        return vals

    # -- statement lifecycle ------------------------------------------------
    def begin(self, sql: str, t0: float | None = None) -> _StatementHandle:
        st = _tls_state()
        h = self._begin(sql, t0, st)
        if st["counters"] is None and self.counters is not None:
            st["counters"] = self.counters
            h.outermost = True
        return h

    def _begin(self, sql: str, t0: float | None, st: dict,
               ) -> _StatementHandle:
        t0 = time.perf_counter() if t0 is None else t0
        if st["trace"] is not None:
            # re-entrant execute on one thread (internal fallback
            # paths): never corrupt the outer statement's stack, and
            # record NOTHING for the inner statement — the outer
            # statement's wall already covers it, so a histogram entry
            # here would double-count the time
            return _StatementHandle(sql, t0, None, nested=True)
        enabled, _keep, _slow, fast_ms, fast_every = self._cfg()
        if not enabled:
            return _StatementHandle(sql, t0, None, nested=True)
        if fast_ms > 0.0 and fast_every > 1:
            # auto-degrade to sampling for PROVEN-fast statement
            # classes (the serving cache-hit hammer): a class whose
            # observed mean wall sits under the threshold after ≥8
            # calls records trees 1-in-N — span trees cost ~15 µs,
            # which is real money on a 0.3 ms statement and nothing on
            # the ≥2 ms statements attribution exists for.  Histograms
            # always update; cold/slow classes always record.  (Racy
            # dict/attr reads are fine: both sides are GIL-atomic and
            # a stale mean only shifts WHEN sampling engages.)
            from .query_stats import fingerprint

            h = self._hists.get(_clamp(fingerprint(sql)))
            if h is not None and h.calls >= 8 and \
                    h.sum_ms < fast_ms * h.calls and \
                    next(self._fast_seq) % fast_every:
                return _StatementHandle(sql, t0, None)
        root = Span(span_name("statement"), t0, threading.get_ident())
        trace = Trace(_clamp(sql), root, next(self._stmt_seq))
        st["trace"] = trace
        st["stack"].append(root)
        _annotate(root, trace.stmt_id)
        return _StatementHandle(sql, t0, trace)

    def end(self, h: _StatementHandle, error: BaseException | None = None,
            ) -> Trace | None:
        t1 = time.perf_counter()
        wall_ms = (t1 - h.t0) * 1000.0
        trace = h.trace
        if h.outermost:
            _tls_state()["counters"] = None
        if trace is not None:
            st = _tls_state()
            root = trace.root
            # close anything the statement left open on this thread
            # (exception unwinding skips no __exit__, so normally only
            # the root is here)
            while st["stack"] and st["stack"][-1] is not root:
                sp = st["stack"].pop()
                if sp.t1 is None:
                    sp.t1 = t1
                _leave(sp)
                trace.leaked += 1
            root.t1 = t1
            _leave(root)
            if st["stack"]:
                st["stack"].pop()
            st["trace"] = None
            trace.wall_ms = round(wall_ms, 4)
            if error is not None:
                trace.error = type(error).__name__
        if h.nested and trace is None:
            return None
        from .query_stats import fingerprint

        cls = _clamp(fingerprint(h.sql))
        if trace is not None:
            trace.cls = cls
        with self._mu:
            hist = self._hists.get(cls)
            if hist is None:
                if len(self._hists) >= self.max_hist_classes:
                    victim = min(self._hists,
                                 key=lambda k: self._hists[k].calls)
                    del self._hists[victim]
                hist = self._hists[cls] = ClassHist()
            hist.record(wall_ms)
            if trace is not None:
                self._ring.append(trace)
                keep = self._cfg()[1]
                if len(self._ring) > keep:
                    del self._ring[:len(self._ring) - keep]
        if trace is not None:
            slow_ms = self._cfg()[2]
            if slow_ms and wall_ms >= slow_ms and self.data_dir:
                try:
                    self._persist_slow(trace)
                except OSError:
                    pass  # a full/readonly disk must not fail the query
        return trace

    # -- slow-query log -----------------------------------------------------
    def _persist_slow(self, trace: Trace) -> None:
        from ..utils.io import atomic_write_json

        d = os.path.join(self.data_dir, SLOW_TRACE_DIR)
        os.makedirs(d, exist_ok=True)
        with self._mu:
            self._slow_seq += 1
            seq = self._slow_seq
        doc = trace.to_dict()
        doc["recorded_unix"] = time.time()
        fname = f"trace_{int(time.time() * 1000):015d}_{seq:04d}.json"
        atomic_write_json(os.path.join(d, fname), doc)
        # bound the log: keep the newest SLOW_TRACE_KEEP files
        names = sorted(n for n in os.listdir(d)
                       if n.startswith("trace_") and n.endswith(".json"))
        for stale in names[:-SLOW_TRACE_KEEP]:
            try:
                os.remove(os.path.join(d, stale))
            except OSError:
                pass  # raced with another session's prune

    # -- read side ----------------------------------------------------------
    def traces(self) -> list[Trace]:
        with self._mu:
            return list(self._ring)

    def last_trace(self) -> dict | None:
        """Newest completed trace as a dict (bench drivers re-derive
        their phase_*_seconds keys from this instead of hand timers)."""
        with self._mu:
            if not self._ring:
                return None
            return self._ring[-1].to_dict()

    def latency_rows(self) -> list[dict]:
        """citus_stat_latency() rows: per-class calls + DDSketch
        quantiles, busiest classes first.  Per-class state is COPIED
        under the lock; quantiles compute on the snapshots (the live
        bucket dicts mutate under concurrent end() calls)."""
        with self._mu:
            items = sorted(
                ((cls, h.calls, h.sum_ms, h.max_ms, dict(h.buckets))
                 for cls, h in self._hists.items()),
                key=lambda t: -t[2])
        rows = []
        qof = ClassHist.quantile_of
        for cls, calls, sum_ms, max_ms, buckets in items:
            rows.append({
                "statement_class": cls,
                "calls": calls,
                "mean_ms": round(sum_ms / calls, 3) if calls else 0,
                "p50_ms": _round_q(qof(buckets, 0.50)),
                "p95_ms": _round_q(qof(buckets, 0.95)),
                "p99_ms": _round_q(qof(buckets, 0.99)),
                "max_ms": round(max_ms, 3),
            })
        return rows

    def reset_latency(self) -> None:
        with self._mu:
            self._hists.clear()

    def ring_bytes(self) -> int:
        """Rough in-memory footprint of the ring (span count × a fixed
        per-span estimate) — the boundedness assert's measuring stick."""
        with self._mu:
            return sum(t.spans for t in self._ring) * 200


def _round_q(v):
    return None if v is None else round(float(v), 3)


# -- phase attribution -------------------------------------------------------
def phase_breakdown(root) -> dict[str, float]:
    """Coarse phase walls in SECONDS from a span tree (`root` is either
    a live Span or a to_dict() span dict).  A span whose name maps in
    PHASE_OF contributes its whole duration and is not descended into,
    so phases never double-count; "other" is the root wall minus every
    attributed phase (glue code, counter folds)."""
    phases = dict.fromkeys(PHASE_ORDER, 0.0)

    def dur_s(s) -> float:
        if isinstance(s, dict):
            return s.get("dur_ms", 0.0) / 1000.0
        return max(0.0, s.duration())

    def kids(s):
        if isinstance(s, dict):
            return s.get("children", ())
        return list(s.children)

    def name_of(s):
        return s["name"] if isinstance(s, dict) else s.name

    def walk(s):
        # an EXPLAIN ANALYZE reads the breakdown mid-statement: spans
        # still open (the in-flight "execute") are containers to
        # descend, never durations to attribute
        still_open = not isinstance(s, dict) and s.t1 is None
        ph = PHASE_OF.get(name_of(s))
        if ph is not None and not still_open:
            phases[ph] += dur_s(s)
            return
        for c in kids(s):
            walk(c)

    for c in kids(root):
        walk(c)
    total = dur_s(root)
    phases["total"] = total
    phases["other"] = max(0.0, total - sum(
        phases[p] for p in PHASE_ORDER))
    return phases


def span_seconds(root, *names: str) -> float:
    """Summed duration of every span named in `names` across the whole
    tree (dict or Span form) — the bench drivers' phase_*_seconds
    derivation."""
    want = set(names)
    out = 0.0

    def walk(s):
        nonlocal out
        if isinstance(s, dict):
            if s["name"] in want:
                out += s.get("dur_ms", 0.0) / 1000.0
            for c in s.get("children", ()):
                walk(c)
        else:
            if s.name in want and s.t1 is not None:
                out += s.duration()
            for c in list(s.children):
                walk(c)

    walk(root)
    return out


def current_root() -> Span | None:
    """The in-flight statement's root span on this thread, or None —
    EXPLAIN ANALYZE reads its own trace-so-far through this."""
    st = getattr(_tls, "state", None)
    if st is None or st["trace"] is None:
        return None
    return st["trace"].root


def format_timing_line(root) -> str:
    """The EXPLAIN ANALYZE Timing payload: total + every nonzero phase,
    in ms (phase names are stable — tests and trace_summarize key on
    them)."""
    ph = phase_breakdown(root)
    parts = [f"total={ph['total'] * 1000:.2f}ms"]
    for name in PHASE_ORDER + ("other",):
        v = ph.get(name, 0.0)
        if v > 0.0005 or name in ("plan", "device"):
            parts.append(f"{name}={v * 1000:.2f}ms")
    return " ".join(parts)
