"""Benchmark driver: the five BASELINE.json configs on one chip, plus the
SF10 scale configs and a columnar-scan bandwidth line.

Prints one JSON line per config; the LAST line is the headline metric
(TPC-H Q1 scan-aggregate throughput), matching the driver contract of a
final `{"metric": ..., "value": N, "unit": ..., "vs_baseline": N}` line.

Baseline yardstick: the reference's only published absolute number — the
columnar engine aggregating 75M rows in 16 s (≈4.69M rows/s) on a 2-vCPU
Azure VM (/root/reference/src/backend/columnar/README.md:303-321).  Every
rows/s config reports against that scan rate; the GB/s line reports
against the same workload expressed in bytes (75M rows × 20 scanned
bytes/row ≈ 0.088 GB/s).

Configs (BASELINE.json):
  1. TPC-H Q1 scan + grouped aggregate over lineitem      [headline]
  2. co-located hash join (orders ⋈ lineitem on orderkey)
  3. single-repartition join (customer ⋈ orders on custkey)
  4. dual-repartition join + global aggregate (psum combine); also at SF10
  5. TPC-H Q3 multi-join (repartition + colocated + grouped agg); also SF10
  +  columnar cold-scan bandwidth (stripe read → HBM → aggregate)

Driver contract hardening: every JSON line is printed and flushed the
moment its config finishes, so a timeout mid-run still leaves parseable
output; a wall-clock budget (BENCH_BUDGET seconds) skips remaining
optional configs once exceeded so the headline always prints.  The SF10
section is ON by default (round-4 VERDICT #1: the scale numbers must be
driver-captured); its ingest caches in .benchdata/bench_sf10 so only
the first run pays the ~14 min single-core generation, and the budget
check skips the section rather than truncating the run.

`python bench.py concurrency` runs the workload-manager A/B instead
(bench_concurrency: N concurrent mixed-tenant sessions, admission gate
off vs on, rows/sec + p50/p99 queue wait — PERF_NOTES round 8).
`python bench.py cold_start` runs the restart-survival A/B
(bench_cold_start: child-process restart-to-first-answer and 8-session
compile-storm p99, executable cache on vs off, plus the single-flight
zero-redundant-compiles ledger — PERF_NOTES round 17).
`python bench.py replica_fleet` runs the log-shipped replica fleet
(bench_replica_fleet: replica QPS scale-out, replica-kill
zero-wrong-rows, leader-kill-to-first-promoted-answer and cold-replica
provision-to-first-answer — PERF_NOTES round 18).
Neither is part of the default sweep: both start child processes that
open sessions, and one process at a time may hold a chip, so their
parents only spawn, one child at a time.

Env knobs: BENCH_SF (default 1.0), BENCH_REPEATS (default 3),
BENCH_REPEAT (best-of-N authority: forces EVERY config — the SF10
section's reduced repeat counts included — to at least N measured
executions and stamps each timed JSON line with the `"repeats"` count
that actually ran, so the emitted artifact itself is the authoritative
best-of-N instead of a hand-curated "best run I saw"), BENCH_ONLY (comma list of config names),
BENCH_SF10 (default 1; 0 disables the SF10 section), BENCH_SF10_SCALE
(default 10.0), BENCH_SF10_DIR (persistent SF10 data dir),
BENCH_EXTRAS (default 0; 1 adds approx/exact count-distinct and
INSERT..SELECT mode configs), BENCH_BUDGET (default 2400 s).
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

BASELINE_ROWS_PER_SEC = 75_000_000 / 16.0  # reference columnar agg scan
# the same reference scan in bytes: vendor_id int4 + quantity int8 ≈ 12
# logical bytes/row, but the table had 8 more columns the row engine read;
# charge the columnar engine only what it scanned (2 cols ≈ 12 B/row)
BASELINE_SCAN_GB_PER_SEC = (75_000_000 * 12) / 16.0 / 1e9


def bench_query(sess, sql: str, rows_processed: int, repeats: int):
    sess.execute(sql)  # warmup: compile + populate caches
    best = float("inf")
    result = None
    # measured reps always record a span tree (the fast-class
    # auto-degrade must not sample out the very run whose trace the
    # artifact keys derive from)
    with sess.settings.override(trace_fast_statement_ms=0):
        for _ in range(repeats):
            t0 = time.perf_counter()
            result = sess.execute(sql)
            best = min(best, time.perf_counter() - t0)
    assert result is not None and result.row_count > 0
    return rows_processed / best, best


def trace_phase_keys(doc, wall_seconds=None, sql=None):
    """phase_*_seconds derived FROM THE SPAN TRACE of a measured run
    (stats/tracing.py) — the drivers used to hand-roll these from
    ScanPhaseStats timers; deriving them from the same trace EXPLAIN
    ANALYZE renders makes artifact and EXPLAIN agree by construction.
    Stamps phase_source="trace" so test_bench_artifacts can gate README
    phase-attribution quotes on trace-derived keys.

    `sql`: the measured statement — when the recorder's fast-class
    auto-degrade sampled THIS run's tree out, last_trace() returns an
    OLDER statement's trace; pairing its walls with this run's wall
    clock would stamp wrong numbers under the provenance tag, so a
    mismatched doc stamps nothing."""
    from citus_tpu.stats.tracing import clamp_sql, span_seconds

    if doc is None or (sql is not None
                       and doc.get("sql") != clamp_sql(sql)):
        return {}
    root = doc["root"]
    transfer = (span_seconds(root, "scan.transfer")
                + span_seconds(root, "stream.transfer"))
    out = {
        "phase_source": "trace",
        "phase_prefetch_decode_seconds": round(
            span_seconds(root, "scan.prefetch")
            + span_seconds(root, "stream.decode"), 4),
        "phase_wire_encode_seconds": round(
            span_seconds(root, "scan.wire_encode"), 4),
        "phase_transfer_dispatch_seconds": round(transfer, 4),
        "phase_device_decode_seconds": round(
            span_seconds(root, "scan.device_decode"), 4),
        "phase_compile_seconds": round(
            span_seconds(root, "compile"), 4),
        "phase_device_execute_seconds": round(
            span_seconds(root, "mesh.dispatch")
            + span_seconds(root, "mesh.fetch"), 4),
    }
    if wall_seconds:
        out["transfer_wall_share"] = round(
            min(1.0, transfer / wall_seconds), 4)
    return out


def trace_acceptance_keys(sess, sql=None):
    """Acceptance evidence for the newest measured statement: the
    top-level-spans-sum-to-wall share of ITS trace and p50/p99 of its
    statement class from the DDSketch histograms.  `sql` guards
    against last_trace() returning a different (auto-degrade-sampled)
    statement's trace — see trace_phase_keys."""
    from citus_tpu.stats.tracing import clamp_sql

    doc = sess.stats.tracing.last_trace()
    if doc is None or (sql is not None
                       and doc.get("sql") != clamp_sql(sql)):
        return {}
    root = doc["root"]
    top_ms = sum(c["dur_ms"] for c in root.get("children", ()))
    out = {"trace_wall_ms": doc["wall_ms"],
           "trace_top_span_share": (round(top_ms / root["dur_ms"], 4)
                                    if root["dur_ms"] else None)}
    cls = doc.get("class")  # traces carry their histogram key
    for row in sess.stats.tracing.latency_rows():
        if row["statement_class"] == cls:
            out["trace_p50_ms"] = row["p50_ms"]
            out["trace_p99_ms"] = row["p99_ms"]
            out["trace_calls"] = row["calls"]
            break
    return out


def bench_cold_scan(sess, n_rows: int):
    """Cold columnar scan: stripe read + decompress + pad + device_put +
    aggregate, with the HBM feed cache emptied first (the plan stays
    compiled — this measures the data path, not XLA).

    Runs the cold scan in the session's resolved scan_pipeline mode AND
    with the pipeline forced off, so the artifact itself carries the
    overlapped-vs-eager A/B; the pipelined run's per-phase walls
    (prefetch+decode, host wire-encode, transfer dispatch, on-device
    decode) and its bytes_on_wire vs bytes_decoded ratio come from the
    executor's ScanPhaseStats (reset per rep; the best rep's snapshot
    is published).  Returns (rate, best, parts, reps, eager_rate,
    eager_best); `parts` keeps the legacy host-decode/transfer split
    (measured separately over the same columns) next to the new phase
    keys so older artifact consumers still parse."""
    from citus_tpu.executor.scanpipe import resolve_scan_mode

    sql = ("select sum(l_quantity), sum(l_extendedprice), "
           "sum(l_discount), sum(l_tax) from lineitem")
    sess.execute(sql)  # compile + warm
    bytes_scanned = n_rows * 4 * 8  # four float64 columns as stored
    reps = 2
    mode = resolve_scan_mode(sess.settings)

    def run_mode(m):
        best, best_stats, best_doc = float("inf"), {}, None
        # trace_fast_statement_ms=0: the measured rep's tree must
        # exist — the phase keys below are derived from it
        with sess.settings.override(scan_pipeline=m,
                                    trace_fast_statement_ms=0):
            for _ in range(reps):
                sess.executor.feed_cache.clear()
                sess.executor.scan_stats.reset()
                t0 = time.perf_counter()
                r = sess.execute(sql)
                dt = time.perf_counter() - t0
                if dt < best:
                    best = dt
                    best_stats = sess.executor.scan_stats.snapshot()
                    best_doc = sess.stats.tracing.last_trace()
                assert r.row_count == 1
        return best, best_stats, best_doc

    best, stats, doc = run_mode(mode)
    eager_best, _, _ = run_mode("off")
    # host-only leg: same stripe read + decompress, no device
    cols = ["l_quantity", "l_extendedprice", "l_discount", "l_tax"]
    decode_best = float("inf")
    decoded_bytes = 0
    for _ in range(reps):
        sess.store._manifests.clear()
        t0 = time.perf_counter()
        decoded_bytes = 0
        for shard in sess.catalog.table_shards("lineitem"):
            vals, _mask, cnt = sess.store.read_shard(
                "lineitem", shard.shard_id, cols)
            decoded_bytes += sum(v.nbytes for v in vals.values())
        decode_best = min(decode_best, time.perf_counter() - t0)
    parts = {
        "host_decode_seconds": round(decode_best, 4),
        "host_decode_gb_per_sec": round(
            decoded_bytes / decode_best / 1e9, 3),
        # legacy split: decode vs remainder of the EAGER arm (the
        # pipelined arm overlaps the phases, so subtracting the serial
        # decode leg from its wall would not decompose anything and
        # could go negative) — the pipelined arm's decomposition is
        # the phase_* keys below
        "transfer_and_dispatch_seconds": round(
            max(0.0, eager_best - decode_best), 4),
        "bytes_decoded": decoded_bytes,
        "bytes_to_device": bytes_scanned,
        # pipelined-scan phase breakdown (best pipelined rep): the
        # phase_*_seconds walls come from the run's SPAN TRACE (the
        # same spans EXPLAIN ANALYZE's Timing line renders), byte
        # totals from ScanPhaseStats (the trace carries no byte
        # ledger); phase_source stamps the provenance for the README
        # honesty test
        "scan_pipeline": mode,
        "prefetch_stalls": stats.get("prefetch_stalls", 0),
        "bytes_on_wire": stats.get("bytes_on_wire", 0),
        "bytes_decoded_pipeline": stats.get("bytes_decoded", 0),
        "wire_ratio": (round(stats["bytes_on_wire"]
                             / stats["bytes_decoded"], 4)
                       if stats.get("bytes_decoded") else None),
        "eager_seconds": round(eager_best, 4),
        "vs_eager": round(eager_best / best, 3) if best else None,
    }
    parts.update(trace_phase_keys(doc, wall_seconds=best, sql=sql))
    return (bytes_scanned / best / 1e9, best, parts, reps,
            bytes_scanned / eager_best / 1e9, eager_best)


def bench_concurrency() -> None:
    """`python bench.py concurrency` — concurrent-throughput A/B for the
    workload manager (PERF_NOTES round 8): N worker sessions over one
    data_dir run an identical mixed-tenant statement stream twice, with
    the admission gate off then on (`wlm_enabled`, 2 slots), printing
    one JSON line per mode with aggregate rows/sec and the p50/p99
    admission queue wait.  Knobs: BENCH_CONC_WORKERS (default 4),
    BENCH_CONC_ITERS (statements per worker, default 10), BENCH_SF
    (default 0.05 — the scenario measures scheduling, not scan speed)."""
    import threading

    from citus_tpu.ingest.tpch import load_into_session
    from citus_tpu.session import Session

    n_workers = int(os.environ.get("BENCH_CONC_WORKERS", "4"))
    n_iters = int(os.environ.get("BENCH_CONC_ITERS", "10"))
    sf = float(os.environ.get("BENCH_SF", "0.05"))
    data_dir = tempfile.mkdtemp(prefix="citus_tpu_conc_")
    try:
        seed_sess = Session(data_dir=data_dir)
        counts = load_into_session(seed_sess, sf=sf, seed=0,
                                   tables={"orders", "lineitem"})
        n_li = counts["lineitem"]
        n_ord = counts["orders"]
        # per-iteration statement mix: a grouped scan-agg, a colocated
        # join, and a fast-path point read (exempt — rides free)
        mix = [
            ("select l_returnflag, count(*), sum(l_quantity) "
             "from lineitem group by l_returnflag", n_li),
            ("select count(*), sum(l_extendedprice) from orders, "
             "lineitem where o_orderkey = l_orderkey", n_ord + n_li),
            ("select o_totalprice from orders where o_orderkey = 1", 1),
        ]

        def run_mode(wlm_on: bool):
            # result cache off: the scenario measures admission
            # scheduling over real executions, not cache hits
            sessions = [Session(
                data_dir=data_dir, wlm_enabled=wlm_on,
                serving_result_cache_bytes=0,
                max_concurrent_statements=2,
                wlm_tenant=f"tenant{i % 2}",
                wlm_tenant_weights="tenant0:3,tenant1:1",
                wlm_default_priority="interactive" if i % 2 == 0
                else "batch")
                for i in range(n_workers)]
            for s in sessions:  # warm every plan cache off the clock
                for sql, _ in mix:
                    s.execute(sql)
            waits: list[float] = []
            waits_lock = threading.Lock()
            rows_done = [0] * n_workers

            def worker(i, s):
                local_waits = []
                for it in range(n_iters):
                    for sql, rows in mix:
                        s.execute(sql)
                        rows_done[i] += rows
                        info = getattr(s._wlm_tls, "last", None)
                        if info is not None:
                            local_waits.append(info["queued_ms"])
                with waits_lock:
                    waits.extend(local_waits)

            threads = [threading.Thread(target=worker, args=(i, s))
                       for i, s in enumerate(sessions)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - t0
            for s in sessions:
                s.close()
            waits.sort()

            def pct(p):
                return (round(waits[min(len(waits) - 1,
                                        int(p * len(waits)))], 2)
                        if waits else 0.0)

            return {
                "metric": "concurrency_rows_per_sec_wlm_"
                          + ("on" if wlm_on else "off"),
                "value": round(sum(rows_done) / elapsed, 1),
                "unit": "rows/s",
                "seconds": round(elapsed, 4),
                "sf": sf,
                "workers": n_workers,
                "iters": n_iters,
                "slots": 2 if wlm_on else None,
                "statements": n_workers * n_iters * len(mix),
                "p50_queue_wait_ms": pct(0.50),
                "p99_queue_wait_ms": pct(0.99),
            }

        seed_sess.close()
        for wlm_on in (False, True):
            print(json.dumps(run_mode(wlm_on)), flush=True)
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)


def bench_memory_pressure() -> None:
    """`python bench.py memory_pressure` — graceful-degradation A/B
    under device-memory starvation (PERF_NOTES round 12): N worker
    sessions over one data_dir run a mixed join/agg statement stream
    while the shared device-memory accountant (executor/hbm.py) is
    armed with a MemSim budget deliberately sized BELOW the workload's
    rehearsed peak, in two modes:

      * `memory_pressure_completed_share_ungoverned` — oom_degradation
        OFF: every allocator OOM surfaces immediately as a clean
        ResourceExhausted (the pre-PR-10 behavior minus the dead
        process);
      * `memory_pressure_completed_share_governed` — the degradation
        ladder ON: evict → shrink → stream → multi-pass before giving
        up.

    Each line reports the completed-statement share, the OOM-error
    rate, ladder counters (oom events / evictions / spill passes) and
    aggregate rows/s, so the artifact records BOTH what the ladder
    saves and what it costs.  Knobs: BENCH_MEM_WORKERS (default 8),
    BENCH_MEM_ITERS (statements per worker, default 6),
    BENCH_MEM_BUDGET_SHARE (budget as a fraction of rehearsed peak,
    default 0.5), BENCH_SF (default 0.05)."""
    import threading

    from citus_tpu.executor.hbm import accountant_for, oom_budget
    from citus_tpu.errors import ResourceExhausted
    from citus_tpu.ingest.tpch import load_into_session
    from citus_tpu.session import Session
    from citus_tpu.stats import counters as mem_sc

    n_workers = int(os.environ.get("BENCH_MEM_WORKERS", "8"))
    n_iters = int(os.environ.get("BENCH_MEM_ITERS", "6"))
    share = float(os.environ.get("BENCH_MEM_BUDGET_SHARE", "0.5"))
    sf = float(os.environ.get("BENCH_SF", "0.05"))
    data_dir = tempfile.mkdtemp(prefix="citus_tpu_mem_")
    try:
        seed_sess = Session(data_dir=data_dir,
                            serving_result_cache_bytes=0)
        counts = load_into_session(seed_sess, sf=sf, seed=0,
                                   tables={"orders", "lineitem"})
        n_li, n_ord = counts["lineitem"], counts["orders"]
        mix = [
            ("select l_returnflag, count(*), sum(l_quantity) "
             "from lineitem group by l_returnflag", n_li),
            ("select count(*), sum(l_extendedprice) from orders, "
             "lineitem where o_orderkey = l_orderkey", n_ord + n_li),
            ("select count(*) from orders, lineitem "
             "where o_custkey = l_suppkey", n_ord + n_li),
        ]
        acc = accountant_for(data_dir)
        # rehearsal: un-failing MemSim records the workload's peak live
        # bytes; the armed budget is a deliberate fraction of it
        for sql, _ in mix:
            seed_sess.execute(sql)
        peak0 = acc.peak_bytes
        with oom_budget(acc):
            seed_sess.executor.feed_cache.clear()
            for sql, _ in mix:
                seed_sess.execute(sql)
        budget = max(1, int(max(acc.peak_bytes, peak0) * share))
        seed_sess.close()

        def run_mode(governed: bool):
            # BOTH arms run with the WLM HBM gate aligned to the armed
            # budget (planned-estimate + measured-pressure admission,
            # oversized statements admit solo, streaming engages by
            # sizing) — the A/B isolates the LADDER: what happens when
            # an allocation still fails anyway
            sessions = [Session(
                data_dir=data_dir, serving_result_cache_bytes=0,
                oom_degradation=governed,
                max_feed_bytes_per_device=budget,
                retry_backoff_base_ms=1, retry_backoff_max_ms=5)
                for _ in range(n_workers)]
            for s in sessions:  # warm plan caches off the clock
                for sql, _ in mix:
                    s.execute(sql)
                s.executor.feed_cache.clear()
            tallies = {"completed": 0, "oom_errors": 0, "other": 0}
            tlock = threading.Lock()
            rows_done = [0] * n_workers
            snap0 = [s.stats.counters.snapshot() for s in sessions]

            def worker(i, s):
                local = {"completed": 0, "oom_errors": 0, "other": 0}
                for _ in range(n_iters):
                    for sql, rows in mix:
                        try:
                            s.execute(sql)
                            local["completed"] += 1
                            rows_done[i] += rows
                        except ResourceExhausted:
                            local["oom_errors"] += 1
                        except Exception:
                            local["other"] += 1
                with tlock:
                    for k, v in local.items():
                        tallies[k] += v

            threads = [threading.Thread(target=worker, args=(i, s))
                       for i, s in enumerate(sessions)]
            t0 = time.perf_counter()
            with oom_budget(acc, budget=budget):
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
            elapsed = time.perf_counter() - t0

            def counter_delta(name):
                return sum(
                    s.stats.counters.snapshot().get(name, 0)
                    - snap0[i].get(name, 0)
                    for i, s in enumerate(sessions))

            oom_events = counter_delta(mem_sc.OOM_EVENTS_TOTAL)
            evictions = counter_delta(mem_sc.CACHE_EVICTIONS_TOTAL)
            spills = counter_delta(mem_sc.SPILL_PASSES_TOTAL)
            shrinks = counter_delta(
                mem_sc.STREAM_BATCH_SHRINKS_TOTAL)
            for s in sessions:
                s.close()
            total = n_workers * n_iters * len(mix)
            return {
                "metric": "memory_pressure_completed_share_"
                          + ("governed" if governed else "ungoverned"),
                "value": round(tallies["completed"] / total, 4),
                "unit": "share",
                "seconds": round(elapsed, 4),
                "sf": sf,
                "workers": n_workers,
                "iters": n_iters,
                "statements": total,
                "budget_bytes": budget,
                "budget_share_of_peak": share,
                "completed": tallies["completed"],
                "oom_errors": tallies["oom_errors"],
                "other_errors": tallies["other"],
                "oom_error_share": round(
                    tallies["oom_errors"] / total, 4),
                "oom_events": oom_events,
                "cache_evictions": evictions,
                "stream_batch_shrinks": shrinks,
                "spill_passes": spills,
                "rows_per_sec": round(sum(rows_done) / elapsed, 1),
            }

        for governed in (False, True):
            print(json.dumps(run_mode(governed)), flush=True)
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)


def bench_serving() -> None:
    """`python bench.py serving` — high-QPS point-lookup A/B for the
    serving layer (PERF_NOTES round 11): N concurrent sessions over one
    data_dir fire repeated literal point reads (keys drawn from a hot
    pool — the serving workload shape, routed via the persistent point
    index) in three modes, one JSON line each:

      * `point_lookup_qps_baseline`  — serving OFF (per-statement solo
        dispatch, the pre-PR-8 path);
      * `point_lookup_qps_batched`   — micro-batcher ON, result cache
        OFF (isolates the coalescing win; batch occupancy reported);
      * `point_lookup_qps`           — the full serving layer (batcher
        + CDC-invalidated result cache; cache hit rate reported) —
        the headline stamped into the BENCH artifact.

    Every line reports QPS + per-lookup p50/p99 latency.  Knobs:
    BENCH_SRV_SESSIONS (default 8), BENCH_SRV_ITERS (lookups per
    session, default 150 — long enough that the hot pool's one-time
    misses amortize the way a resident working set does),
    BENCH_SRV_HOT_KEYS (hot-pool size, default 32 — the Zipf head a
    read-mostly serving tier actually absorbs), BENCH_SF (default
    0.05 — the scenario measures dispatch amortization, not scan
    speed)."""
    import threading

    from citus_tpu.ingest.tpch import load_into_session
    from citus_tpu.session import Session
    from citus_tpu.stats import counters as srv_sc

    n_sessions = int(os.environ.get("BENCH_SRV_SESSIONS", "8"))
    n_iters = int(os.environ.get("BENCH_SRV_ITERS", "150"))
    n_hot = int(os.environ.get("BENCH_SRV_HOT_KEYS", "32"))
    sf = float(os.environ.get("BENCH_SF", "0.05"))
    data_dir = tempfile.mkdtemp(prefix="citus_tpu_srv_")
    try:
        # seed with the result cache OFF so warming the point index
        # below cannot pre-fill the cache the measured modes report on
        seed_sess = Session(data_dir=data_dir,
                            serving_result_cache_bytes=0)
        load_into_session(seed_sess, sf=sf, seed=0, tables={"orders"})
        n_ord = seed_sess.store.table_row_count("orders")
        # hot keys that actually exist (orders keys are sparse ints)
        rows = seed_sess.execute(
            f"select o_orderkey from orders where o_orderkey >= 0 "
            f"order by o_orderkey limit {n_hot}").rows()
        hot = [int(k) for (k,) in rows]
        for k in hot:  # build the per-shard index sidecars off the clock
            seed_sess.execute(
                f"select o_totalprice from orders where o_orderkey = {k}")
        seed_sess.close()

        def run_mode(name, serving_on, cache_on, trace_on=True,
                     shared_sessions=None):
            # `shared_sessions`: the trace-overhead A/B flips ONE knob
            # on one warmed session set instead of rebuilding sessions
            # per arm — fresh-session warmup variance (~8% run to run
            # on this sandbox) would otherwise drown a ~1% effect
            own = shared_sessions is None
            if own:
                sessions = [Session(
                    data_dir=data_dir, serving_enabled=serving_on,
                    trace_enabled=trace_on,
                    serving_result_cache_bytes=(256 << 20) if cache_on
                    else 0) for _ in range(n_sessions)]
            else:
                sessions = shared_sessions
                for s in sessions:
                    s.settings.set("trace_enabled", trace_on)
            for s in sessions:  # warm parse/plan caches off the clock
                s.execute("select o_totalprice from orders "
                          f"where o_orderkey = {hot[0]}")
            from citus_tpu.serving.batcher import batcher_for

            # per-mode totals: max_batch_seen is a monotone max, so a
            # snapshot delta cannot isolate this mode — reset instead
            batcher_for(data_dir).reset_totals()
            b0 = batcher_for(data_dir).snapshot()
            lats: list[float] = []
            lats_lock = threading.Lock()
            barrier = threading.Barrier(n_sessions)

            def worker(wid, s):
                rng = __import__("random").Random(wid)
                local = []
                barrier.wait()
                for _ in range(n_iters):
                    k = hot[rng.randrange(len(hot))]
                    t0 = time.perf_counter()
                    r = s.execute("select o_totalprice from orders "
                                  f"where o_orderkey = {k}")
                    local.append(time.perf_counter() - t0)
                    assert r.row_count >= 1
                with lats_lock:
                    lats.extend(local)

            threads = [threading.Thread(target=worker, args=(i, s))
                       for i, s in enumerate(sessions)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - t0
            snaps = [s.stats.counters.snapshot() for s in sessions]
            hits = sum(sn[srv_sc.SERVING_CACHE_HITS_TOTAL]
                       for sn in snaps)
            misses = sum(sn[srv_sc.SERVING_CACHE_MISSES_TOTAL]
                         for sn in snaps)
            b1 = batcher_for(data_dir).snapshot()
            d_disp = b1["batch_dispatch_total"] - \
                b0["batch_dispatch_total"]
            d_lk = b1["batched_lookups_total"] - \
                b0["batched_lookups_total"]
            if own:
                for s in sessions:
                    s.close()
            lats.sort()

            def pct(p):
                return round(lats[min(len(lats) - 1,
                                      int(p * len(lats)))] * 1000, 3)

            total = n_sessions * n_iters
            return {
                "metric": name,
                "value": round(total / elapsed, 1),
                "unit": "lookups/s",
                "seconds": round(elapsed, 4),
                "sf": sf,
                "sessions": n_sessions,
                "iters": n_iters,
                "hot_keys": len(hot),
                "orders_rows": n_ord,
                "p50_ms": pct(0.50),
                "p99_ms": pct(0.99),
                "avg_batch_occupancy": (round(d_lk / d_disp, 3)
                                        if d_disp else 0.0),
                "max_batch_seen": b1["max_batch_seen"],
                "cache_hit_rate": (round(hits / (hits + misses), 3)
                                   if hits + misses else None),
            }

        for name, srv, cache in (
                ("point_lookup_qps_baseline", False, False),
                ("point_lookup_qps_batched", True, False)):
            print(json.dumps(run_mode(name, srv, cache)), flush=True)
        # span-recorder overhead A/B: the full serving stack traced vs
        # trace_enabled=off, measured as paired order-alternating
        # rounds over ONE warmed session set (flipping only the knob).
        # Methodology matters more than the effect here: fresh
        # sessions per arm plus a fixed order charged the sandbox's
        # run-to-run drift to whichever arm ran first and "measured"
        # the recorder at 13% — an overhead that flipped sign when the
        # order flipped.  The always-on recorder must cost ≲2% of
        # steady-state QPS (PERF_NOTES r16).
        import statistics

        ab_rounds = int(os.environ.get("BENCH_SRV_AB_ROUNDS", "4"))
        if ab_rounds < 1:
            # A/B disabled: still print the headline serving line the
            # artifact contract expects
            print(json.dumps(run_mode("point_lookup_qps", True, True)),
                  flush=True)
            return
        ab_sessions = [Session(
            data_dir=data_dir, serving_enabled=True,
            serving_result_cache_bytes=256 << 20)
            for _ in range(n_sessions)]
        try:
            on_lines, off_lines = [], []
            for rnd in range(ab_rounds):
                arms = [("point_lookup_qps", True),
                        ("point_lookup_qps_trace_off", False)]
                if rnd % 2:
                    arms.reverse()
                for aname, tr in arms:
                    line = run_mode(aname, True, True, tr,
                                    shared_sessions=ab_sessions)
                    (on_lines if tr else off_lines).append(line)
        finally:
            for s in ab_sessions:
                s.close()
        on_best = max(on_lines, key=lambda x: x["value"])
        off_best = max(off_lines, key=lambda x: x["value"])
        # overhead from MEDIANS over the post-warmup rounds (a
        # difference of noisy maxima is noisier than either; round 0
        # is cold for both arms), plus the derived per-statement CPU
        # cost in µs — the number that transfers off this sandbox:
        # this scenario's cache-hit statement is ~0.4 ms of pure
        # Python, so the share is its worst case; on any ≥2 ms
        # statement the same µs is <2% of wall
        med_on = statistics.median(
            x["value"] for x in on_lines[1:] or on_lines)
        med_off = statistics.median(
            x["value"] for x in off_lines[1:] or off_lines)
        if med_off:
            off_best["trace_overhead_pct"] = round(
                100.0 * (1.0 - med_on / med_off), 2)
        if med_on and med_off:
            # the hammer is GIL-bound: aggregate QPS ≈ one core's
            # statement rate, so 1/QPS deltas are CPU-per-statement
            off_best["trace_overhead_us_per_stmt"] = round(
                (1.0 / med_on - 1.0 / med_off) * 1e6, 1)
        off_best["trace_ab_rounds"] = ab_rounds
        off_best["trace_ab_qps_on"] = [x["value"] for x in on_lines]
        off_best["trace_ab_qps_off"] = [x["value"] for x in off_lines]
        print(json.dumps(on_best), flush=True)
        print(json.dumps(off_best), flush=True)
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)


def bench_cold_start() -> None:
    """`python bench.py cold_start` — restart-survival A/B for the
    persistent executable cache (PERF_NOTES round 17).  Every arm runs
    in a CHILD PROCESS (the bench_multichip pattern): a restart is a
    process boundary, and an in-process "fresh session" would still
    share jax's in-memory state.  One JSON line per measurement:

      * `cold_start_first_answer_s_cache_on/off` — connect → first Q3
        answer on a fresh process over a warm data_dir, with the
        persisted cache adopted (warm-before-admit engaged) vs the
        recompile-per-process baseline;
      * `cold_start_storm_p99_ms_cache_on/off` — 8 sessions in a fresh
        process all hitting one cold shape concurrently (the deploy-
        under-live-traffic storm): worst first-answer latency, cache
        loads vs 8 redundant compiles;
      * `cold_start_redundant_compiles` — the dedup contract measured
        with an EMPTY disk cache: 8-session cold fan-in through the
        single-flight gate must produce exactly 1 compile for 1
        distinct shape (value = compiles beyond that, i.e. 0);
      * `cold_start_first_answer_speedup` / `cold_start_storm_speedup`
        — the A/B ratios (the ≥10× acceptance numbers).

    Knobs: BENCH_COLD_SF (default 0.01 — compile cost is structural,
    not data-sized, so the dataset stays small), BENCH_COLD_SESSIONS
    (default 8), BENCH_COLD_QUERY (TPC-H name overriding the default
    FK-chain probe)."""
    import subprocess

    here = os.path.abspath(__file__)
    base = tempfile.mkdtemp(prefix="citus_tpu_coldstart_")
    data_dir = os.path.join(base, "data")
    vals: dict[str, float] = {}

    lines: dict[str, dict] = {}

    def child(*args) -> None:
        out = subprocess.run(
            [sys.executable, here, "_cold_child", data_dir, *args],
            capture_output=True, text=True, timeout=1800)
        sys.stderr.write(out.stderr)
        if out.returncode != 0:
            raise RuntimeError(
                f"cold_start child {args} rc={out.returncode}")
        for line in out.stdout.splitlines():
            line = line.strip()
            if not line.startswith("{"):
                continue
            obj = json.loads(line)
            if "metric" in obj:
                vals[obj["metric"]] = obj["value"]
                lines[obj["metric"]] = obj
                print(json.dumps(obj), flush=True)

    try:
        child("seed")
        child("first_answer", "on")
        child("first_answer", "off")
        child("storm", "on")
        child("storm", "off")
        child("storm_dedup")
        for name, on, off, unit in (
                ("cold_start_first_answer_speedup",
                 "cold_start_first_answer_s_cache_on",
                 "cold_start_first_answer_s_cache_off", "x"),
                ("cold_start_storm_speedup",
                 "cold_start_storm_p99_ms_cache_on",
                 "cold_start_storm_p99_ms_cache_off", "x")):
            if vals.get(on) and vals.get(off):
                print(json.dumps({
                    "metric": name, "unit": unit,
                    # off/on: how many times FASTER the cache makes it
                    "value": round(vals[off] / vals[on], 2),
                }), flush=True)
        # executable-acquisition ratio: compile phase + warmup
        # adoption, trace-derived — the isolated cost the cache
        # replaces (wall ratios above additionally carry session
        # init/plan/feed costs both arms pay identically)
        acq_on = lines.get("cold_start_first_answer_s_cache_on",
                           {}).get("executable_acquisition_s")
        acq_off = lines.get("cold_start_first_answer_s_cache_off",
                            {}).get("executable_acquisition_s")
        if acq_on and acq_off:
            print(json.dumps({
                "metric": "cold_start_compile_speedup", "unit": "x",
                "value": round(acq_off / acq_on, 2),
                "acquisition_s_cache_on": acq_on,
                "acquisition_s_cache_off": acq_off,
            }), flush=True)
    finally:
        shutil.rmtree(base, ignore_errors=True)


def _cold_child(data_dir: str, mode: str, arm: str = "on") -> None:
    """One cold_start measurement arm in its own process (see
    bench_cold_start).  Prints JSON metric lines on stdout."""
    import threading

    import numpy as np

    from citus_tpu.executor.execcache import exec_cache_for
    from citus_tpu.ingest.tpch import QUERIES, load_into_session
    from citus_tpu.session import Session
    from citus_tpu.stats import counters as cs

    sf = float(os.environ.get("BENCH_COLD_SF", "0.01"))
    n_sessions = int(os.environ.get("BENCH_COLD_SESSIONS", "8"))
    # the probe is a compile-heavy 7-table FK join chain (multiple
    # repartition stages — the statement class a restart hurts most)
    # WITHOUT subqueries: subplan temp tables are per-session, so a
    # subquery shape would fingerprint differently in every session
    # and the storm would measure temp-table churn, not compile dedup.
    # BENCH_COLD_QUERY swaps in a named TPC-H query instead.
    probe_name = os.environ.get("BENCH_COLD_QUERY", "")
    storm_sql = QUERIES[probe_name] if probe_name else (
        "select n_name, count(*), "
        "sum(l_extendedprice * (1 - l_discount)), min(o_totalprice), "
        "max(s_acctbal), sum(ps_supplycost * l_quantity) "
        "from orders, lineitem, part, partsupp, supplier, customer, "
        "nation where o_orderkey = l_orderkey "
        "and l_partkey = p_partkey and ps_partkey = l_partkey "
        "and ps_suppkey = l_suppkey and s_suppkey = l_suppkey "
        "and o_custkey = c_custkey and c_nationkey = n_nationkey "
        "group by n_name")
    on = arm == "on"
    # result cache OFF everywhere: a cache-served repeat would measure
    # the serving layer, not restart survival; capacity feedback OFF in
    # the storm arms so one statement is exactly one executable shape
    common = dict(data_dir=data_dir, serving_result_cache_bytes=0)

    if mode == "seed":
        sess = Session(**common)
        load_into_session(sess, sf=sf, seed=0)
        sess.execute(storm_sql)
        sess.close()
        print(json.dumps({"seeded": True, "sf": sf,
                          "probe": probe_name or "fk_chain_7table"}),
              flush=True)
        return

    if mode == "first_answer":
        t0 = time.perf_counter()
        sess = Session(exec_cache_enabled=on,
                       warmup_budget_ms=30_000 if on else 0,
                       **common)
        t_init = time.perf_counter()
        # warm-before-admit runs on its own thread; join it so the
        # adoption cost is measured explicitly (warmup_wall_s) instead
        # of hiding inside the first statement's admission wait
        if sess._warmup_thread is not None:
            sess._warmup_thread.join()
        warmup_wall = time.perf_counter() - t_init
        r = sess.execute(storm_sql)
        wall = time.perf_counter() - t0
        assert r.row_count > 0
        snap = sess.stats.counters.snapshot()
        line = {
            "metric": f"cold_start_first_answer_s_cache_{arm}",
            "value": round(wall, 4), "unit": "s", "sf": sf,
            "exec_cache_hits": snap[cs.EXEC_CACHE_HITS_TOTAL],
            "warmup_compiles": snap[cs.WARMUP_COMPILES_TOTAL],
            "warmup_wall_s": round(warmup_wall, 4),
        }
        # compile-phase attribution from the span trace: the wall
        # above includes session init + feed build (paid identically
        # by both arms); executable ACQUISITION — in-statement compile
        # phase plus the explicit warmup adoption above — is what the
        # cache replaces.  Trace-derived, same provenance contract as
        # the scan phase keys (phase_source="trace")
        phases = trace_phase_keys(sess.stats.tracing.last_trace(),
                                  sql=storm_sql)
        if "phase_compile_seconds" in phases:
            line["phase_source"] = "trace"
            line["phase_compile_seconds"] = \
                phases["phase_compile_seconds"]
            line["executable_acquisition_s"] = round(
                phases["phase_compile_seconds"] + warmup_wall, 4)
        print(json.dumps(line), flush=True)
        sess.close()
        return

    if mode in ("storm", "storm_dedup"):
        ec = exec_cache_for(data_dir)
        if mode == "storm_dedup":
            # the dedup contract needs a COLD disk: wipe the persisted
            # entries so all 8 sessions race one genuinely cold shape
            cache_dir = ec.dir
            for f in (os.listdir(cache_dir)
                      if os.path.isdir(cache_dir) else []):
                os.unlink(os.path.join(cache_dir, f))
        sessions = [Session(exec_cache_enabled=(on or
                                                mode == "storm_dedup"),
                            enable_capacity_feedback=False, **common)
                    for _ in range(n_sessions)]
        barrier = threading.Barrier(n_sessions)
        lats = [0.0] * n_sessions

        def worker(i):
            barrier.wait(timeout=60)
            t0 = time.perf_counter()
            r = sessions[i].execute(storm_sql)
            lats[i] = time.perf_counter() - t0
            assert r.row_count > 0

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_sessions)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        snap = ec.snapshot()
        if mode == "storm":
            print(json.dumps({
                "metric": f"cold_start_storm_p99_ms_cache_{arm}",
                "value": round(
                    float(np.percentile(lats, 99)) * 1000.0, 2),
                "unit": "ms", "sessions": n_sessions, "sf": sf,
                "latencies_ms": [round(x * 1000.0, 2) for x in lats],
                "compiles": snap["compiles_total"],
            }), flush=True)
        else:
            # 1 distinct shape, N sessions: redundant = compiles - 1
            print(json.dumps({
                "metric": "cold_start_redundant_compiles",
                "value": snap["compiles_total"] - 1,
                "unit": "compiles",
                "sessions": n_sessions, "distinct_shapes": 1,
                "compiles_total": snap["compiles_total"],
                "compiles_deduped": snap["gate_deduped_total"],
                "exec_cache_hits": ec.hits_total,
            }), flush=True)
        for s in sessions:
            s.close()
        return
    raise SystemExit(f"unknown _cold_child mode {mode!r}")


def bench_replica_fleet() -> None:
    """`python bench.py replica_fleet` — CDC log-shipped replica fleet
    (PERF_NOTES round 18).  A leader data_dir ships committed stripes +
    the CDC journal to three follower data_dirs.  This parent only
    spawns: every session lives in a `_replica_child`, ONE child at a
    time, because a process that has touched JAX holds the chip and a
    second one then fails or hangs.  The fleet's replicas are therefore
    sessions of one child process, each served by its own thread.  One
    JSON line per measurement:

      * `replica_process_capacity_qps` — UNPACED point-lookup QPS of
        one replica process: the raw per-process capacity of this
        host, and the ceiling the offered load below is sized from;
      * `replica_fleet_single_qps` — one replica serving a paced
        offered load (capacity/(fleet+2) QPS, stamped as
        `offered_qps`): the per-replica serving baseline;
      * `replica_fleet_aggregate_qps` — three replica sessions each
        serving the same offered load concurrently while the leader
        keeps committing and shipping; every answer verified;
      * `replica_kill_wrong_rows` — one replica stops dead mid-storm
        (its session abandoned, never closed); every answer the
        survivors returned must verify against the seeded oracle
        (value is the wrong-answer count: 0);
      * `replica_promote_first_answer_s` — leader death to first
        WRITE answered by a freshly promoted replica, in a cold
        process (connect → citus_promote_replica() → INSERT → SELECT);
      * `replica_provision_first_answer_s` — cold-replica provision:
        empty dir → full reseed ship/apply → first verified answer,
        in a cold process.

    Knobs: BENCH_REPLICA_ROWS (default 20000), BENCH_REPLICA_SECONDS
    (storm length per arm, default 6), BENCH_REPLICA_FLEET (default 3
    replicas)."""
    import subprocess

    here = os.path.abspath(__file__)
    n_rows = int(os.environ.get("BENCH_REPLICA_ROWS", "20000"))
    seconds = float(os.environ.get("BENCH_REPLICA_SECONDS", "6"))
    fleet = int(os.environ.get("BENCH_REPLICA_FLEET", "3"))
    base = tempfile.mkdtemp(prefix="citus_tpu_replfleet_")
    lead = os.path.join(base, "leader")
    replicas = [os.path.join(base, f"replica{i}") for i in range(fleet)]
    vals: dict[str, float] = {}

    def emit(obj) -> None:
        vals[obj["metric"]] = obj["value"]
        print(json.dumps(obj), flush=True)

    def child(dirname, *args) -> dict:
        """Run one child to its end and return its one JSON line."""
        out = subprocess.run(
            [sys.executable, here, "_replica_child", dirname,
             *(str(a) for a in args)],
            capture_output=True, text=True, timeout=600)
        if out.returncode != 0:
            sys.stderr.write(out.stderr)
            raise RuntimeError(
                f"replica child {args[0]} rc={out.returncode}")
        lines = [ln for ln in out.stdout.splitlines()
                 if ln.strip().startswith("{")]
        return json.loads(lines[-1])

    try:
        child(lead, "seed", n_rows, *replicas)

        # raw per-process capacity (unpaced): the host's ceiling
        res = child(replicas[0], "storm", seconds, n_rows, 1, 0)
        assert res["wrong"] == 0, "capacity storm wrong rows"
        capacity = res["qps"]
        emit({"metric": "replica_process_capacity_qps",
              "value": round(capacity, 1), "unit": "queries/s",
              "queries": res["queries"], "rows": n_rows,
              "paced": False, "storm_seconds": seconds})

        # offered load per replica, sized so the WHOLE fleet plus the
        # leader's churn fits the host's capacity (the scale-out
        # question is "does each shared-nothing replica sustain its
        # load", not "does one process run three sessions faster")
        offered = max(10.0, capacity / (fleet + 2))

        # single-replica baseline at the offered load
        res = child(replicas[0], "storm", seconds, n_rows, 1,
                    f"{offered:.3f}")
        assert res["wrong"] == 0, "single-replica storm wrong rows"
        emit({"metric": "replica_fleet_single_qps",
              "value": round(res["qps"], 1), "unit": "queries/s",
              "queries": res["queries"], "rows": n_rows,
              "paced": True, "offered_qps": round(offered, 1),
              "storm_seconds": seconds})

        # fleet storm: N replica sessions at the offered load + live
        # leader churn, all in one child
        out = child(lead, "fleet", seconds, n_rows, f"{offered:.3f}",
                    2, 0, *replicas)
        res = out["replicas"]
        agg = sum(r["qps"] for r in res)
        wrong = sum(r["wrong"] for r in res)
        assert wrong == 0, f"fleet storm wrong rows: {wrong}"
        scaleout = round(
            agg / max(vals["replica_fleet_single_qps"], 1e-9), 2)
        emit({"metric": "replica_fleet_aggregate_qps",
              "value": round(agg, 1), "unit": "queries/s",
              "replicas": fleet, "paced": True,
              "offered_qps_per_replica": round(offered, 1),
              "per_replica_qps": [round(r["qps"], 1) for r in res],
              "batches_shipped_mid_storm": out["shipped"],
              "scaleout_x": scaleout})
        emit({"metric": "replica_fleet_scaleout", "unit": "x",
              "value": scaleout})

        # replica-kill mid-storm: one replica stops dead, survivors
        # keep answering; zero wrong rows across every answered lookup
        out = child(lead, "fleet", seconds, n_rows, f"{offered:.3f}",
                    20, 1, *replicas)
        res = out["replicas"]
        wrong = sum(r["wrong"] for r in res)
        emit({"metric": "replica_kill_wrong_rows", "value": wrong,
              "unit": "rows", "survivors": len(res),
              "answered_by_survivors": sum(r["queries"] for r in res)})
        assert wrong == 0 and len(res) == fleet - 1

        # leader-kill → first promoted answer (cold process; the
        # leader's session ended with the fleet child)
        res = child(replicas[0], "promote", n_rows)
        emit({"metric": "replica_promote_first_answer_s",
              "value": res["wall_s"], "unit": "s",
              "epoch": res["epoch"], "promote_s": res["promote_s"]})

        # cold-replica provision → first verified answer: a brand-new
        # follower of the PROMOTED leader (the post-failover refill)
        res = child(os.path.join(base, "replica_new"), "provision",
                    replicas[0], n_rows)
        emit({"metric": "replica_provision_first_answer_s",
              "value": res["wall_s"], "unit": "s",
              "files_shipped": res["files"]})
    finally:
        shutil.rmtree(base, ignore_errors=True)


def _replica_storm(sess, seconds: float, n_rows: int, seed: int,
                   rate: float, stop=None) -> dict:
    """Point lookups against one replica session for `seconds` (or
    until `stop` is set), every answer checked against the seeded
    oracle.  rate 0 = unpaced (capacity); >0 = closed-loop offered
    load."""
    import random

    rng = random.Random(seed)
    # answer once before the clock starts: session warm-up is the
    # provision/promote arms' metric, not the storm's
    sess.execute("SELECT v FROM kv WHERE id = 0")
    t0 = time.perf_counter()
    queries = wrong = 0
    while stop is None or not stop.is_set():
        now = time.perf_counter() - t0
        if now >= seconds:
            break
        if rate > 0:
            due = queries / rate
            if due > now:
                time.sleep(min(due - now, seconds - now))
                continue
        k = rng.randrange(n_rows)
        rows = sess.execute(
            f"SELECT v FROM kv WHERE id = {k}").rows()
        queries += 1
        if len(rows) != 1 or int(rows[0][0]) != k * 3:
            wrong += 1
    wall = time.perf_counter() - t0
    return {"qps": queries / wall, "queries": queries, "wrong": wrong,
            "wall_s": round(wall, 3), "offered_qps": rate}


def _replica_child(data_dir: str, mode: str, *args: str) -> None:
    """One replica_fleet measurement arm in its own process (see
    bench_replica_fleet).  Prints one JSON line on stdout."""
    import threading

    from citus_tpu.session import Session

    if mode == "seed":
        from citus_tpu.replication import provision_replica

        n_rows, replicas = int(args[0]), args[1:]
        sess = Session(data_dir=data_dir,
                       serving_result_cache_bytes=0)
        sess.execute("CREATE TABLE kv (id INT, v INT)")
        sess.execute("SELECT create_distributed_table('kv', 'id', 4)")
        step = 5000
        for lo in range(0, n_rows, step):
            sess.execute("INSERT INTO kv VALUES " + ", ".join(
                f"({i}, {i * 3})" for i in range(lo,
                                                 min(lo + step, n_rows))))
        for rdir in replicas:
            provision_replica(data_dir, rdir,
                              counters=sess.stats.counters)
        sess.close()
        print(json.dumps({"seeded": n_rows,
                          "replicas": len(replicas)}), flush=True)
        return

    if mode == "storm":
        sess = Session(data_dir=data_dir,
                       serving_result_cache_bytes=0)
        res = _replica_storm(sess, float(args[0]), int(args[1]),
                             int(args[2]),
                             float(args[3]) if len(args) > 3 else 0.0)
        print(json.dumps(res), flush=True)
        sess.close()
        return

    if mode == "fleet":
        # data_dir is the LEADER's; every replica is a session of this
        # process with a thread of its own, and the leader keeps
        # committing and shipping meanwhile.  kill=1: replica 0 stops
        # dead at half time — its session is abandoned, not closed,
        # and its answers are dropped like a killed process's
        from citus_tpu.replication import ship_all

        seconds, n_rows, rate, seed0, kill = (
            float(args[0]), int(args[1]), float(args[2]), int(args[3]),
            args[4] == "1")
        leader = Session(data_dir=data_dir,
                         serving_result_cache_bytes=0)
        sessions = [Session(data_dir=d, serving_result_cache_bytes=0)
                    for d in args[5:]]
        victim_stop = threading.Event()
        results: list = [None] * len(sessions)

        def serve(i):
            results[i] = _replica_storm(
                sessions[i], seconds, n_rows, seed0 + i, rate,
                stop=victim_stop if kill and i == 0 else None)

        threads = [threading.Thread(target=serve, args=(i,))
                   for i in range(len(sessions))]
        for t in threads:
            t.start()
        t0, shipped, nid = time.perf_counter(), 0, 10_000_000 + seed0
        while time.perf_counter() - t0 < seconds * 0.8:
            if kill and time.perf_counter() - t0 >= seconds / 2:
                victim_stop.set()
            leader.execute(f"INSERT INTO kv VALUES ({nid}, {nid * 3})")
            nid += 1000
            ship_all(data_dir, counters=leader.stats.counters)
            shipped += 1
            time.sleep(0.05)
        for t in threads:
            t.join(timeout=seconds + 120)
            assert not t.is_alive(), "replica storm thread hung"
        survivors = [r for i, r in enumerate(results)
                     if not (kill and i == 0)]
        print(json.dumps({"replicas": survivors, "shipped": shipped}),
              flush=True)
        for i, s in enumerate(sessions):
            if not (kill and i == 0):
                s.close()
        leader.close()
        return

    if mode == "promote":
        n_rows = int(args[0])
        t0 = time.perf_counter()
        sess = Session(data_dir=data_dir,
                       serving_result_cache_bytes=0)
        t1 = time.perf_counter()
        epoch = sess.execute(
            "SELECT citus_promote_replica()").rows()[0][0]
        t2 = time.perf_counter()
        sess.execute(f"INSERT INTO kv VALUES ({n_rows + 1}, -1)")
        r = sess.execute(
            f"SELECT v FROM kv WHERE id = {n_rows + 1}").rows()
        assert int(r[0][0]) == -1
        wall = time.perf_counter() - t0
        print(json.dumps({"wall_s": round(wall, 4),
                          "connect_s": round(t1 - t0, 4),
                          "promote_s": round(t2 - t1, 4),
                          "epoch": int(epoch)}), flush=True)
        sess.close()
        return

    if mode == "provision":
        from citus_tpu.replication import provision_replica

        leader_dir, n_rows = args[0], int(args[1])
        t0 = time.perf_counter()
        provision_replica(leader_dir, data_dir)
        sess = Session(data_dir=data_dir,
                       serving_result_cache_bytes=0)
        k = n_rows // 2
        r = sess.execute(f"SELECT v FROM kv WHERE id = {k}").rows()
        assert int(r[0][0]) == k * 3
        wall = time.perf_counter() - t0
        nfiles = sum(len(fs) for _, _, fs in
                     os.walk(os.path.join(data_dir, "tables")))
        print(json.dumps({"wall_s": round(wall, 4),
                          "files": nfiles}), flush=True)
        sess.close()
        return
    raise SystemExit(f"unknown _replica_child mode {mode!r}")


def main() -> None:
    if sys.argv[1:2] == ["concurrency"]:
        bench_concurrency()
        return
    if sys.argv[1:2] == ["serving"]:
        bench_serving()
        return
    if sys.argv[1:2] == ["memory_pressure"]:
        bench_memory_pressure()
        return
    if sys.argv[1:2] == ["cold_start"]:
        bench_cold_start()
        return
    if sys.argv[1:2] == ["_cold_child"]:
        _cold_child(sys.argv[2], sys.argv[3], *sys.argv[4:5])
        return
    if sys.argv[1:2] == ["replica_fleet"]:
        bench_replica_fleet()
        return
    if sys.argv[1:2] == ["_replica_child"]:
        _replica_child(sys.argv[2], sys.argv[3], *sys.argv[4:])
        return
    sf = float(os.environ.get("BENCH_SF", "1.0"))
    repeats = int(os.environ.get("BENCH_REPEATS", "3"))
    # BENCH_REPEAT=N: best-of-N authority — every config (SF10 lines
    # included) runs at least N measured executions, and each emitted
    # line records it, so the artifact is self-describing best-of-N
    rep_override = int(os.environ.get("BENCH_REPEAT", "0"))

    def n_reps(default: int) -> int:
        return max(default, rep_override)

    repeats = n_reps(repeats)
    sf10 = os.environ.get("BENCH_SF10", "1") not in ("0", "false", "")
    sf10_scale = float(os.environ.get("BENCH_SF10_SCALE", "10.0"))
    extras = os.environ.get("BENCH_EXTRAS", "0") not in ("0", "false", "")
    budget = float(os.environ.get("BENCH_BUDGET", "2400"))
    t_start = time.perf_counter()
    only = os.environ.get("BENCH_ONLY")
    only = set(only.split(",")) if only else None

    from citus_tpu.session import Session
    from citus_tpu.ingest.tpch import QUERIES, load_into_session

    lines = []

    def over_budget(share: float = 1.0) -> bool:
        """True once `share` of the wall-clock budget is spent; optional
        configs check this before starting so the headline always runs."""
        return time.perf_counter() - t_start > budget * share

    # measured CPU rows (bench_cpu_baseline.py; sqlite3 on this host) —
    # a second, honest denominator next to the reference yardstick
    cpu_rows = {}
    try:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BASELINE.json")) as f:
            cpu_rows = json.load(f).get("cpu_baseline", {})
    except Exception:
        pass

    def emit(name, rate, best, this_sf, unit="rows/s",
             baseline=BASELINE_ROWS_PER_SEC, extra=None, reps=None,
             sess_obj=None):
        line = {
            "metric": name,
            "value": round(rate, 3 if unit != "rows/s" else 1),
            "unit": unit,
            "vs_baseline": round(rate / baseline, 3),
            "seconds": round(best, 4),
            "sf": this_sf,
        }
        if extra:
            line.update(extra)
        if reps is not None:
            # the ACTUAL measured-execution count for EVERY line (a
            # config default above BENCH_REPEAT runs its default) —
            # the artifact must describe what actually ran, not just
            # the lines BENCH_REPEAT happened to touch
            line["repeats"] = reps
        # cumulative plan-cache traffic of the emitting session at the
        # moment this line lands: warm-vs-cold is auditable from the
        # JSON alone (a config whose misses didn't grow ran entirely
        # on cached executables)
        s = sess_obj if sess_obj is not None else sess
        line["plan_cache_hits"] = s.executor.plan_cache.hits
        line["plan_cache_misses"] = s.executor.plan_cache.misses
        cpu = cpu_rows.get(name)
        if cpu and cpu.get("sf") == this_sf and cpu.get("rows_per_sec"):
            line["vs_cpu"] = round(rate / cpu["rows_per_sec"], 3)
            line["cpu_engine"] = cpu.get("engine", "")
        lines.append(line)
        # print + flush immediately: a timeout later in the run must not
        # erase configs that already finished (round-3 postmortem).
        print(json.dumps(line), flush=True)

    data_dir = tempfile.mkdtemp(prefix="citus_tpu_bench_")
    try:
        # result cache OFF: bench_query repeats the same SQL — serving a
        # repeat from the result cache would measure the cache, not the
        # engine (the serving scenario measures the cache explicitly)
        sess = Session(data_dir=data_dir, serving_result_cache_bytes=0)
        load_into_session(sess, sf=sf, seed=0)
        n_li = sess.store.table_row_count("lineitem")
        n_ord = sess.store.table_row_count("orders")
        n_cust = sess.store.table_row_count("customer")

        configs = [
            # (name, sql, rows processed by the query)
            ("colocated_join_rows_per_sec",
             "select count(*), sum(l_extendedprice) from orders, lineitem "
             "where o_orderkey = l_orderkey",
             n_ord + n_li),
            ("single_repartition_join_rows_per_sec",
             "select count(*), sum(o_totalprice) from customer, orders "
             "where c_custkey = o_custkey",
             n_cust + n_ord),
            ("dual_repartition_join_rows_per_sec",
             "select count(*) from orders, lineitem "
             "where o_custkey = l_suppkey",
             n_ord + n_li),
            ("tpch_q3_rows_per_sec", QUERIES["Q3"], n_cust + n_ord + n_li),
            # high-cardinality GROUP BY (~0.25·n_li distinct orderkeys
            # over the full lineitem): the aggregation-stage wall the
            # bucketed dense-grid path (ops/groupby.py, group_by_kernel)
            # targets — bench_kernels.py groupby is the kernel-level A/B
            ("high_card_groupby_rows_per_sec",
             "select l_orderkey, count(*), sum(l_quantity) "
             "from lineitem group by l_orderkey",
             n_li),
        ]
        distinct_extras = {"approx_count_distinct_rows_per_sec",
                           "exact_count_distinct_rows_per_sec"}
        if extras or (only is not None and only & distinct_extras):
            # HLL sketch build + register fold (vs the exact two-level
            # DISTINCT split the next line measures).  Opt-in: these
            # programs are slow to compile, and the default sweep must
            # stay inside its time budget
            configs += [
                ("approx_count_distinct_rows_per_sec",
                 "select approx_count_distinct(l_partkey) from lineitem",
                 n_li),
                ("exact_count_distinct_rows_per_sec",
                 "select count(distinct l_partkey) from lineitem",
                 n_li),
            ]
        for name, sql, rows in configs:
            if only is not None and name not in only:
                continue
            if over_budget(0.6):
                print(f"# budget: skipping {name}", file=sys.stderr)
                continue
            rate, best = bench_query(sess, sql, rows, repeats)
            # Q3 carries the tracing acceptance evidence: top-level
            # spans of the measured run's trace must tile its wall,
            # and the DDSketch histogram quotes its p50/p99
            extra = (trace_acceptance_keys(sess, sql=sql)
                     if name == "tpch_q3_rows_per_sec" else None)
            emit(name, rate, best, sf, reps=repeats, extra=extra)
        if ((only is None or "columnar_scan_gb_per_sec" in only)
                and not over_budget(0.7)):
            (rate, best, parts, scan_reps,
             eager_rate, eager_best) = bench_cold_scan(sess, n_li)
            emit("columnar_scan_gb_per_sec", rate, best, sf, unit="GB/s",
                 baseline=BASELINE_SCAN_GB_PER_SEC, extra=parts,
                 reps=scan_reps)
            # the eager (scan_pipeline=off) arm of the same cold scan:
            # the artifact itself carries the pipelined-vs-eager A/B
            emit("columnar_scan_gb_per_sec_eager", eager_rate,
                 eager_best, sf, unit="GB/s",
                 baseline=BASELINE_SCAN_GB_PER_SEC, reps=scan_reps)
            # the host-only decode leg as its own line: the end-to-end
            # number above includes the host-to-device transfer
            emit("columnar_host_decode_gb_per_sec",
                 parts["host_decode_gb_per_sec"],
                 parts["host_decode_seconds"], sf, unit="GB/s",
                 baseline=BASELINE_SCAN_GB_PER_SEC, reps=scan_reps)

        # -- INSERT..SELECT modes (reference README: pushdown ~100M vs
        #    repartition ~10M rows/s — here the colocated path writes
        #    per-device blocks directly, no hash routing) ----------------
        is_wanted = {"insert_select_colocated_rows_per_sec",
                     "insert_select_repartition_rows_per_sec"}
        is_run = ((is_wanted if extras else set())
                  if only is None else is_wanted & only)
        if is_run and over_budget(0.75):
            print("# budget: skipping INSERT..SELECT section",
                  file=sys.stderr)
            is_run = set()
        for name, dist_col in (
                ("insert_select_colocated_rows_per_sec", "o_orderkey"),
                ("insert_select_repartition_rows_per_sec", "o_custkey")):
            if name not in is_run:
                continue
            from citus_tpu.ingest.tpch import SCHEMAS

            best = float("inf")
            is_reps = n_reps(2)
            for _ in range(is_reps):  # first run pays the source-plan compile
                ddl = SCHEMAS["orders"].replace("orders", "bench_is_dst")
                sess.execute(ddl)
                sess.create_distributed_table(
                    "bench_is_dst", dist_col,
                    colocate_with="orders" if dist_col == "o_orderkey"
                    else None)
                t0 = time.perf_counter()
                sess.execute(
                    "insert into bench_is_dst select * from orders")
                best = min(best, time.perf_counter() - t0)
                sess.execute("drop table bench_is_dst")
            emit(name, n_ord / best, best, sf, reps=is_reps)

        # -- SF10 section (BASELINE configs at scale; on by default —
        #    r4 VERDICT #1: the scale story must be driver-captured) ----
        sf10_wanted = {"dual_repartition_join_sf10_rows_per_sec",
                       "single_repartition_join_sf10_rows_per_sec",
                       "tpch_q3_sf10_rows_per_sec"}
        sf10_run = (sf10_wanted if only is None
                    else sf10_wanted & only) if sf10 else set()
        if sf10_run and over_budget(0.5):
            print("# budget: skipping SF10 section", file=sys.stderr)
            sf10_run = set()
        if sf10_run:
            # persistent data dir: SF10 ingest costs ~14 min of pure
            # host-side generation on one core — cache it across runs
            # (first run pays it once inside the budget check above)
            sf10_tag = ("sf%g" % sf10_scale).replace(".", "_")
            sf10_dir = os.environ.get(
                "BENCH_SF10_DIR",
                os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             ".benchdata", sf10_tag))
            s10 = Session(data_dir=sf10_dir,
                          serving_result_cache_bytes=0)
            if s10.store.table_row_count("lineitem") == 0:
                load_into_session(
                    s10, sf=sf10_scale, seed=0,
                    tables={"customer", "orders", "lineitem"})
            n_li10 = s10.store.table_row_count("lineitem")
            n_ord10 = s10.store.table_row_count("orders")
            n_cust10 = s10.store.table_row_count("customer")
            if "dual_repartition_join_sf10_rows_per_sec" in sf10_run:
                r = n_reps(1)
                rate, best = bench_query(
                    s10,
                    "select count(*) from orders, lineitem "
                    "where o_custkey = l_suppkey",
                    n_ord10 + n_li10, r)
                emit("dual_repartition_join_sf10_rows_per_sec", rate,
                     best, sf10_scale, reps=r, sess_obj=s10)
            if "single_repartition_join_sf10_rows_per_sec" in sf10_run:
                # at SF1 the device work is small beside the fixed
                # dispatch and fetch cost of one statement; at SF10 the
                # same shape shows the engine's actual rate
                r = n_reps(2)
                rate, best = bench_query(
                    s10,
                    "select count(*), sum(o_totalprice) "
                    "from customer, orders "
                    "where c_custkey = o_custkey",
                    n_cust10 + n_ord10, r)
                emit("single_repartition_join_sf10_rows_per_sec", rate,
                     best, sf10_scale, reps=r, sess_obj=s10)
            if "tpch_q3_sf10_rows_per_sec" in sf10_run:
                r = n_reps(2)
                rate, best = bench_query(
                    s10, QUERIES["Q3"], n_cust10 + n_ord10 + n_li10, r)
                # the acceptance run: EXPLAIN-equal phase walls from
                # the trace and the class's DDSketch p50/p99
                extra = trace_phase_keys(
                    s10.stats.tracing.last_trace(), wall_seconds=best,
                    sql=QUERIES["Q3"])
                extra.update(trace_acceptance_keys(
                    s10, sql=QUERIES["Q3"]))
                emit("tpch_q3_sf10_rows_per_sec", rate, best,
                     sf10_scale, reps=r, sess_obj=s10, extra=extra)

        # -- serving scenario (PR 8): the three point_lookup_qps lines
        #    land in the driver artifact so the README/PERF_NOTES
        #    serving claims stay honesty-checkable ---------------------
        if (only is None or "point_lookup_qps" in only) \
                and not over_budget(0.85):
            bench_serving()

        # -- memory-pressure scenario (PR 10): the governed/ungoverned
        #    A/B lands in the driver artifact so the README/PERF_NOTES
        #    degradation claims stay honesty-checkable ----------------
        if (only is None or "memory_pressure" in only) \
                and not over_budget(0.9):
            bench_memory_pressure()

        # cold_start and replica_fleet are NOT part of this sweep: both
        # start children that open sessions of their own, and this
        # process holds a live Session on the device — one process per
        # chip.  Run them as `python bench.py cold_start` and `python
        # bench.py replica_fleet`, whose parents only spawn.

        # headline LAST (driver contract: final JSON line)
        if only is None or "tpch_q1_rows_per_sec" in only:
            rate, best = bench_query(sess, QUERIES["Q1"], n_li, repeats)
            emit("tpch_q1_rows_per_sec", rate, best, sf, reps=repeats)

        _publish(lines)
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)


def _publish(lines) -> None:
    """Record measurements in BASELINE.json's `published` map.  Skipped
    for non-default scale factors (smoke runs must not clobber real
    published numbers)."""
    if float(os.environ.get("BENCH_SF", "1.0")) != 1.0:
        return
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BASELINE.json")
    try:
        with open(path) as f:
            doc = json.load(f)
        doc.setdefault("published", {})
        for line in lines:
            doc["published"][line["metric"]] = {
                f"{line['unit'].replace('/', '_per_')}":
                    line["value"],
                "vs_baseline": line["vs_baseline"],
                "sf": line["sf"],
            }
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=2)
        os.replace(tmp, path)
    except Exception:
        pass  # publishing is best-effort; the JSON lines are the contract


if __name__ == "__main__":
    main()
