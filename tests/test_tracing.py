"""Span flight recorder (stats/tracing.py): trace correctness.

The contracts under test (ISSUE 14):
* top-level spans TILE the statement wall (sum within tolerance) — the
  reconciliation that makes queued_ms / retry waits / degradation-rung
  time add up instead of living in three disconnected reports;
* spans nest correctly across the scanpipe producer thread and the
  serving leader/follower promotion, with ZERO open spans left behind;
* the in-memory ring and per-trace span counts stay bounded under a
  many-session hammer;
* DDSketch latency histograms (citus_stat_latency) report honest
  quantiles; sampling and trace_enabled degrade recording, never
  correctness;
* the slow-query log persists through the io seam and the persisted
  tree's phases sum to statement wall (the acceptance shape,
  exercised here at test scale and by bench.py at SF10).
"""

import json
import os
import threading
import time

import pytest

import citus_tpu
from citus_tpu.stats.tracing import (
    open_span_count,
    phase_breakdown,
    span_seconds,
)
from citus_tpu.utils.faultinjection import inject, reset


@pytest.fixture(autouse=True)
def _clean_faults():
    reset()
    yield
    reset()


def _mk(data_dir, **kw):
    kw.setdefault("n_devices", 2)
    kw.setdefault("retry_backoff_base_ms", 1)
    kw.setdefault("retry_backoff_max_ms", 5)
    # result cache off by default: most contracts here need the
    # statement to actually execute, not be served from the cache
    kw.setdefault("serving_result_cache_bytes", 0)
    return citus_tpu.connect(data_dir=data_dir, **kw)


def _seed(sess, n=4000):
    sess.execute("CREATE TABLE kv (id INT, v INT, w FLOAT)")
    sess.execute("SELECT create_distributed_table('kv', 'id', 4)")
    vals = ", ".join(f"({i}, {i % 17}, {i * 0.25})" for i in range(n))
    sess.execute(f"INSERT INTO kv VALUES {vals}")


def _top_sum_ms(doc):
    return sum(c["dur_ms"] for c in doc["root"].get("children", ()))


def _assert_tiles_wall(doc, share=0.95, abs_ms=5.0):
    wall = doc["root"]["dur_ms"]
    top = _top_sum_ms(doc)
    assert top <= wall * 1.001 + 0.05, (top, wall)
    gap = wall - top
    assert gap <= max((1.0 - share) * wall, abs_ms), (
        f"top-level spans cover only {top:.2f} of {wall:.2f} ms "
        f"(gap {gap:.2f} ms) — a phase is untraced:\n"
        + json.dumps(doc["root"], indent=1)[:2000])


# ---------------------------------------------------------------------------
# sum-to-wall reconciliation (tier-1 satellite)
# ---------------------------------------------------------------------------
class TestSumToWall:
    def test_cold_select_top_level_spans_tile_wall(self, tmp_path):
        sess = _mk(str(tmp_path / "d"))
        _seed(sess)
        sess.execute("SELECT sum(v), sum(w) FROM kv WHERE v > 3")
        sess.executor.feed_cache.clear()
        sess.execute("SELECT sum(v), sum(w) FROM kv WHERE v > 3")
        doc = sess.stats.tracing.last_trace()
        assert doc is not None and doc["root"]["name"] == "statement"
        _assert_tiles_wall(doc)
        # wall_ms in the doc is the recorder's own statement clock
        assert abs(doc["wall_ms"] - doc["root"]["dur_ms"]) < 1.0
        assert open_span_count() == 0
        sess.close()

    def test_queue_span_reconciles_wlm_queued_ms(self, tmp_path):
        """queued_ms (WLM stats), previously only reported beside the
        trace, must equal the traced queue-wait within tolerance."""
        d = str(tmp_path / "d")
        sess = _mk(d, max_concurrent_statements=1)
        _seed(sess, n=1500)
        sql = "SELECT count(*), sum(v) FROM kv WHERE v >= 0"
        sess.execute(sql)  # warm
        other = _mk(d, max_concurrent_statements=1)
        # occupy the single admission slot: the other session's cold
        # read sleeps 0.2 s at the read seam while holding it
        from citus_tpu.utils.faultinjection import arm, disarm

        arm("store.read_shard", sleep=0.2, error=None, once=True)
        try:
            hog = threading.Thread(
                target=lambda: other.execute(sql + " AND v < 99"))
            hog.start()
            time.sleep(0.05)  # let the hog admit + start executing
            sess.execute(sql)
            hog.join(30)
        finally:
            disarm("store.read_shard")
        doc = sess.stats.tracing.last_trace()
        waits = [c for c in doc["root"]["children"]
                 if c["name"] == "queue"
                 and (c.get("meta") or {}).get("queued_ms")
                 is not None]
        assert waits, doc["root"]
        waited = max(waits, key=lambda c: c["meta"]["queued_ms"])
        queued_ms = waited["meta"]["queued_ms"]
        span_ms = waited["dur_ms"]
        assert queued_ms > 20.0, "the statement never actually queued"
        # the span covers classification + wait: >= queued_ms, and the
        # non-wait part must be small
        assert span_ms >= queued_ms - 1.0, (span_ms, queued_ms)
        assert span_ms - queued_ms < 60.0, (span_ms, queued_ms)
        _assert_tiles_wall(doc, abs_ms=8.0)
        sess.close()
        other.close()

    def test_retry_and_backoff_time_visible_in_trace(self, tmp_path):
        """Retry waits reconcile through the trace: a retried statement
        shows N execute attempts + retry.backoff, still tiling wall."""
        sess = _mk(str(tmp_path / "d"), retry_backoff_base_ms=20,
                   retry_backoff_max_ms=40)
        _seed(sess, n=800)
        sess.executor.feed_cache.clear()
        with inject("store.read_shard", require_fired=True):
            sess.execute("SELECT count(*), sum(v) FROM kv")
        doc = sess.stats.tracing.last_trace()
        names = [c["name"] for c in doc["root"]["children"]]
        assert names.count("execute") >= 2, names  # failed + retried
        assert "retry.backoff" in names, names
        backoff_s = span_seconds(doc["root"], "retry.backoff")
        assert backoff_s * 1000 >= 5.0  # the backoff actually waited
        _assert_tiles_wall(doc, abs_ms=8.0)
        # the failed attempt's span records the error class
        failed = [c for c in doc["root"]["children"]
                  if c["name"] == "execute"
                  and (c.get("meta") or {}).get("error")]
        assert failed, doc["root"]
        sess.close()

    def test_oom_degradation_rung_time_visible_in_trace(self, tmp_path):
        sess = _mk(str(tmp_path / "d"))
        _seed(sess, n=800)
        sess.executor.feed_cache.clear()
        with inject("executor.hbm_exhausted", error="oom",
                    require_fired=True):
            sess.execute("SELECT count(*), sum(w) FROM kv")
        doc = sess.stats.tracing.last_trace()
        names = [c["name"] for c in doc["root"]["children"]]
        assert "oom.degrade" in names, names
        _assert_tiles_wall(doc, abs_ms=8.0)
        sess.close()


# ---------------------------------------------------------------------------
# cross-thread nesting
# ---------------------------------------------------------------------------
class TestCrossThreadNesting:
    def test_scanpipe_producer_spans_nest_under_feed(self, tmp_path):
        sess = _mk(str(tmp_path / "d"), scan_pipeline="host")
        _seed(sess)
        sess.execute("SELECT sum(v), sum(w) FROM kv")
        sess.executor.feed_cache.clear()
        sess.execute("SELECT sum(v), sum(w) FROM kv")
        doc = sess.stats.tracing.last_trace()

        def find(span, name, out):
            if span["name"] == name:
                out.append(span)
            for c in span.get("children", ()):
                find(c, name, out)

        feeds, prefetch = [], []
        find(doc["root"], "feed", feeds)
        find(doc["root"], "scan.prefetch", prefetch)
        assert feeds and prefetch
        # the producer's spans are CHILDREN of the feed span, recorded
        # from a different thread
        under_feed = []
        for f in feeds:
            find(f, "scan.prefetch", under_feed)
        assert under_feed == prefetch
        stmt_tid = doc["root"]["tid"]
        assert any(p["tid"] != stmt_tid for p in prefetch), (
            "producer spans should carry the producer thread's id")
        assert span_seconds(doc["root"], "scan.prefetch") > 0
        assert open_span_count() == 0
        sess.close()

    def test_device_mode_records_wire_and_decode_legs(self, tmp_path):
        sess = _mk(str(tmp_path / "d"), scan_pipeline="device")
        _seed(sess)
        sess.execute("SELECT sum(v) FROM kv")
        sess.executor.feed_cache.clear()
        sess.execute("SELECT sum(v) FROM kv")
        doc = sess.stats.tracing.last_trace()
        for name in ("scan.prefetch", "scan.wire_encode",
                     "scan.transfer", "scan.device_decode"):
            assert span_seconds(doc["root"], name) > 0, name
        # trace-derived legs match ScanPhaseStats within slack (both
        # time the same regions; bench drivers now read the trace)
        assert open_span_count() == 0
        sess.close()

    def test_serving_leader_follower_spans(self, tmp_path):
        """Concurrent point lookups: the leader's trace carries the
        batch probe, followers carry the wait — and every session's
        stack is empty afterward (the leader/follower promotion path
        cannot leak spans)."""
        d = str(tmp_path / "d")
        seed = _mk(d)
        seed.execute("CREATE TABLE pt (id INT, v INT)")
        seed.execute("SELECT create_distributed_table('pt', 'id', 2)")
        seed.execute("INSERT INTO pt VALUES " + ", ".join(
            f"({i}, {i * 10})" for i in range(64)))
        sql = "SELECT v FROM pt WHERE id = 7"
        seed.execute(sql)  # build the pkindex sidecars
        sessions = [_mk(d, serving_batch_window_ms=5.0)
                    for _ in range(4)]
        for s in sessions:
            s.execute(sql)  # warm plan/parse
        barrier = threading.Barrier(len(sessions))

        def worker(s):
            barrier.wait()
            for _ in range(5):
                r = s.execute(sql)
                assert r.row_count == 1
        threads = [threading.Thread(target=worker, args=(s,))
                   for s in sessions]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        probe = wait = 0.0
        for s in sessions:
            for tr in s.stats.tracing.traces():
                d_ = tr.to_dict()
                probe += span_seconds(d_["root"],
                                      "serving.batch_probe")
                wait += span_seconds(d_["root"], "serving.batch_wait")
                assert tr.leaked == 0
        assert probe > 0, "no leader ever recorded a batch probe"
        assert open_span_count() == 0
        for s in sessions:
            s.close()
        seed.close()


# ---------------------------------------------------------------------------
# boundedness / sampling / histograms
# ---------------------------------------------------------------------------
class TestBoundedness:
    def test_ring_and_span_caps_bound_memory(self, tmp_path):
        from citus_tpu.stats.tracing import MAX_SPANS_PER_TRACE

        sess = _mk(str(tmp_path / "d"), trace_ring_statements=6)
        _seed(sess, n=300)
        for i in range(25):
            sess.execute(f"SELECT count(*) FROM kv WHERE v = {i % 5}")
        traces = sess.stats.tracing.traces()
        assert len(traces) <= 6
        assert all(t.spans <= MAX_SPANS_PER_TRACE for t in traces)
        assert sess.stats.tracing.ring_bytes() < 6 * \
            MAX_SPANS_PER_TRACE * 200 + 1
        sess.close()

    def test_eight_session_hammer_stays_bounded(self, tmp_path):
        d = str(tmp_path / "d")
        seed = _mk(d)
        _seed(seed, n=500)
        sessions = [_mk(d, trace_ring_statements=4) for _ in range(8)]
        barrier = threading.Barrier(len(sessions))

        def worker(wid, s):
            barrier.wait()
            for i in range(8):
                s.execute(
                    f"SELECT count(*) FROM kv WHERE v = {(wid + i) % 7}")
        threads = [threading.Thread(target=worker, args=(i, s))
                   for i, s in enumerate(sessions)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        for s in sessions:
            assert len(s.stats.tracing.traces()) <= 4
            assert all(t.leaked == 0
                       for t in s.stats.tracing.traces())
        assert open_span_count() == 0
        for s in sessions:
            s.close()
        seed.close()

    def test_sampling_records_histograms_for_every_statement(
            self, tmp_path):
        # every class counts as fast, so after its 8 proving calls a
        # class records a tree 1 in 5 times
        sess = _mk(str(tmp_path / "d"), trace_fast_statement_ms=60_000,
                   trace_fast_sample_every=5)
        _seed(sess, n=200)
        for i in range(8):
            sess.execute(f"SELECT count(*) FROM kv WHERE v = {i}")
        r0 = len(sess.stats.tracing.traces())
        for i in range(10):
            sess.execute(f"SELECT count(*) FROM kv WHERE v = {i}")
        sampled = len(sess.stats.tracing.traces()) - r0
        assert sampled <= 3  # ~1 in 5 record a tree
        rows = {r["statement_class"]: r
                for r in sess.stats.tracing.latency_rows()}
        cls = [c for c in rows if "count" in c and "kv" in c]
        assert cls and rows[cls[0]]["calls"] == 18  # hist sees ALL
        assert open_span_count() == 0
        sess.close()

    def test_fast_class_auto_degrade_still_samples_trees(self):
        """A proven-fast class still records trees, 1 in N: the first
        8 calls prove it, then 392/16 sample in."""
        from citus_tpu.config import Settings
        from citus_tpu.stats.tracing import TraceRecorder

        rec = TraceRecorder(None, Settings({
            "trace_fast_statement_ms": 10_000,  # every class "fast"
            "trace_fast_sample_every": 16,
            "trace_ring_statements": 1000}))
        for _ in range(400):
            rec.end(rec.begin("select 1"))
        rows = rec.latency_rows()
        assert rows and rows[0]["calls"] == 400
        recorded = len(rec.traces())
        assert 8 + 392 // 16 - 1 <= recorded <= 8 + 392 // 16 + 1, \
            recorded
        # the numbers of the recorded trees are one sequence
        ids = [t.stmt_id for t in rec.traces()]
        assert ids == list(range(1, recorded + 1))

    def test_trace_enabled_off_records_nothing(self, tmp_path):
        sess = _mk(str(tmp_path / "d"), trace_enabled=False)
        _seed(sess, n=200)
        sess.execute("SELECT count(*) FROM kv")
        assert sess.stats.tracing.traces() == []
        assert sess.stats.tracing.latency_rows() == []
        assert open_span_count() == 0
        sess.close()


class TestLatencyHistograms:
    def test_citus_stat_latency_quantiles_honest(self, tmp_path):
        sess = _mk(str(tmp_path / "d"))
        _seed(sess, n=300)
        sql = "SELECT sum(v) FROM kv"
        for _ in range(12):
            sess.execute(sql)
        r = sess.execute("SELECT citus_stat_latency()")
        assert r.column_names[:2] == ["statement_class", "calls"]
        rows = {row[0]: row for row in r.rows()}
        key = [k for k in rows if "sum" in k and "kv" in k]
        assert key, rows.keys()
        row = rows[key[0]]
        cols = dict(zip(r.column_names, row))
        assert cols["calls"] == 12
        assert 0 < cols["p50_ms"] <= cols["p95_ms"] <= cols["p99_ms"]
        # DDSketch relative-error bound (α ≈ 1%) against the recorded
        # max: p99 of 12 samples cannot exceed the max bucket
        assert cols["p99_ms"] <= cols["max_ms"] * 1.02
        # the UDF surface is resettable (the reset statement itself
        # records afterward — always-on means always-on)
        sess.execute("SELECT citus_stat_latency_reset()")
        after = [row[0] for row in sess.execute(
            "SELECT citus_stat_latency()").rows()]
        assert key[0] not in after
        sess.close()


# ---------------------------------------------------------------------------
# slow-query log + EXPLAIN Timing (the acceptance shape at test scale;
# bench.py runs it at SF10)
# ---------------------------------------------------------------------------
class TestSlowLogAndExport:
    def test_slow_log_persists_and_chrome_sums_to_wall(self, tmp_path):
        d = str(tmp_path / "d")
        sess = _mk(d, trace_slow_statement_ms=1)
        _seed(sess)
        sess.executor.feed_cache.clear()
        sess.execute("SELECT sum(v), sum(w) FROM kv WHERE v > 2")
        assert os.path.isdir(os.path.join(d, "slow_traces"))
        names = sorted(os.listdir(os.path.join(d, "slow_traces")))
        with open(os.path.join(d, "slow_traces", names[-1])) as f:
            doc = json.load(f)
        _assert_tiles_wall(doc)
        assert doc["stmt_id"] == sess.stats.tracing.traces()[-1].stmt_id
        # acceptance: the persisted tree's phases sum to wall within
        # 5% (small statements get a small absolute allowance for glue)
        ph = phase_breakdown(doc["root"])
        assert ph["total"] * 1000.0 == pytest.approx(doc["wall_ms"],
                                                     rel=0.01, abs=0.05)
        from citus_tpu.stats.tracing import PHASE_ORDER

        named = sum(ph[p] for p in PHASE_ORDER)
        assert named + ph["other"] == pytest.approx(ph["total"])
        # how much of a LIVE statement no phase names is the host's
        # clock under six test workers; the bound on `other` is held on
        # a tree of fixed timestamps, below
        sess.close()

    def test_phase_breakdown_bounds_other_on_fixed_timestamps(self):
        """`phase_breakdown` alone, on a span tree written by hand in
        the persisted form: every phase is its spans' durations, a span
        under a phase span is not counted twice, and what no phase
        names (`other`) is the root's wall less the phases — here 0.5
        of 40 ms, under the acceptance bound of max(10 % of wall,
        10 ms) that the live test used to hold against the clock."""
        from citus_tpu.stats.tracing import PHASE_ORDER

        def span(name, t0, dur, *children, meta=None):
            d = {"name": name, "t0_ms": t0, "dur_ms": dur, "tid": 1}
            if meta:
                d["meta"] = meta
            if children:
                d["children"] = list(children)
            return d

        root = span(
            "statement", 0.0, 40.0,
            span("parse", 0.0, 0.5),
            span("queue", 0.5, 1.0),
            span("execute", 1.6, 38.3,
                 span("gate", 1.6, 0.1),
                 span("plan", 1.7, 12.0,
                      span("subplan", 1.8, 11.0,
                           span("plan", 1.8, 1.0),
                           span("mesh.fetch", 3.0, 2.0),
                           span("subplan.store", 5.0, 7.0,
                                span("subplan.store.type", 5.0, 4.0),
                                span("subplan.store.append", 9.0, 3.0)))),
                 span("route", 13.7, 0.3),
                 span("feed", 14.0, 5.0,
                      span("scan.transfer", 14.5, 4.0)),
                 span("caps", 19.0, 0.2),
                 span("compile", 19.2, 0.3, meta={"cache": "hit"}),
                 span("mesh.dispatch", 19.5, 1.0),
                 span("mesh.fetch", 20.5, 15.0,
                      span("mesh.fetch.wait", 20.5, 12.0,
                           span("gc.pause", 21.0, 3.0,
                                meta={"gen": 2, "collected": 0})),
                      span("mesh.fetch.pull", 32.5, 3.0,
                           meta={"bytes": 4096})),
                 span("settle", 35.5, 0.1),
                 span("combine", 35.6, 4.0),
                 span("subplan.drop", 39.6, 0.3)))
        ph = phase_breakdown(root)
        want = {"parse": 0.5, "queue": 1.0, "plan": 12.4, "feed": 5.0,
                "compile": 0.5, "device": 16.0, "combine": 4.1}
        for name in PHASE_ORDER:
            assert ph[name] * 1000.0 == pytest.approx(want.get(name, 0.0))
        assert ph["total"] * 1000.0 == pytest.approx(40.0)
        named = sum(ph[p] for p in PHASE_ORDER)
        assert named + ph["other"] == pytest.approx(ph["total"])
        # 0.1 ms on each side of `execute` and `subplan.drop`'s 0.3 ms,
        # which no phase names: nothing else
        assert ph["other"] * 1000.0 == pytest.approx(0.5)
        assert ph["other"] <= max(0.10 * ph["total"], 0.010)

    def test_slow_log_bounded(self, tmp_path):
        from citus_tpu.stats.tracing import SLOW_TRACE_KEEP

        d = str(tmp_path / "d")
        sess = _mk(d, trace_slow_statement_ms=1)
        _seed(sess, n=200)
        for i in range(SLOW_TRACE_KEEP + 8):
            sess.execute(f"SELECT count(*) FROM kv WHERE v = {i % 9}")
        names = os.listdir(os.path.join(d, "slow_traces"))
        assert 0 < len(names) <= SLOW_TRACE_KEEP
        sess.close()

    def test_explain_analyze_timing_line(self, tmp_path):
        sess = _mk(str(tmp_path / "d"))
        _seed(sess, n=500)
        r = sess.execute(
            "EXPLAIN ANALYZE SELECT count(*), sum(v) FROM kv")
        lines = [x for x in r.columns["QUERY PLAN"]
                 if x.startswith("Timing:")]
        assert len(lines) == 1, r.columns["QUERY PLAN"]
        line = lines[0]
        assert "total=" in line and "plan=" in line
        assert "device=" in line
        # phases come from the registered span names (registry-synced)
        sess.close()

    def test_phase_breakdown_never_double_counts(self, tmp_path):
        sess = _mk(str(tmp_path / "d"))
        _seed(sess, n=500)
        sess.executor.feed_cache.clear()
        sess.execute("SELECT sum(v) FROM kv")
        doc = sess.stats.tracing.last_trace()
        ph = phase_breakdown(doc["root"])
        attributed = sum(v for k, v in ph.items()
                         if k not in ("total", "other"))
        assert attributed <= ph["total"] * 1.001
        assert ph["other"] >= 0
        sess.close()


# ---------------------------------------------------------------------------
# the host path's owners (PR 37): `mesh.fetch` cut where the program
# ends and its bytes counted, an intermediate result's store and drop
# step by step, the collector on the statement's clock
# ---------------------------------------------------------------------------
@pytest.fixture
def no_gc():
    """No automatic collection inside the test: a `gc.pause` span is
    there only where the test forces one."""
    import gc

    gc.disable()
    try:
        yield
    finally:
        gc.enable()


def _find(span, name):
    out = [span] if span["name"] == name else []
    for c in span.get("children", ()):
        out += _find(c, name)
    return out


def _bulk_kv(sess, n):
    """`kv` with `n` rows through the ingest path (an INSERT's text of
    50,000 tuples is a parser test)."""
    import numpy as np

    from citus_tpu.ingest.copy_from import _ingest_batch

    sess.execute("CREATE TABLE kv (id INT, v INT, s TEXT)")
    sess.execute("SELECT create_distributed_table('kv', 'id', 4)")
    ids = np.arange(n, dtype=np.int32)
    _ingest_batch(sess, "kv", ["id", "v", "s"],
                  [ids, ids % 17, [f"n{i % 5}" for i in range(n)]],
                  pre_typed=True)


class TestHostPathOwners:
    def test_store_children_cover_a_50k_row_result(self, tmp_path, no_gc):
        n = 50_000
        sess = _mk(str(tmp_path / "d"))
        _bulk_kv(sess, n)
        c0 = sess.stats.counters.snapshot()
        r = sess.execute(
            "SELECT count(*), sum(t.c) FROM "
            "(SELECT id, count(*) AS c FROM kv GROUP BY id) t")
        assert r.rows() == [(n, n)]
        c1 = sess.stats.counters.snapshot()
        assert c1["intermediate_rows_total"] \
            - c0["intermediate_rows_total"] == n
        root = sess.stats.tracing.last_trace()["root"]
        (store,) = _find(root, "subplan.store")
        kids = store["children"]
        assert [k["name"] for k in kids] == ["subplan.store.type",
                                             "subplan.store.append"]
        assert kids[0]["meta"] == {"rows": n, "cols": 2}
        assert kids[1]["meta"]["bytes"] == \
            c1["intermediate_bytes_total"] - c0["intermediate_bytes_total"]
        covered = sum(k["dur_ms"] for k in kids)
        assert covered >= 0.90 * store["dur_ms"], (covered, store)
        # the drop: after the outer statement's combine, under its
        # `execute`, from the `finally` (none of the call sites knows)
        (drop,) = _find(root, "subplan.drop")
        (execute,) = [c for c in root["children"] if c["name"] == "execute"]
        assert drop in execute["children"]
        assert not sess.catalog.has_table("__intermediate_1")
        assert open_span_count() == 0
        sess.close()

    def test_a_string_column_adds_the_intern_span(self, tmp_path, no_gc):
        sess = _mk(str(tmp_path / "d"))
        _bulk_kv(sess, 2000)
        r = sess.execute(
            "SELECT t.s, sum(t.c) FROM (SELECT s, v, count(*) AS c "
            "FROM kv GROUP BY s, v) t GROUP BY t.s ORDER BY t.s")
        assert r.rows() == [(f"n{i}", 400) for i in range(5)]
        root = sess.stats.tracing.last_trace()["root"]
        (store,) = _find(root, "subplan.store")
        assert [k["name"] for k in store["children"]] == [
            "subplan.store.type", "subplan.store.intern",
            "subplan.store.append"]
        assert store["children"][0]["meta"] == {"rows": 85, "cols": 3}
        assert len(_find(root, "subplan.drop")) == 1
        sess.close()

    def test_the_outer_feed_is_built_from_the_held_arrays(self, tmp_path,
                                                          no_gc):
        """`subplan.feed` stands under the outer statement's `feed` (so
        `idle_dispatch_ms` keeps counting it) and is its one child: no
        `scan.*` span of the pipeline, which the inner statement's feed
        of the user table still runs at this size."""
        n = 6000  # over scanpipe.AUTO_MIN_ROWS, as a table and as a result
        sess = _mk(str(tmp_path / "d"))
        _bulk_kv(sess, n)
        c0 = sess.stats.counters.snapshot()
        r = sess.execute(
            "SELECT count(*), sum(t.c) FROM "
            "(SELECT id, count(*) AS c FROM kv GROUP BY id) t")
        assert r.rows() == [(n, n)]
        c1 = sess.stats.counters.snapshot()
        assert c1["intermediate_resident_total"] \
            - c0["intermediate_resident_total"] == 1
        root = sess.stats.tracing.last_trace()["root"]
        (execute,) = [c for c in root["children"] if c["name"] == "execute"]
        (outer,) = [c for c in execute["children"] if c["name"] == "feed"]
        assert [k["name"] for k in outer["children"]] == ["subplan.feed"]
        assert _find(root, "subplan.feed") == outer["children"]
        (subplan,) = _find(root, "subplan")
        (inner,) = [c for c in subplan["children"] if c["name"] == "feed"]
        assert any(k["name"].startswith("scan.")
                   for k in inner.get("children", ()))
        assert os.listdir(os.path.join(sess.data_dir, "tables")) == ["kv"]
        assert open_span_count() == 0
        sess.close()

    def test_every_execution_waits_then_pulls_and_counts_its_bytes(
            self, tmp_path, monkeypatch, no_gc):
        import jax

        sess = _mk(str(tmp_path / "d"))
        _bulk_kv(sess, 2000)
        pulled = []
        device_get = jax.device_get

        def watched(x):
            got = device_get(x)
            if isinstance(x, tuple) and len(x) == 2:
                pulled.append(sum(a.nbytes for a in got))
            return got

        monkeypatch.setattr(jax, "device_get", watched)
        c0 = sess.stats.counters.snapshot()
        # a derived table: two programs, each fetched once
        sess.execute(
            "SELECT count(*) FROM "
            "(SELECT v, count(*) AS c FROM kv GROUP BY v) t")
        moved = sess.stats.counters.snapshot()["fetch_bytes_total"] \
            - c0["fetch_bytes_total"]
        root = sess.stats.tracing.last_trace()["root"]
        fetches = _find(root, "mesh.fetch")
        assert len(fetches) == len(pulled) == 2
        for fetch, n_bytes in zip(fetches, pulled):
            wait, pull = fetch["children"]
            assert (wait["name"], pull["name"]) == ("mesh.fetch.wait",
                                                    "mesh.fetch.pull")
            assert pull["meta"] == {"bytes": n_bytes} and n_bytes > 0
            assert wait["t0_ms"] + wait["dur_ms"] <= pull["t0_ms"] + 1e-3
            assert pull["t0_ms"] + pull["dur_ms"] \
                <= fetch["t0_ms"] + fetch["dur_ms"] + 1e-3
        assert moved == sum(pulled)
        sess.close()

    def test_an_untraced_statement_pays_no_wait_and_counts_its_bytes(
            self, tmp_path, monkeypatch):
        """The cut is for a statement that is traced: with no tree the
        fetch is the one `device_get` it was, and the counter moves."""
        import jax

        sess = _mk(str(tmp_path / "d"), trace_enabled=False)
        _bulk_kv(sess, 2000)
        sql = "SELECT sum(v) FROM kv"
        sess.execute(sql)
        waits = []
        block_until_ready = jax.block_until_ready
        monkeypatch.setattr(
            jax, "block_until_ready",
            lambda x: (waits.append(x), block_until_ready(x))[1])
        c0 = sess.stats.counters.snapshot()
        assert sess.execute(sql).rows() == [(sum(i % 17 for i in
                                                 range(2000)),)]
        assert not [x for x in waits if isinstance(x, tuple)
                    and len(x) == 2]
        assert sess.stats.counters.snapshot()["fetch_bytes_total"] \
            > c0["fetch_bytes_total"]
        assert sess.stats.tracing.last_trace() is None
        sess.close()

    def test_a_collection_inside_a_statement_is_a_span_and_two_counters(
            self, tmp_path, monkeypatch, no_gc):
        import gc

        sess = _mk(str(tmp_path / "d"))
        _bulk_kv(sess, 2000)
        sql = "SELECT sum(v) FROM kv"
        sess.execute(sql)
        execute_plan = sess.executor.execute_plan

        def collecting(plan, *a, **kw):
            gc.collect()
            return execute_plan(plan, *a, **kw)

        monkeypatch.setattr(sess.executor, "execute_plan", collecting)
        c0 = sess.stats.counters.snapshot()
        sess.execute(sql)
        c1 = sess.stats.counters.snapshot()
        root = sess.stats.tracing.last_trace()["root"]
        (pause,) = _find(root, "gc.pause")
        assert pause["meta"]["gen"] == 2
        assert pause["meta"]["collected"] >= 0
        assert c1["gc_pauses_total"] - c0["gc_pauses_total"] == 1
        took_us = c1["gc_pause_us_total"] - c0["gc_pause_us_total"]
        assert 0 < took_us <= pause["dur_ms"] * 1000.0 + 1
        # outside any statement: nothing raised, nothing counted, and
        # generations 0 and 1 of the same thread likewise
        gc.collect()
        gc.collect(0)
        assert sess.stats.counters.snapshot()["gc_pauses_total"] \
            == c1["gc_pauses_total"]
        assert open_span_count() == 0
        sess.close()

    def test_a_collection_is_counted_for_an_untraced_statement(self, no_gc):
        """Sampled out or `trace_enabled` off the statement has no tree,
        and its session's counters still move; a recorder without
        counters records the span alone; a producer thread that adopted
        the statement's context counts for its session."""
        import gc

        from citus_tpu.config import Settings
        from citus_tpu.stats.counters import StatCounters
        from citus_tpu.stats.tracing import (
            TraceRecorder,
            adopt_context,
            capture_context,
            trace_span,
        )

        counters = StatCounters()
        rec = TraceRecorder(None, Settings({"trace_enabled": False}),
                            counters)
        h = rec.begin("select 1")
        gc.collect(1)
        assert rec.end(h) is None
        assert counters.snapshot()["gc_pauses_total"] == 1
        gc.collect(1)  # the statement is over
        assert counters.snapshot()["gc_pauses_total"] == 1

        bare = TraceRecorder(None, None)
        h = bare.begin("select 2")
        with trace_span("plan"):
            gc.collect(1)
        doc = bare.end(h).to_dict()
        (plan,) = doc["root"]["children"]
        assert [(c["name"], c["meta"]["gen"]) for c in plan["children"]] \
            == [("gc.pause", 1)]

        rec = TraceRecorder(None, None, counters)
        h = rec.begin("select 3")

        def producer(token):
            with adopt_context(token):
                with trace_span("scan.prefetch"):
                    gc.collect()

        with trace_span("feed"):
            t = threading.Thread(target=producer,
                                 args=(capture_context(),))
            t.start()
            t.join(30)
            assert not t.is_alive()
        doc = rec.end(h).to_dict()
        assert len(_find(doc["root"], "gc.pause")) == 1
        assert counters.snapshot()["gc_pauses_total"] == 2
        assert open_span_count() == 0


# ---------------------------------------------------------------------------
# the profiler's clock: spans as `ct:` events, stages as `ct.` scopes
# ---------------------------------------------------------------------------
def _profile(tmp_path, body):
    """Run `body()` under a jax.profiler session; the trace's `ct:`
    events by host line (benchmark/xspans.py's reading of them)."""
    import jax.profiler

    from benchmark import xspans, xtrace

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    log_dir = str(tmp_path / "profile")
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return xspans.read_events(xtrace.newest_xplane(log_dir))["host"]


def _names(tree, keep):
    """The tree as nested [name, [children]] of the nodes `keep` takes."""
    return [tree["name"], [_names(c, keep) for c in sorted(
        tree["children"], key=lambda c: c["t0"]) if keep(c)]]


class TestProfilerClock:
    def test_statement_tree_appears_as_nested_ct_events(self, tmp_path):
        from benchmark import xspans

        sess = _mk(str(tmp_path / "d"), scan_pipeline="host")
        _seed(sess)
        sql = "SELECT sum(v), sum(w) FROM kv"
        sess.execute(sql)

        def body():
            sess.executor.feed_cache.clear()
            sess.execute(sql)

        host = _profile(tmp_path, body)
        doc = sess.stats.tracing.last_trace()
        trees = xspans.build_trees(host)
        assert trees["orphans"] == 0
        root = trees["statements"][doc["stmt_id"]]
        assert root["name"] == "statement" and root["stmt"] == doc["stmt_id"]
        # on the statement's own line containment in time IS the span
        # tree; the root alone carries `stmt` there
        tid = doc["root"]["tid"]

        def doc_names(span, keep):
            return [span["name"], [doc_names(c, keep)
                                   for c in span.get("children", ())
                                   if keep(c)]]

        own = _names(root, lambda c: c["line_no"] == root["line_no"])
        assert own == doc_names(doc["root"], lambda c: c["tid"] == tid)
        assert own[1], "the statement recorded no phase"

        def stmts_on_own_line(n):
            return [c["stmt"] for c in n["children"]
                    if c["line_no"] == root["line_no"]] + [
                s for c in n["children"]
                if c["line_no"] == root["line_no"]
                for s in stmts_on_own_line(c)]

        assert set(stmts_on_own_line(root)) == {None}
        # the scanpipe producer's spans: another line, the same stmt
        other = [c for c in root["children"]
                 if c["line_no"] != root["line_no"]]
        assert other and {c["stmt"] for c in other} == {doc["stmt_id"]}
        assert {c["name"] for c in other} >= {"scan.prefetch"}
        prefetch = []

        def find(span):
            if span["name"] == "scan.prefetch":
                prefetch.append(span)
            for c in span.get("children", ()):
                find(c)

        find(doc["root"])
        assert len([c for c in other if c["name"] == "scan.prefetch"]) \
            == len([p for p in prefetch if p["tid"] != tid])
        assert open_span_count() == 0
        sess.close()

    def test_without_a_session_no_annotation_outlives_its_span(self, no_gc):
        """No profiler session: the tree is what it always was, and
        every force-close path leaves its annotation too."""
        from citus_tpu.stats.tracing import (
            TraceRecorder,
            adopt_context,
            capture_context,
            trace_span,
        )

        rec = TraceRecorder(None, None)
        # an exception unwinding through two spans
        h = rec.begin("select 1")
        err = None
        try:
            with trace_span("execute"):
                with trace_span("plan"):
                    raise ValueError("boom")
        except ValueError as e:
            err = e
        tr = rec.end(h, error=err)
        assert open_span_count() == 0
        doc = tr.to_dict()
        assert doc["error"] == "ValueError" and doc["leaked"] == 0
        assert [c["name"] for c in doc["root"]["children"]] == ["execute"]
        assert doc["root"]["children"][0]["children"][0]["meta"] == {
            "error": "ValueError"}
        # an abandoned producer: its span is closed for it
        h = rec.begin("select 2")
        left_open = []

        def producer(token):
            with adopt_context(token):
                left_open.append(trace_span("scan.prefetch"))

        with trace_span("feed"):
            t = threading.Thread(target=producer,
                                 args=(capture_context(),))
            t.start()
            t.join(30)
            assert not t.is_alive()
        # a span the statement's own thread never closes
        stray = trace_span("combine")
        tr = rec.end(h)
        assert open_span_count() == 0
        assert tr.leaked == 2
        for sp in left_open + [stray, tr.root]:
            assert sp.t1 is not None and sp._ann is None
        # sampled out or tracing off: no span, so no annotation
        assert trace_span("plan") is not None and \
            trace_span("plan").__enter__() is None

    def test_session_edge_inside_a_statement_loses_only_that_one(
            self, tmp_path, no_gc):
        import jax.profiler

        from benchmark import xspans, xtrace
        from citus_tpu.stats.tracing import TraceRecorder, trace_span

        rec = TraceRecorder(None, None)

        def statement(inside=None):
            h = rec.begin("select 1")
            with trace_span("execute"):
                with trace_span("plan"):
                    if inside is not None:
                        inside()
                with trace_span("combine"):
                    pass
            return rec.end(h)

        log_dir = str(tmp_path / "profile")
        a = statement(lambda: jax.profiler.start_trace(log_dir))
        b = statement()
        c = statement(jax.profiler.stop_trace)
        d = statement()
        host = xspans.read_events(xtrace.newest_xplane(log_dir))["host"]
        trees = xspans.build_trees(host)
        # b is whole; a's root began before the session and c's ended
        # after it: neither is in the trace, and nothing else is lost
        assert sorted(trees["statements"]) == [b.stmt_id]
        assert _names(trees["statements"][b.stmt_id], lambda c: True) == [
            "statement", [["execute", [["plan", []], ["combine", []]]]]]
        for tr in (a, b, c, d):
            doc = tr.to_dict()
            assert doc["leaked"] == 0 and doc["spans"] == 4
        assert [t.stmt_id for t in (a, b, c, d)] == [1, 2, 3, 4]
        assert open_span_count() == 0

    def test_stage_registry_covers_every_capacity_stage(self):
        import ast

        from citus_tpu.executor import compiler
        from citus_tpu.stats.tracing import STAGE_NAMES, stage_scope

        tree = ast.parse(open(compiler.__file__).read())
        kinds = {n.args[1].value for n in ast.walk(tree)
                 if isinstance(n, ast.Call)
                 and isinstance(n.func, ast.Attribute)
                 and n.func.attr == "_record" and len(n.args) >= 2
                 and isinstance(n.args[1], ast.Constant)}
        assert kinds >= {"scan_out", "repartition", "join_out",
                         "agg_grid", "agg_out"}
        assert kinds <= set(STAGE_NAMES)
        # the bucketed group-by's pack has no capacity to record since
        # PR 36; its scope and its pack's sub-scope keep their names
        assert "agg_bucket" not in kinds
        assert {"agg_bucket", "pack"} <= set(STAGE_NAMES)
        with pytest.raises(KeyError):
            stage_scope("not_a_stage")
        import jax
        import jax.numpy as jnp

        def f(x):
            with stage_scope("scan_out"):
                return x * 2

        text = jax.jit(f).lower(jnp.ones(4)).as_text(debug_info=True)
        assert "ct.scan_out" in text

    @pytest.mark.parametrize("n_devices", [1, 4])
    def test_q3_program_carries_stage_scopes(self, tmp_path, n_devices):
        import re

        from citus_tpu.ingest.tpch import QUERIES, load_into_session

        sess = citus_tpu.connect(data_dir=str(tmp_path / "d"),
                                 n_devices=n_devices,
                                 serving_result_cache_bytes=0)
        load_into_session(sess, sf=0.002)
        sess.execute(QUERIES["Q3"]).rows()
        programs = [e[0].as_text()
                    for e in sess.executor.plan_cache._entries.values()]
        sess.close()
        assert programs
        glue = {"parameter", "constant", "broadcast", "iota", "tuple",
                "get-tuple-element", "bitcast"}
        for text in programs:
            scopes = set(re.findall(r"ct\.(\w+)", text))
            assert "lookup_join" in scopes
            assert scopes >= {"agg_sort", "sort", "reduce", "topk",
                              "scan_out", "join_out", "output_pack"}
            if n_devices > 1:
                # (`unpack` is reshapes, which compile to nothing)
                assert scopes >= {"repartition", "pack", "exchange"}
            # instructions traced from the program (their op_name is a
            # path from the jit down), constants and the like aside
            traced = [m.group(2) for m in re.finditer(
                r'= \S+ ([\w\-]+)\(.*op_name="(jit\([^"]*)"', text)
                if m.group(1) not in glue]
            outside = [p for p in traced if "ct." not in p]
            assert len(traced) > 100
            assert len(outside) < 0.05 * len(traced), outside[:10]
