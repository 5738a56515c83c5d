"""One-off SF100 capability run: TPC-H Q3 + dual-repartition join at
SF100 on a single chip via slab-streamed ingest and streamed execution.

Not part of the default bench.py sweep: the 600M-row ingest alone
takes most of an hour on one core.  The run demonstrates correctness +
completion at the BASELINE north-star scale; results publish into
BASELINE.json under *_sf100_* metric names.

Env: SF100_DATA_DIR (reuse a loaded dir), SF100_SCALE (default 100).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time


def main():
    scale = float(os.environ.get("SF100_SCALE", "100"))
    data_dir = os.environ.get("SF100_DATA_DIR")
    from citus_tpu.session import Session
    from citus_tpu.ingest.tpch import QUERIES
    from citus_tpu.ingest.tpch_slab import load_slabbed

    fresh = data_dir is None or not os.path.isdir(
        os.path.join(data_dir or "", "tables"))
    if data_dir is None:
        data_dir = tempfile.mkdtemp(prefix="citus_tpu_sf100_")
    print(f"data dir: {data_dir}", flush=True)
    sess = Session(data_dir=data_dir, serving_result_cache_bytes=0)
    if fresh:
        t0 = time.perf_counter()

        def prog(what, done, total):
            print(f"  {what}: {done:,}/{total:,} "
                  f"@ {time.perf_counter() - t0:.0f}s", flush=True)

        counts = load_slabbed(sess, sf=scale, seed=0, progress=prog)
        print(f"loaded {counts} in {time.perf_counter() - t0:.0f}s",
              flush=True)
    n_li = sess.store.table_row_count("lineitem")
    n_ord = sess.store.table_row_count("orders")
    n_cust = sess.store.table_row_count("customer")
    print(f"rows: lineitem={n_li:,} orders={n_ord:,} customer={n_cust:,}",
          flush=True)

    from citus_tpu.executor.scanpipe import resolve_scan_mode

    lines = []
    for name, sql, rows in [
        ("dual_repartition_join_sf100_rows_per_sec",
         "select count(*) from orders, lineitem "
         "where o_custkey = l_suppkey", n_ord + n_li),
        ("tpch_q3_sf100_rows_per_sec", QUERIES["Q3"],
         n_cust + n_ord + n_li),
    ]:
        t0 = time.perf_counter()
        r = sess.execute(sql)
        cold = time.perf_counter() - t0
        # warm = compiled plan, cold data path: the feed cache is
        # cleared so the timed run actually rebuilds its feeds (at
        # SF100 the big side streams either way; the small sides'
        # pipelined builds are what the phase keys must describe —
        # resetting stats AFTER a cache-served run would publish
        # structurally-zero phases)
        sess.executor.feed_cache.clear()
        sess.executor.scan_stats.reset()
        t0 = time.perf_counter()
        # the measured run must record its span tree (phase keys
        # below derive from it; auto-degrade must not sample it out)
        with sess.settings.override(trace_fast_statement_ms=0):
            r = sess.execute(sql)
        warm = time.perf_counter() - t0
        # per-phase walls + the bytes-on-wire ratio for the warm run:
        # "no longer transfer-bound" must be artifact-backed, not
        # PERF_NOTES prose.  The phase_*_seconds walls now come from
        # the warm run's SPAN TRACE (stats/tracing.py — the same spans
        # EXPLAIN ANALYZE's Timing line renders; scan.* legs from
        # pipelined resident feeds, stream.* legs from the batched
        # stream path), byte totals from ScanPhaseStats
        from bench import trace_phase_keys

        ss = sess.executor.scan_stats.snapshot()
        line = {"metric": name, "value": round(rows / warm, 1),
                "unit": "rows/s",
                "vs_baseline": round(rows / warm / (75_000_000 / 16.0), 3),
                "seconds": round(warm, 1), "cold_seconds": round(cold, 1),
                "sf": scale, "rows_out": r.row_count,
                "streamed_batches": r.streamed_batches,
                "scan_pipeline": resolve_scan_mode(sess.settings),
                "bytes_on_wire": ss["bytes_on_wire"],
                "bytes_decoded": ss["bytes_decoded"],
                "wire_ratio": (round(ss["bytes_on_wire"]
                                     / ss["bytes_decoded"], 4)
                               if ss["bytes_decoded"] else None)}
        line.update(trace_phase_keys(
            sess.stats.tracing.last_trace(), wall_seconds=warm,
            sql=sql))
        lines.append(line)
        print(json.dumps(line), flush=True)

    # publish (same best-effort map bench.py uses)
    try:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BASELINE.json")
        with open(path) as f:
            doc = json.load(f)
        doc.setdefault("published", {})
        for line in lines:
            # .get: "note" was never stamped on any line, so the
            # strict lookup made every publish die silently in the
            # except below (pre-existing; found wiring the trace keys)
            doc["published"][line["metric"]] = {
                k: line.get(k) for k in ("value", "vs_baseline", "sf",
                                         "seconds", "cold_seconds",
                                         "streamed_batches",
                                         "phase_source")}
        with open(path + ".tmp", "w") as f:
            json.dump(doc, f, indent=2)
        os.replace(path + ".tmp", path)
    except Exception as e:  # pragma: no cover
        print(f"publish skipped: {e}", file=sys.stderr)


if __name__ == "__main__":
    main()
