"""Multi-chip scaling driver: measured rows/s at 1/2/4/8 devices.

The scale axis the BENCH_r* artifacts never had: every published number
so far ran on ONE chip, and the MULTICHIP_r* artifacts were empty
shells (r05: rc 0, empty tail).  This driver makes the mesh dimension a
measured fact:

* the parent prepares ONE persistent dataset (shard_count=8 — divisible
  by every mesh width) and then spawns one CHILD PROCESS per device
  count.  A separate process per count is mandatory: the XLA device
  count is fixed at backend init (`xla_force_host_platform_device_count`
  must be set before the first jax import), so one process can never
  measure two mesh widths;
* each child runs Q1 (scan-aggregate), Q3 (repartition + colocated
  joins + grouped agg) and the dual-repartition join at its mesh width,
  printing one JSON line per config with rows/s, the hot device's
  measured cold-feed wire bytes (`feed_bytes_per_device` — the
  device-owned slice seam charges each device its own slice, so this is
  ≈ 1/N of the 1-device transfer when placement is spread), and the
  statement's static all_to_all volume (`shuffle_bytes` — what the
  cross-device dimension costs);
* the parent folds the lines into MULTICHIP_r<next>.json with
  per-device-count rows/s, speedup-vs-1-device and scaling-efficiency
  keys (rate_N / (N × rate_1)), and stamps `host_fake_devices` honestly
  when the mesh is virtual CPU devices.  A run that produces no metric
  lines records `skipped: true` WITH a reason or a nonzero rc — the
  silent-success shell (rc 0, empty tail, skipped false) is a shape
  tests/test_bench_artifacts.py rejects.

What CPU fake devices can and cannot predict is documented in
PERF_NOTES (round 14): the data-parallel compute split and the
per-device transfer split are real; ICI all_to_all latency/bandwidth is
not (fake-device collectives are memcpys through host RAM).

Env knobs: BENCH_MC_SF (default 2.0 — large enough that per-device
compute dominates fake-device dispatch overhead; the first run pays
a ~3 min single-core ingest, cached under BENCH_MC_DIR after),
BENCH_MC_REPEATS (default 3),
BENCH_MC_DEVICES (default: the widths of 1,2,4,8 the backend has — a
probe child asks it; the CPU backend makes virtual devices to order),
BENCH_MC_DIR (persistent dataset
dir, default .benchdata/multichip_sf<sf>), MULTICHIP_OUT (artifact
path; "0" disables writing, default MULTICHIP_r<next>.json).
"""

from __future__ import annotations

import glob
import json
import os
import re
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

QUERY_CONFIGS = (
    # (metric, query key or SQL, rows-processed spec)
    ("multichip_q1_rows_per_sec", "Q1", ("lineitem",)),
    ("multichip_q3_rows_per_sec", "Q3",
     ("customer", "orders", "lineitem")),
    ("multichip_dual_repartition_rows_per_sec",
     "select count(*) from orders, lineitem where o_custkey = l_suppkey",
     ("orders", "lineitem")),
    # high-cardinality GROUP BY on a non-distribution key: the partial
    # groups MUST cross the mesh (all_to_all combine) at every width >1
    # — the psum-directory pushdown cannot compile this shuffle away,
    # so the line measures what paying a genuine all_to_all costs/buys
    ("multichip_groupby_shuffle_rows_per_sec",
     "select l_partkey, count(*), sum(l_quantity) from lineitem "
     "group by l_partkey",
     ("lineitem",)),
)


def _sf() -> float:
    return float(os.environ.get("BENCH_MC_SF", "2.0"))


def _data_dir() -> str:
    tag = ("sf%g" % _sf()).replace(".", "_")
    return os.environ.get(
        "BENCH_MC_DIR",
        os.path.join(ROOT, ".benchdata", f"multichip_{tag}"))


# ---------------------------------------------------------------------------
# child: one mesh width, one process


def _child(n_devices: int) -> None:
    from citus_tpu.runtime import ensure_jax_configured

    platform = os.environ.get("JAX_PLATFORMS") or None
    ensure_jax_configured(platform=platform,
                          host_device_count=n_devices)
    import jax

    if len(jax.devices()) < n_devices:
        raise RuntimeError(
            f"need {n_devices} devices, have {len(jax.devices())}")

    from citus_tpu.ingest.tpch import QUERIES, load_into_session
    from citus_tpu.session import Session
    from citus_tpu.stats import counters as sc

    repeats = int(os.environ.get("BENCH_MC_REPEATS", "3"))
    sess = Session(data_dir=_data_dir(), n_devices=n_devices,
                   serving_result_cache_bytes=0)
    try:
        if sess.store.table_row_count("lineitem") == 0:
            load_into_session(sess, sf=_sf(), seed=0, shard_count=8,
                              tables={"customer", "orders", "lineitem"})
        counts = {t: sess.store.table_row_count(t)
                  for t in ("customer", "orders", "lineitem")}
        platform = str(jax.default_backend())
        for metric, q, tables in QUERY_CONFIGS:
            sql = QUERIES.get(q, q)
            rows = sum(counts[t] for t in tables)
            # cold pass: measure the per-device feed transfer through
            # the pipelined scan's per-device wire ledger (feed cache
            # emptied so the bytes actually cross)
            sess.executor.feed_cache.clear()
            sess.executor.scan_stats.reset()
            sess.execute(sql)  # also warms the compile
            scan = sess.executor.scan_stats.snapshot()
            by_dev = scan.get("wire_bytes_by_device") or []
            feed_per_dev = max(by_dev) if by_dev else None
            snap0 = sess.stats.counters.snapshot()
            best = float("inf")
            # measured reps always record a span tree (the phase keys
            # stamped below derive from the last one)
            with sess.settings.override(trace_fast_statement_ms=0):
                for _ in range(repeats):
                    t0 = time.perf_counter()
                    r = sess.execute(sql)
                    best = min(best, time.perf_counter() - t0)
                    assert r.row_count > 0
            shuffle = (sess.stats.counters.snapshot().get(
                sc.SHUFFLE_BYTES_TOTAL, 0)
                - snap0.get(sc.SHUFFLE_BYTES_TOTAL, 0)) // repeats
            line = {
                "metric": metric,
                "n_devices": n_devices,
                "value": round(rows / best, 1),
                "unit": "rows/s",
                "seconds": round(best, 4),
                "sf": _sf(),
                "repeats": repeats,
                "rows_processed": rows,
                "feed_bytes_per_device": feed_per_dev,
                "shuffle_bytes": int(shuffle),
                "platform": platform,
                "host_fake_devices": platform == "cpu",
            }
            # phase walls of the last measured rep, derived from its
            # span trace (bench.trace_phase_keys — same provenance as
            # bench.py/bench_sf100.py, stamped phase_source="trace")
            from bench import trace_phase_keys

            line.update(trace_phase_keys(
                sess.stats.tracing.last_trace(), sql=sql))
            print(json.dumps(line), flush=True)
        if n_devices >= 2:
            # LAST (the failover shrinks this session's mesh): measured
            # kill-to-first-answer recovery under a mid-query device
            # loss — the number a preemption-tolerant stack lives by
            _device_loss_scenario(sess, n_devices, platform)
    finally:
        sess.close()


def _device_loss_scenario(sess, n_devices: int, platform: str) -> None:
    """Kill one fake device mid-query (MeshSim) and measure the wall
    clock from the kill to the first correct answer through the
    shrink-and-failover path.  Runs on its own replication-2 table
    (the TPC-H bench tables are replication 1 by design); the table is
    dropped afterward so the cached dataset dir stays canonical."""
    from citus_tpu.stats import counters as sc
    from citus_tpu.utils import faultinjection as fi

    sess.execute("DROP TABLE IF EXISTS dl_kv")
    sess.execute("SET shard_replication_factor = 2")
    sess.execute("CREATE TABLE dl_kv (id INT, v INT, grp INT)")
    sess.execute(
        f"SELECT create_distributed_table('dl_kv', 'id', {n_devices})")
    n = 60_000
    for base in range(0, n, 10_000):
        sess.execute("INSERT INTO dl_kv VALUES " + ", ".join(
            f"({base + i}, {(base + i) * 3}, {(base + i) % 13})"
            for i in range(10_000)))
    q = "select grp, count(*), sum(v) from dl_kv group by grp"
    warm = sorted(map(tuple, sess.execute(q).rows()))
    t_warm0 = time.perf_counter()
    sess.execute(q)
    warm_s = time.perf_counter() - t_warm0
    victim = sess.mesh.devices.flat[n_devices - 1].id
    snap0 = sess.stats.counters.snapshot()
    # after=1: feeds are warm, so the kill lands at the result fetch —
    # the program RAN and its answer died on the wire (mid-query)
    with fi.simulate_mesh(kill={victim}, after=1):
        t0 = time.perf_counter()
        r = sess.execute(q)
        recovery_s = time.perf_counter() - t0
    ok = sorted(map(tuple, r.rows())) == warm
    snap = sess.stats.counters.snapshot()
    rescued = (snap.get(sc.QUERIES_RESCUED_TOTAL, 0)
               - snap0.get(sc.QUERIES_RESCUED_TOTAL, 0))
    sess.execute("DROP TABLE dl_kv")
    print(json.dumps({
        "metric": "multichip_device_loss_recovery_seconds",
        "n_devices": n_devices,
        "value": round(recovery_s, 4),
        "unit": "s",
        "sf": _sf(),
        "rows_processed": n,
        "warm_seconds": round(warm_s, 4),
        "recovery_over_warm": (round(recovery_s / warm_s, 2)
                               if warm_s > 0 else None),
        "devices_after_failover": sess.n_devices,
        "queries_rescued_total": int(rescued),
        "oracle_identical": bool(ok),
        "platform": platform,
        "host_fake_devices": platform == "cpu",
    }), flush=True)


# ---------------------------------------------------------------------------
# parent: spawn one child per device count, fold the artifact


def _next_artifact_path() -> str:
    out = os.environ.get("MULTICHIP_OUT")
    if out:
        return out
    rounds = [int(m.group(1)) for p in glob.glob(
        os.path.join(ROOT, "MULTICHIP_r*.json"))
        if (m := re.search(r"MULTICHIP_r(\d+)\.json$", p))]
    nxt = (max(rounds) + 1) if rounds else 1
    return os.path.join(ROOT, f"MULTICHIP_r{nxt:02d}.json")


def _backend_widths() -> list[int]:
    """The mesh widths the backend can run, asked of a child so the
    parent stays off JAX.  The CPU backend makes virtual devices to
    order (each width's child sets its own count); an accelerator has
    the devices it has."""
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--probe"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        check=True)
    dev = json.loads(proc.stdout.strip().splitlines()[-1])
    if dev["platform"] == "cpu":
        return [1, 2, 4, 8]
    return [w for w in (1, 2, 4, 8) if w <= dev["count"]]


def main() -> int:
    if sys.argv[1:2] == ["--child"]:
        _child(int(sys.argv[2]))
        return 0
    if sys.argv[1:2] == ["--probe"]:
        from citus_tpu.runtime import ensure_jax_configured

        ensure_jax_configured(
            platform=os.environ.get("JAX_PLATFORMS") or None)
        import jax

        print(json.dumps({"platform": jax.devices()[0].platform,
                          "count": len(jax.devices())}))
        return 0

    env_counts = os.environ.get("BENCH_MC_DEVICES")
    device_counts = ([int(x) for x in env_counts.split(",")]
                     if env_counts else _backend_widths())
    tail_lines: list[str] = []
    rc = 0
    # widest mesh first: the first child to touch an empty dataset dir
    # creates the catalog, and its node set must span the WIDEST mesh
    # (8 nodes fold onto narrower meshes through node_device_map;
    # 1 node on an 8-device mesh would serialize everything onto
    # device 0 — the skew rebalance_mesh exists to fix, not to bench)
    for n in sorted(device_counts, reverse=True):
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child",
             str(n)],
            cwd=ROOT, capture_output=True, text=True, timeout=3600)
        for line in proc.stdout.splitlines():
            print(line, flush=True)
            tail_lines.append(line)
        if proc.returncode != 0:
            rc = proc.returncode
            err = proc.stderr.strip().splitlines()[-8:]
            msg = f"# child n_devices={n} rc={proc.returncode}: " + \
                " | ".join(err)
            print(msg, file=sys.stderr, flush=True)
            tail_lines.append(msg)

    # fold metric lines into per-device-count tables
    results: dict[str, dict[str, dict]] = {}
    for line in tail_lines:
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except json.JSONDecodeError:
            continue
        if "metric" in obj and "n_devices" in obj:
            results.setdefault(obj["metric"], {})[
                str(obj["n_devices"])] = obj

    speedup: dict[str, dict[str, float]] = {}
    efficiency: dict[str, dict[str, float]] = {}
    for metric, by_n in results.items():
        base = by_n.get("1")
        if base is None or not base.get("value"):
            continue
        for nd, obj in by_n.items():
            n = int(nd)
            if n <= 1:
                continue
            sp = obj["value"] / base["value"]
            speedup.setdefault(metric, {})[nd] = round(sp, 3)
            efficiency.setdefault(metric, {})[nd] = round(sp / n, 3)

    have_metrics = bool(results)
    host_fake = any(obj.get("host_fake_devices")
                    for by_n in results.values()
                    for obj in by_n.values())
    artifact = {
        "n_devices": device_counts,
        "rc": rc,
        "ok": rc == 0 and have_metrics,
        # a run that measured nothing must say WHY — the silent-success
        # shell (rc 0, empty tail, skipped false) is a rejected shape
        "skipped": not have_metrics,
        "skip_reason": (None if have_metrics
                        else "no child produced a metric line "
                             f"(rc={rc}; see tail)"),
        "host_fake_devices": host_fake,
        "sf": _sf(),
        "results": results,
        "speedup_vs_1dev": speedup,
        "scaling_efficiency": efficiency,
        "tail": "\n".join(tail_lines),
    }
    out = os.environ.get("MULTICHIP_OUT", "")
    if out != "0":
        path = _next_artifact_path()
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(artifact, f, indent=2)
        os.replace(tmp, path)
        print(f"# wrote {os.path.basename(path)}", file=sys.stderr,
              flush=True)
    # headline LAST (driver contract: final JSON line)
    q3 = results.get("multichip_q3_rows_per_sec", {})
    top = max(q3, key=lambda nd: q3[nd]["value"], default=None)
    if top is not None:
        print(json.dumps({
            "metric": "multichip_q3_best_rows_per_sec",
            "value": q3[top]["value"], "unit": "rows/s",
            "n_devices": int(top),
            "speedup_vs_1dev": speedup.get(
                "multichip_q3_rows_per_sec", {}).get(top),
            "host_fake_devices": host_fake,
        }), flush=True)
    return rc if have_metrics else (rc or 1)


if __name__ == "__main__":
    raise SystemExit(main())
