#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the Session path starts on the chip.

One process, one TPU chip (``--chips 4``: the four-chip mesh path only).
Through ``citus_tpu.connect()`` / ``Session.execute`` and nothing below
them it loads TPC-H (``ingest/tpch.py``, all eight tables at the
specification's column widths and key distributions), answers a
scan-aggregate (Q1), the join-heavy Q3, a dual-repartition join, one
INSERT + UPDATE + DELETE read back, and 100 fast-path point lookups —
cold, then warm — and holds every answer to a plain numpy reference
computed from ``generate_tables(sf, seed)``, never from the engine's own
store.  Then it closes the session, reopens the same ``data_dir`` and
answers Q3 again: that answer must come from the persistent executable
cache (a load from disk, not a compile).

Output: one JSON object per line; the LAST line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
No accelerator means a non-zero exit and no result line at all.  A
mismatch, an exception in any phase or a span left open means a non-zero
exit and ``"ok": false``.  No number printed here is a benchmark: the
walls are single readings that say the statement ran, not how fast the
engine is.
"""

from __future__ import annotations

import argparse
import datetime
import json
import math
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
# the two values tests/test_chip_smoke.py overrides to rehearse the script
# on the CPU: the platform JAX must report, and where the data lives
REQUIRED_PLATFORM = "tpu"
DATA_ROOT = os.path.join(ROOT, ".benchdata")

_F32_EPS = float(np.finfo(np.float32).eps)
_EPOCH = datetime.date(1970, 1, 1)

DUAL_REPARTITION_SQL = ("select count(*) from orders, lineitem "
                        "where o_custkey = l_suppkey")
POINT_SQL = ("select o_orderkey, o_custkey, o_totalprice, o_orderdate "
             "from orders where o_orderkey = {key}")
N_POINT_LOOKUPS = 100
DML_ROWS = 1000
# counters whose per-statement deltas say which paths actually ran
_COUNTERS = ("capacity_retries", "retries_total", "oom_events_total",
             "queries_repartition", "queries_streamed",
             "groupby_bucketed_total", "lookup_sorted_total",
             "lookup_dense_total", "lookup_sorted_joins_total",
             "lookup_dense_joins_total", "lookup_probe_slots_total",
             "broadcast_joins_total",
             "deferred_columns_total", "deferred_gathers_total",
             "device_decoded_bytes_total",
             "shuffle_bytes_total", "queries_fast_path",
             "point_index_lookups", "exec_cache_hits_total",
             "exec_cache_misses_total", "exec_cache_rejects_total")


def emit(obj: dict) -> None:
    print(json.dumps(obj, default=str), flush=True)


def _days(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - _EPOCH).days


def _iso(days: int) -> str:
    return (_EPOCH + datetime.timedelta(days=int(days))).isoformat()


def float_tol(n_rows: int) -> float:
    """Relative tolerance for a float32 sum over `n_rows` rows, fixed
    from the dtype before any run: the engine stores and accumulates
    DOUBLE PRECISION columns in float32 (compute_dtype), the reference
    in float64, and the rounding error of a float32 sum grows like a
    random walk in the worst (sequential) summation order."""
    return max(1e-6, 2.0 * math.sqrt(max(n_rows, 1)) * _F32_EPS)


# ---------------------------------------------------------------------------
# the plain reference (numpy over generate_tables — not the engine's store)

def reference_q1(data: dict) -> list[tuple]:
    li = data["lineitem"]
    keep = li["l_shipdate"] <= _days("1998-12-01") - 90
    key = np.char.add(li["l_returnflag"][keep].astype("U1"),
                      li["l_linestatus"][keep].astype("U1"))
    groups, inv = np.unique(key, return_inverse=True)
    qty = li["l_quantity"][keep]
    price = li["l_extendedprice"][keep]
    disc = li["l_discount"][keep]
    tax = li["l_tax"][keep]
    n = np.bincount(inv, minlength=len(groups))

    def s(w):
        return np.bincount(inv, weights=w, minlength=len(groups))

    sq, sp = s(qty), s(price)
    sd = s(price * (1 - disc))
    sc = s(price * (1 - disc) * (1 + tax))
    sdisc = s(disc)
    return [(g[0], g[1], sq[i], sp[i], sd[i], sc[i], sq[i] / n[i],
             sp[i] / n[i], sdisc[i] / n[i], int(n[i]))
            for i, g in enumerate(groups)]


def reference_q3(data: dict) -> dict:
    """Revenue of EVERY qualifying order (not just the top 10): the
    comparison needs the runner-up to judge near-ties at float32."""
    cust, orders, li = data["customer"], data["orders"], data["lineitem"]
    cutoff = _days("1995-03-15")
    building = np.zeros(int(cust["c_custkey"].max()) + 1, dtype=bool)
    building[cust["c_custkey"][cust["c_mktsegment"] == "BUILDING"]] = True
    okey = orders["o_orderkey"]  # ascending by construction
    o_ok = building[orders["o_custkey"]] & (orders["o_orderdate"] < cutoff)
    oi = np.searchsorted(okey, li["l_orderkey"])
    l_ok = (li["l_shipdate"] > cutoff) & o_ok[oi]
    rev = np.bincount(
        oi[l_ok],
        weights=(li["l_extendedprice"] * (1 - li["l_discount"]))[l_ok],
        minlength=len(okey))
    hit = np.bincount(oi[l_ok], minlength=len(okey)) > 0
    idx = np.flatnonzero(hit)
    return {int(okey[i]): (float(rev[i]), _iso(orders["o_orderdate"][i]),
                           int(orders["o_shippriority"][i]))
            for i in idx}


def reference_dual(data: dict) -> int:
    o = np.bincount(data["orders"]["o_custkey"])
    l = np.bincount(data["lineitem"]["l_suppkey"])
    n = min(len(o), len(l))
    return int((o[:n].astype(np.int64) * l[:n].astype(np.int64)).sum())


def reference_points(data: dict, seed: int) -> list[tuple[int, list]]:
    """(key, expected rows) for the point lookups: nine in ten keys
    exist, one in ten does not (order keys are 4i+1; 4i+2 never is)."""
    orders = data["orders"]
    rng = np.random.default_rng(seed + 1)
    picks = rng.integers(0, len(orders["o_orderkey"]), N_POINT_LOOKUPS)
    out = []
    for j, i in enumerate(picks):
        if j % 10 == 9:
            out.append((int(orders["o_orderkey"][i]) + 1, []))
        else:
            out.append((int(orders["o_orderkey"][i]), [(
                int(orders["o_orderkey"][i]), int(orders["o_custkey"][i]),
                float(orders["o_totalprice"][i]),
                _iso(orders["o_orderdate"][i]))]))
    return out


def reference_dml() -> list[tuple]:
    rows = {k: (k * 3, f"n{k % 5}") for k in range(DML_ROWS)}
    for k in rows:
        if k % 2 == 0:
            rows[k] = (rows[k][0] + 1, rows[k][1])
    return [(k, v, note) for k, (v, note) in sorted(rows.items())
            if k % 10 != 3]


def build_reference(data: dict, seed: int) -> dict:
    return {"q1": reference_q1(data), "q3": reference_q3(data),
            "dual": reference_dual(data),
            "points": reference_points(data, seed),
            "dml": reference_dml()}


# ---------------------------------------------------------------------------
# comparisons: each returns (mismatch strings — empty = equal, the largest
# relative error seen in a float column or None)

def _rel_err(got, want) -> float:
    return abs(float(got) - float(want)) / max(abs(float(want)), 1.0)


def _close(got, want, tol: float) -> bool:
    return _rel_err(got, want) <= tol


def compare_q1(rows: list[tuple], want: list[tuple], tol: float):
    bad, err = [], 0.0
    got = sorted(rows, key=lambda r: (r[0], r[1]))
    if [(r[0], r[1]) for r in got] != [(w[0], w[1]) for w in want]:
        return [f"q1 groups {[(r[0], r[1]) for r in got]} != "
                f"{[(w[0], w[1]) for w in want]}"], None
    for r, w in zip(got, want):
        if int(r[9]) != w[9]:
            bad.append(f"q1 {w[0]}{w[1]} count {r[9]} != {w[9]}")
        for c in range(2, 9):
            err = max(err, _rel_err(r[c], w[c]))
            if not _close(r[c], w[c], tol):
                bad.append(f"q1 {w[0]}{w[1]} col {c}: {r[c]} vs {w[c]}")
    return bad, err


def compare_q3(rows: list[tuple], want: dict, tol: float):
    """The engine's top 10 is right when every row carries its order's
    reference revenue/date/priority, the rows are in `revenue desc,
    o_orderdate` order, and no order left out beats the last one kept —
    each up to the float32 tolerance (a near-tie may break either way)."""
    bad, err = [], 0.0
    k = min(10, len(want))
    if len(rows) != k:
        return [f"q3 returned {len(rows)} rows, reference has {k}"], None
    keys = [int(r[0]) for r in rows]
    if len(set(keys)) != len(keys):
        bad.append(f"q3 repeats an order: {keys}")
    for r in rows:
        w = want.get(int(r[0]))
        if w is None:
            bad.append(f"q3 order {r[0]} does not qualify")
            continue
        err = max(err, _rel_err(r[1], w[0]))
        if not _close(r[1], w[0], tol):
            bad.append(f"q3 order {r[0]} revenue {r[1]} vs {w[0]}")
        if str(r[2]) != w[1] or int(r[3]) != w[2]:
            bad.append(f"q3 order {r[0]} {r[2]},{r[3]} vs {w[1]},{w[2]}")
    for a, b in zip(rows, rows[1:]):
        ra, rb = float(a[1]), float(b[1])
        if ra < rb and not _close(ra, rb, tol):
            bad.append(f"q3 order broken: {ra} before {rb}")
    if not bad and len(want) > k:
        kept = set(keys)
        runner_up = max(v[0] for o, v in want.items() if o not in kept)
        last = min(want[o][0] for o in kept)
        if runner_up > last and not _close(runner_up, last, tol):
            bad.append(f"q3 kept revenue {last} but left out {runner_up}")
    return bad, err


def check_dual(rows: list[tuple], want: int):
    return ([] if int(rows[0][0]) == want else
            [f"dual count {rows[0][0]} != {want}"]), None


def compare_rows(name: str, rows: list[tuple], want: list[tuple],
                 tol: float) -> list:
    if len(rows) != len(want):
        return [f"{name}: {len(rows)} rows, reference has {len(want)}"]

    def same(a, b) -> bool:
        if isinstance(b, float):
            return _close(a, b, tol)
        return str(a) == b if isinstance(b, str) else int(a) == b

    return [f"{name}: {tuple(r)} vs {w}" for r, w in zip(rows, want)
            if not all(same(a, b) for a, b in zip(r, w))][:5]


# ---------------------------------------------------------------------------
# driving the session

class Smoke:
    def __init__(self, device: dict):
        self.device = device
        self.ok = True

    def fail(self, phase: str, mismatches: list) -> None:
        self.ok = False
        emit({"phase": phase, "ok": False, "mismatches": mismatches[:8]})

    def explain_tags(self, sess, sql: str) -> list[str]:
        from citus_tpu.planner.explain import EXPLAIN_TAGS

        lines = [str(r[0]).strip() for r in
                 sess.execute("explain " + sql).rows()]
        # the scan tag with its mode (`pipelined scan: device`), every
        # other tag by name
        return [t for t in EXPLAIN_TAGS if t != "pipelined scan"
                and any(t in ln for ln in lines)] + \
            [ln for ln in lines if ln.startswith("pipelined scan:")]

    def memory(self, sess) -> dict:
        r = sess.execute("select citus_stat_memory()")
        m = dict(zip(r.column_names, r.rows()[0]))
        out = {"bytes_limit": m["device_bytes_limit"],
               "peak_bytes_in_use": m["device_peak_bytes_in_use"],
               "ledger_peak_bytes": m["peak_bytes"]}
        if self.device["platform"] == "tpu" and not out["bytes_limit"]:
            self.fail("memory", ["the backend reports no bytes_limit"])
        return out

    @staticmethod
    def phases(sess, sql: str) -> dict | None:
        """Milliseconds by phase of the statement that just ran, from
        its span tree (stats/tracing.py): the children of `execute`
        summed by name.  None when the recorder sampled this run out."""
        from citus_tpu.stats.tracing import clamp_sql

        doc = sess.stats.tracing.last_trace()
        if doc is None or doc.get("sql") != clamp_sql(sql):
            return None
        out: dict[str, float] = {}
        for top in doc["root"].get("children", ()):
            for span in (top.get("children", ())
                         if top["name"] == "execute" else (top,)):
                out[span["name"]] = round(
                    out.get(span["name"], 0.0) + span["dur_ms"], 3)
        return out

    @staticmethod
    def programs(sess) -> dict:
        """What the executable cache holds for this data_dir: entry →
        the capacity stages compiled into that program (`agg_grid`
        means the bucketed group-by is in it).  EXPLAIN's tags come from
        row estimates; these come from the programs that were built.  A
        sort-and-scan lookup join has no capacity and so no stage here:
        the `lookup_sorted_total` counter and EXPLAIN's `sorted lookup`
        tag say that it ran."""
        from citus_tpu.utils.io import read_json_checked

        cache_dir = sess.executor.exec_cache.dir
        out = {}
        for f in sorted(os.listdir(cache_dir)
                        if os.path.isdir(cache_dir) else ()):
            if f.endswith(".meta.json"):
                meta = read_json_checked(os.path.join(cache_dir, f))
                out[f] = sorted({kind for _, kind, _ in
                                 meta["stage_keys"]})
        return out

    def statement(self, sess, name: str, sql: str, check) -> None:
        """EXPLAIN, then the statement cold and warm; `check(rows)`
        holds one answer to the reference (mismatches, largest relative
        float error) and runs outside both timed regions."""
        tags = self.explain_tags(sess, sql)
        programs0 = self.programs(sess)
        sess.executor.scan_stats.reset()
        c0 = sess.stats.counters.snapshot()
        t0 = time.perf_counter()
        cold = sess.execute(sql).rows()
        cold_s = time.perf_counter() - t0
        cold_ms = self.phases(sess, sql)
        t0 = time.perf_counter()
        warm = sess.execute(sql).rows()
        warm_s = time.perf_counter() - t0
        warm_ms = self.phases(sess, sql)
        c1 = sess.stats.counters.snapshot()
        scan = sess.executor.scan_stats.snapshot()
        (bad_cold, err_cold), (bad_warm, err_warm) = check(cold), check(warm)
        bad = bad_cold + bad_warm
        emit({"stmt": name, "ok": not bad, "cold_s": cold_s,
              "warm_s": warm_s, "rows": len(warm), "tags": tags,
              "max_rel_err": max((e for e in (err_cold, err_warm)
                                  if e is not None), default=None),
              "cold_ms": cold_ms, "warm_ms": warm_ms,
              "compiled_stages": [
                  stages for f, stages in self.programs(sess).items()
                  if f not in programs0],
              "counters": {k: c1.get(k, 0) - c0.get(k, 0)
                           for k in _COUNTERS
                           if c1.get(k, 0) != c0.get(k, 0)},
              "scan": {k: scan[k] for k in
                       ("feeds_pipelined", "bytes_on_wire",
                        "bytes_decoded", "wire_bytes_by_device")},
              "hbm": self.memory(sess)})
        if bad:
            self.fail(name, bad)


def connect(data_dir: str, n_devices: int):
    import citus_tpu

    # result cache off: the warm run must execute, not be remembered
    return citus_tpu.connect(data_dir=data_dir, n_devices=n_devices,
                             serving_result_cache_bytes=0)


def load(sess, data: dict, sf: float, seed: int, shard_count, tables):
    """Load TPC-H through the ingest entry point, or reuse a data_dir
    that already holds exactly these rows."""
    from citus_tpu.ingest.tpch import load_into_session

    want = {t: len(next(iter(cols.values()))) for t, cols in data.items()
            if tables is None or t in tables or t in ("region", "nation")}
    t0 = time.perf_counter()
    have = {t: sess.store.table_row_count(t) for t in want} \
        if sess.catalog.has_table("lineitem") else {}
    reused = have == want
    if not reused:
        if have:
            raise RuntimeError(
                f"{sess.data_dir} holds other rows ({have}, want {want}): "
                "remove it")
        got = load_into_session(sess, sf=sf, seed=seed,
                                shard_count=shard_count, tables=tables)
        if {t: got[t] for t in want} != want:
            raise RuntimeError(f"loaded {got}, generated {want}")
    emit({"phase": "load", "ok": True, "reused": reused, "rows": want,
          "seconds": time.perf_counter() - t0})


def served_from_cache(smoke: Smoke, data_dir: str, n_devices: int,
                      name: str, sql: str, check) -> None:
    """A fresh session on the same data_dir answers a statement the
    closed one compiled from the persistent executable cache: the
    shared cache's counters must show a load from disk and no compile.
    (The cache object keeps no executable in memory — only the closed
    session's plan cache did — so every hit counted here is a
    deserialization onto the mesh.)"""
    from citus_tpu.executor.execcache import exec_cache_for

    ec = exec_cache_for(data_dir)
    before = ec.snapshot()
    sess = connect(data_dir, n_devices)
    try:
        t0 = time.perf_counter()
        rows = sess.execute(sql).rows()
        wall = time.perf_counter() - t0
        after = ec.snapshot()
    finally:
        sess.close()
    delta = {k: after[k] - before[k] for k in
             ("hits_total", "misses_total", "rejects_total",
              "compiles_total")}
    bad, _ = check(rows)
    if delta["hits_total"] < 1 or delta["compiles_total"] or \
            delta["rejects_total"]:
        bad.append(f"reopened {name} was not served from the "
                   f"executable cache: {delta}")
    emit({"stmt": f"{name}_reopened_{n_devices}dev", "ok": not bad,
          "wall_s": wall, "exec_cache": delta})
    if bad:
        smoke.fail("exec_cache", bad)


def run_one_chip(smoke: Smoke, sf: float, seed: int) -> None:
    from citus_tpu.ingest.tpch import QUERIES, generate_tables

    data_dir = os.path.join(DATA_ROOT, f"chip_smoke_sf{sf:g}_seed{seed}")
    t0 = time.perf_counter()
    data = generate_tables(sf, seed)
    ref = build_reference(data, seed)
    n_li = len(data["lineitem"]["l_orderkey"])
    tol = float_tol(n_li)
    emit({"phase": "reference", "seconds": time.perf_counter() - t0,
          "float_tol": tol})
    sess = connect(data_dir, 1)
    try:
        load(sess, data, sf, seed, None, None)
        del data
        smoke.statement(sess, "q1", QUERIES["Q1"],
                        lambda rows: compare_q1(rows, ref["q1"], tol))
        smoke.statement(sess, "q3", QUERIES["Q3"],
                        lambda rows: compare_q3(rows, ref["q3"], tol))
        smoke.statement(
            sess, "dual_repartition", DUAL_REPARTITION_SQL,
            lambda rows: check_dual(rows, ref["dual"]))
        dml(smoke, sess, ref["dml"])
        point_lookups(smoke, sess, ref["points"], tol)
    finally:
        sess.close()
    served_from_cache(smoke, data_dir, 1, "q3", QUERIES["Q3"],
                      lambda rows: compare_q3(rows, ref["q3"], tol))


def dml(smoke: Smoke, sess, want: list[tuple]) -> None:
    t0 = time.perf_counter()
    sess.execute("drop table if exists smoke_kv")
    sess.execute("create table smoke_kv (k bigint, v bigint, note text)")
    sess.execute("select create_distributed_table('smoke_kv', 'k')")
    sess.execute("insert into smoke_kv values " + ", ".join(
        f"({k}, {k * 3}, 'n{k % 5}')" for k in range(DML_ROWS)))
    updated = sess.execute(
        "update smoke_kv set v = v + 1 where k % 2 = 0").rows()[0][0]
    deleted = sess.execute(
        "delete from smoke_kv where k % 10 = 3").rows()[0][0]
    wall = time.perf_counter() - t0
    rows = sess.execute(
        "select k, v, note from smoke_kv order by k").rows()
    total = sess.execute("select count(*), sum(v) from smoke_kv").rows()[0]
    bad = compare_rows("dml", rows, want, 0.0)
    if (int(updated), int(deleted)) != (DML_ROWS // 2, DML_ROWS // 10):
        bad.append(f"dml touched {updated}/{deleted} rows")
    if (int(total[0]), int(total[1])) != (len(want),
                                          sum(w[1] for w in want)):
        bad.append(f"dml aggregate {tuple(total)}")
    emit({"stmt": "dml", "ok": not bad, "wall_s": wall,
          "rows": len(rows)})
    if bad:
        smoke.fail("dml", bad)


def point_lookups(smoke: Smoke, sess, want: list, tol: float) -> None:
    tags = smoke.explain_tags(sess, POINT_SQL.format(key=want[0][0]))
    c0 = sess.stats.counters.snapshot()
    walls, answers = [], []
    for key, _ in want:
        t0 = time.perf_counter()
        answers.append(sess.execute(POINT_SQL.format(key=key)).rows())
        walls.append(time.perf_counter() - t0)
    c1 = sess.stats.counters.snapshot()
    bad = []
    for (key, rows_want), rows in zip(want, answers):
        bad += compare_rows(f"point {key}", rows, rows_want, tol)
    emit({"stmt": "point_lookups", "ok": not bad, "n": len(want),
          "cold_s": walls[0], "median_s": float(np.median(walls[1:])),
          "tags": tags,
          "counters": {k: c1.get(k, 0) - c0.get(k, 0) for k in
                       ("queries_fast_path", "point_index_lookups")}})
    if bad:
        smoke.fail("point_lookups", bad)


def run_four_chips(smoke: Smoke, sf: float, seed: int) -> None:
    """The mesh path and what it is compared with, and no other phase:
    Q3 and the dual-repartition join on four devices against the same
    numpy reference, two checks that the mesh is real, and a two-device
    session whose statement a reopened one loads from the executable
    cache, on the four-device host."""
    from citus_tpu.ingest.tpch import QUERIES, generate_tables

    if smoke.device["count"] < 4:
        raise RuntimeError(
            f"--chips 4 needs four devices, have {smoke.device['count']}")
    tables = {"customer", "orders", "lineitem"}
    data_dir = os.path.join(DATA_ROOT,
                            f"chip_smoke_sf{sf:g}_seed{seed}_mesh")
    data = {t: c for t, c in generate_tables(sf, seed).items()
            if t in tables or t in ("region", "nation")}
    ref = {"q3": reference_q3(data), "dual": reference_dual(data)}
    tol = float_tol(len(data["lineitem"]["l_orderkey"]))

    def check_q3(rows):
        return compare_q3(rows, ref["q3"], tol)

    def check_dual_ref(rows):
        return check_dual(rows, ref["dual"])

    sess = connect(data_dir, 4)
    try:
        load(sess, data, sf, seed, 8, tables)
        del data
        for name, sql, check in (("q3", QUERIES["Q3"], check_q3),
                                 ("dual_repartition",
                                  DUAL_REPARTITION_SQL, check_dual_ref)):
            c0 = sess.stats.counters.snapshot().get(
                "shuffle_bytes_total", 0)
            smoke.statement(sess, name + "_4dev", sql, check)
            shuffle = sess.stats.counters.snapshot().get(
                "shuffle_bytes_total", 0) - c0
            by_dev = sess.executor.scan_stats.snapshot()[
                "wire_bytes_by_device"]
            shares = [b / max(sum(by_dev), 1) for b in by_dev]
            bad = []
            if len(shares) != 4 or not all(0.15 <= s <= 0.35
                                           for s in shares):
                bad.append(f"feed bytes by device {by_dev}: every one "
                           "of four should hold 15-35 %")
            # Q3 repartitions customer ⋈ orders over all_to_all; the
            # dual-repartition COUNT is pushed down to a psum over key
            # directories and by design moves no all_to_all bytes
            if name == "q3" and shuffle <= 0:
                bad.append("no bytes crossed the mesh (shuffle_bytes 0)")
            emit({"check": f"mesh_{name}", "ok": not bad,
                  "feed_share_by_device": shares,
                  "shuffle_bytes": shuffle})
            if bad:
                smoke.fail(f"mesh_{name}", bad)
    finally:
        sess.close()
    # a mesh narrower than the backend: compile and store on two of
    # the four devices, then reload.  The dual-repartition count, not
    # Q3: one Q3 program takes the chip's compiler about five minutes,
    # capacity feedback compiles it twice, and four chips are charged
    # for every second of it
    sess = connect(data_dir, 2)
    try:
        smoke.statement(sess, "dual_repartition_2dev",
                        DUAL_REPARTITION_SQL, check_dual_ref)
    finally:
        sess.close()
    served_from_cache(smoke, data_dir, 2, "dual_repartition",
                      DUAL_REPARTITION_SQL, check_dual_ref)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=1.0,
                    help="TPC-H scale factor (default 1: 6.0 M lineitem)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: the four-chip mesh path only")
    args = ap.parse_args(argv)

    # before anything else: no accelerator, no run and no result line
    import jax

    devs = jax.devices()
    if devs[0].platform != REQUIRED_PLATFORM:
        print(f"chip_smoke: JAX found platform {devs[0].platform!r}, "
              f"not {REQUIRED_PLATFORM!r}: nothing was run",
              file=sys.stderr)
        return 2
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    import citus_tpu.native  # from this checkout: the script's own directory
    from citus_tpu.stats import tracing

    emit({"phase": "device", **device, "chips_used": args.chips})
    emit({"phase": "scale", "sf": args.sf, "seed": args.seed,
          "cut": "SF1, not the roadmap's SF10 target for judged cells: "
                 "host ingest is about 90 s per scale unit on one core "
                 "and the whole run has 1200 s"})
    smoke = Smoke(device)
    try:
        if args.chips == 4:
            run_four_chips(smoke, args.sf, args.seed)
        else:
            run_one_chip(smoke, args.sf, args.seed)
        open_spans = tracing.open_span_count()
        if open_spans:
            smoke.fail("tracing", [f"{open_spans} span(s) left open"])
        err = citus_tpu.native.load_error()
        emit({"phase": "environment",
              "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS"),
              "JAX_COMPILATION_CACHE_DIR":
                  os.environ.get("JAX_COMPILATION_CACHE_DIR"),
              "compile_cache_dir": jax.config.jax_compilation_cache_dir,
              "compile_cache_enabled":
                  jax.config.jax_enable_compilation_cache,
              "native_load_error": None if err is None else repr(err),
              "open_spans": open_spans})
    except Exception as e:
        # the one handler: it ends the run, it does not let it go on
        import traceback

        traceback.print_exc()
        emit({"phase": "exception", "ok": False, "error": repr(e)})
        smoke.ok = False
    emit({"ok": smoke.ok, "device": device})
    return 0 if smoke.ok else 1


if __name__ == "__main__":
    sys.exit(main())
