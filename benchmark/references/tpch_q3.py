"""Plain reference for `statements/tpch_q3.sql` (TPC-H Q3, validation
parameters SEGMENT = BUILDING, DATE = 1995-03-15): numpy over the
benchmark's own generated arrays.  It keeps the revenue of EVERY
qualifying order, not the top ten: the comparison needs the runner-up
to judge near-ties at float32.  `reference_q3` / `compare_q3` of
`chip_smoke.py` (PR 23), kept as arrays so it can be cached."""

from __future__ import annotations

import numpy as np

from .common import close, days, float_tol, iso, rel_err


def tolerance(row_counts: dict) -> float:
    return float_tol(row_counts["lineitem"])


def build(data: dict) -> dict[str, np.ndarray]:
    cust, orders, li = data["customer"], data["orders"], data["lineitem"]
    cutoff = days("1995-03-15")
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
    idx = np.flatnonzero(np.bincount(oi[l_ok], minlength=len(okey)) > 0)
    return {"orderkey": okey[idx].astype(np.int64), "revenue": rev[idx],
            "orderdate": orders["o_orderdate"][idx].astype(np.int64),
            "shippriority": orders["o_shippriority"][idx].astype(np.int64)}


def compare(rows: list[tuple], ref: dict, tol: float):
    """The engine's top 10 is right when every row carries its order's
    reference revenue/date/priority, the rows are in `revenue desc,
    o_orderdate` order, and no order left out beats the last one kept —
    each up to the float32 tolerance (a near-tie may break either way).
    Returns (mismatches, largest relative error of a revenue)."""
    okeys, revenue = ref["orderkey"], ref["revenue"]
    bad, err = [], 0.0
    k = min(10, len(okeys))
    if len(rows) != k:
        return [f"q3 returned {len(rows)} rows, reference has {k}"], None
    keys = [int(r[0]) for r in rows]
    if len(set(keys)) != len(keys):
        bad.append(f"q3 repeats an order: {keys}")
    pos = np.searchsorted(okeys, keys)
    kept = []
    for r, key, p in zip(rows, keys, pos):
        if p >= len(okeys) or int(okeys[p]) != key:
            bad.append(f"q3 order {key} does not qualify")
            continue
        kept.append(int(p))
        err = max(err, rel_err(r[1], revenue[p]))
        if not close(r[1], revenue[p], tol):
            bad.append(f"q3 order {key} revenue {r[1]} vs {revenue[p]}")
        w_date, w_prio = iso(ref["orderdate"][p]), int(ref["shippriority"][p])
        if str(r[2]) != w_date or int(r[3]) != w_prio:
            bad.append(f"q3 order {key} {r[2]},{r[3]} vs {w_date},{w_prio}")
    for a, b in zip(rows, rows[1:]):
        ra, rb = float(a[1]), float(b[1])
        if ra < rb and not close(ra, rb, tol):
            bad.append(f"q3 order broken: {ra} before {rb}")
    if not bad and len(okeys) > k:
        left_out = np.ones(len(okeys), dtype=bool)
        left_out[kept] = False
        runner_up = float(revenue[left_out].max())
        last = float(revenue[kept].min())
        if runner_up > last and not close(runner_up, last, tol):
            bad.append(f"q3 kept revenue {last} but left out {runner_up}")
    return bad, err
