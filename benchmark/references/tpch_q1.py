"""Plain reference for `statements/tpch_q1.sql` (TPC-H Q1, validation
parameter DELTA = 90): numpy over the benchmark's own generated arrays,
float64, one `bincount` per aggregate.  `reference_q1` / `compare_q1`
of `chip_smoke.py` (PR 23), kept as arrays so the answer can be cached
beside the data directory."""

from __future__ import annotations

import numpy as np

from .common import close, days, float_tol, rel_err


def tolerance(row_counts: dict) -> float:
    return float_tol(row_counts["lineitem"])


def build(data: dict) -> dict[str, np.ndarray]:
    li = data["lineitem"]
    keep = li["l_shipdate"] <= days("1998-12-01") - 90
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
    # columns 2..8 of the answer, in the statement's order
    floats = np.stack([sq, sp, s(price * (1 - disc)),
                       s(price * (1 - disc) * (1 + tax)),
                       sq / n, sp / n, s(disc) / n], axis=1)
    return {"groups": groups, "floats": floats, "counts": n.astype(np.int64)}


def compare(rows: list[tuple], ref: dict, tol: float):
    """(mismatches, largest relative error of a float column)."""
    want = [(str(g)[0], str(g)[1]) for g in ref["groups"]]
    got = sorted(rows, key=lambda r: (r[0], r[1]))
    if [(r[0], r[1]) for r in got] != want:
        return [f"q1 groups {[(r[0], r[1]) for r in got]} != {want}"], None
    bad, err = [], 0.0
    for i, (r, w) in enumerate(zip(got, want)):
        if int(r[9]) != int(ref["counts"][i]):
            bad.append(f"q1 {w[0]}{w[1]} count {r[9]} != {ref['counts'][i]}")
        for c in range(2, 9):
            x = ref["floats"][i, c - 2]
            err = max(err, rel_err(r[c], x))
            if not close(r[c], x, tol):
                bad.append(f"q1 {w[0]}{w[1]} col {c}: {r[c]} vs {x}")
    return bad, err
