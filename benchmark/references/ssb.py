"""Plain reference for the thirteen Star Schema Benchmark statements
(`statements/ssb_q<flight>_<n>.sql`; the paper's section 3): numpy over
the benchmark's own generated arrays, independent of `citus_tpu`.

Every statement has one shape — restrict each dimension, look the fact
rows' foreign keys up in it, keep the fact rows whose every lookup found
a kept row and that pass the statement's own fact-side filter, group by
dimension attributes, sum an integer measure in int64, order — so one
function answers all thirteen from a description each (`QUERIES`).
`references/ssb_q<f>_<n>.py` hand one description each to the harness.

The comparison is exact: integers, strings, the number of rows and their
order.  Where the statement's `order by` leaves rows tied (Q3.x order by
a revenue that two groups may share), the tied rows are compared as a
set: position by position the ORDER KEYS must be the reference's, and
the rows as a whole the same multiset.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import numpy as np

# fact foreign key -> (dimension, its key)
JOINS = {"dwdate": ("lo_orderdate", "d_datekey"),
         "customer": ("lo_custkey", "c_custkey"),
         "supplier": ("lo_suppkey", "s_suppkey"),
         "part": ("lo_partkey", "p_partkey")}


@dataclass(frozen=True)
class Query:
    dims: dict[str, Callable | None]      # dimension -> filter on its rows
    measure: Callable                     # fact columns -> int64 a row
    fact: Callable | None = None          # fact-side filter
    group: tuple[tuple[str, str], ...] = ()  # (dimension, attribute)
    # output columns in the select list's order: a group attribute's
    # name, or "sum"
    select: tuple[str, ...] = ("sum",)
    # order by: (output column, descending)
    order: tuple[tuple[str, bool], ...] = ()


def _i64(col):
    return col.astype(np.int64)


def _eq(col: str, *values):
    return lambda t: np.isin(t[col], values)


def _years(lo: int, hi: int):
    return lambda d: (d["d_year"] >= lo) & (d["d_year"] <= hi)


def _discounted(lo):
    return _i64(lo["lo_extendedprice"]) * lo["lo_discount"]


def _revenue(lo):
    return _i64(lo["lo_revenue"])


def _profit(lo):
    return _i64(lo["lo_revenue"]) - lo["lo_supplycost"]


def _flight1(date_filter, disc: tuple[int, int], qty: tuple[int, int]):
    return Query(
        dims={"dwdate": date_filter}, measure=_discounted,
        fact=lambda lo: (lo["lo_discount"] >= disc[0])
        & (lo["lo_discount"] <= disc[1]) & (lo["lo_quantity"] >= qty[0])
        & (lo["lo_quantity"] <= qty[1]))


def _flight2(part_filter, region: str):
    return Query(
        dims={"dwdate": None, "part": part_filter,
              "supplier": _eq("s_region", region)},
        measure=_revenue, group=(("dwdate", "d_year"), ("part", "p_brand1")),
        select=("sum", "d_year", "p_brand1"),
        order=(("d_year", False), ("p_brand1", False)))


def _flight3(level: str, cust, supp, date_filter):
    return Query(
        dims={"customer": cust, "supplier": supp, "dwdate": date_filter},
        measure=_revenue,
        group=(("customer", "c_" + level), ("supplier", "s_" + level),
               ("dwdate", "d_year")),
        select=("c_" + level, "s_" + level, "d_year", "sum"),
        order=(("d_year", False), ("sum", True)))


def _flight4(dims: dict, group: tuple):
    names = tuple(c for _, c in group)
    return Query(dims=dims, measure=_profit, group=group,
                 select=names + ("sum",),
                 order=tuple((c, False) for c in names))


_KI = ("UNITED KI1", "UNITED KI5")
_MFGR12 = _eq("p_mfgr", "MFGR#1", "MFGR#2")
_Y9798 = _eq("d_year", 1997, 1998)

QUERIES: dict[str, Query] = {
    "q1_1": _flight1(_eq("d_year", 1993), (1, 3), (1, 24)),
    "q1_2": _flight1(_eq("d_yearmonthnum", 199401), (4, 6), (26, 35)),
    "q1_3": _flight1(lambda d: (d["d_weeknuminyear"] == 6)
                     & (d["d_year"] == 1994), (5, 7), (26, 35)),
    "q2_1": _flight2(_eq("p_category", "MFGR#12"), "AMERICA"),
    "q2_2": _flight2(lambda p: (p["p_brand1"] >= "MFGR#2221")
                     & (p["p_brand1"] <= "MFGR#2228"), "ASIA"),
    "q2_3": _flight2(_eq("p_brand1", "MFGR#2221"), "EUROPE"),
    "q3_1": _flight3("nation", _eq("c_region", "ASIA"),
                     _eq("s_region", "ASIA"), _years(1992, 1997)),
    "q3_2": _flight3("city", _eq("c_nation", "UNITED STATES"),
                     _eq("s_nation", "UNITED STATES"), _years(1992, 1997)),
    "q3_3": _flight3("city", _eq("c_city", *_KI), _eq("s_city", *_KI),
                     _years(1992, 1997)),
    "q3_4": _flight3("city", _eq("c_city", *_KI), _eq("s_city", *_KI),
                     _eq("d_yearmonth", "Dec1997")),
    "q4_1": _flight4(
        {"dwdate": None, "customer": _eq("c_region", "AMERICA"),
         "supplier": _eq("s_region", "AMERICA"), "part": _MFGR12},
        (("dwdate", "d_year"), ("customer", "c_nation"))),
    "q4_2": _flight4(
        {"dwdate": _Y9798, "customer": _eq("c_region", "AMERICA"),
         "supplier": _eq("s_region", "AMERICA"), "part": _MFGR12},
        (("dwdate", "d_year"), ("supplier", "s_nation"),
         ("part", "p_category"))),
    "q4_3": _flight4(
        {"dwdate": _Y9798, "customer": _eq("c_region", "AMERICA"),
         "supplier": _eq("s_nation", "UNITED STATES"),
         "part": _eq("p_category", "MFGR#14")},
        (("dwdate", "d_year"), ("supplier", "s_city"), ("part", "p_brand1"))),
}


def _lookup(dim_key: np.ndarray, fk: np.ndarray):
    """(row of the dimension for each fact row, found)."""
    order = np.argsort(dim_key, kind="stable")
    pos = np.minimum(np.searchsorted(dim_key[order], fk), len(order) - 1)
    row = order[pos]
    return row, dim_key[row] == fk


def answer(q: Query, data: dict) -> dict[str, np.ndarray]:
    """The statement's rows as arrays, one an output column (`c<i>`,
    strings as numpy unicode), in the statement's order; `order_cols`
    the output positions the order is by.  A statement without GROUP BY
    gives one row, `c0` empty when no fact row qualifies (SQL's NULL)."""
    lo = data["lineorder"]
    keep = np.ones(len(lo["lo_orderkey"]), dtype=bool) if q.fact is None \
        else q.fact(lo)
    rows = {}
    for table, pred in q.dims.items():
        fk, key = JOINS[table]
        dim = data[table]
        rows[table], found = _lookup(dim[key], lo[fk])
        keep &= found
        if pred is not None:
            keep &= pred(dim)[rows[table]]
    idx = np.flatnonzero(keep)
    value = q.measure(lo)[idx]
    if not q.group:
        return {"c0": value.sum(keepdims=True) if len(idx)
                else np.zeros(0, np.int64),
                "order_cols": np.zeros(0, np.int64)}
    # group: code each attribute, then the tuples of codes
    attrs, codes = [], np.zeros(len(idx), dtype=np.int64)
    for table, col in q.group:
        uniq, inv = np.unique(data[table][col][rows[table][idx]].astype(
            str if data[table][col].dtype == object else np.int64),
            return_inverse=True)
        attrs.append(uniq)
        codes = codes * len(uniq) + inv
    groups, inv = np.unique(codes, return_inverse=True)
    sums = np.zeros(len(groups), dtype=np.int64)
    np.add.at(sums, inv, value)
    cols = {}
    for (_table, col), uniq in zip(reversed(q.group), reversed(attrs)):
        cols[col] = uniq[groups % len(uniq)]
        groups = groups // len(uniq)
    cols["sum"] = sums
    # order by: the last key first, each pass stable
    perm = np.arange(len(sums))
    for col, desc in reversed(q.order):
        key = cols[col][perm]
        if desc:
            key = -key  # only ever the sum
        perm = perm[np.argsort(key, kind="stable")]
    out = {f"c{i}": cols[name][perm] for i, name in enumerate(q.select)}
    out["order_cols"] = np.array([q.select.index(c) for c, _ in q.order],
                                 dtype=np.int64)
    return out


def _plain(value, want_kind: str):
    """An engine value as the reference holds it, or the value itself
    when its type is not the column's (a float where an integer sum is
    due compares unequal)."""
    if want_kind == "U":
        return str(value) if isinstance(value, str) else value
    if isinstance(value, (int, np.integer)) and not isinstance(value, bool):
        return int(value)
    return value


def compare(rows: list[tuple], ref: dict, tol: float = 0.0):
    """(mismatches, 0.0): exact, ties within the order compared as sets."""
    ncol = len(ref) - 1
    cols = [ref[f"c{i}"] for i in range(ncol)]
    want = list(zip(*(c.tolist() for c in cols)))
    if ncol == 1 and not want:  # no GROUP BY and no qualifying row
        want = [(None,)]
    if len(rows) != len(want):
        return [f"{len(rows)} rows, the reference has {len(want)}"], 0.0
    kinds = [c.dtype.kind for c in cols]
    got = [tuple(_plain(v, k) for v, k in zip(r, kinds)) for r in rows]
    bad = []
    by = ref["order_cols"].tolist()
    for i, (g, w) in enumerate(zip(got, want)):
        if len(g) != ncol:
            bad.append(f"row {i} has {len(g)} columns, not {ncol}")
        elif [g[c] for c in by] != [w[c] for c in by]:
            bad.append(f"row {i}: {g} where the order puts {w}")
    if not bad and sorted(map(repr, got)) != sorted(map(repr, want)):
        missing = set(want) - set(got)
        bad.append(f"rows differ: {len(missing)} of the reference's are "
                   f"not there, e.g. {sorted(missing)[:2]}; got e.g. "
                   f"{sorted(set(got) - set(want), key=repr)[:2]}")
    return bad, 0.0


def tolerance(row_counts: dict) -> float:
    return 0.0


def reference_for(name: str):
    """(build, compare, tolerance) of one statement, for its module."""
    q = QUERIES[name]
    return (lambda data: answer(q, data)), compare, tolerance
