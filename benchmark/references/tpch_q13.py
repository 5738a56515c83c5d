"""Plain reference for `statements/tpch_q13.sql` (TPC-H Q13, "Customer
Distribution", validation parameters WORD1 = special, WORD2 = requests):
numpy and `re` over the benchmark's own generated arrays, nothing of
`citus_tpu`.

Compared exactly, row for row and in the statement's order: every value
is an integer count, and `c_count` is unique in the answer, so `custdist
desc, c_count desc` is a total order and there is one right answer.  The
comparison is tight without any tolerance to choose: the hot customer's
count (about 118,800 at SF1, z = 1) does not fit 16 bits, so a count
narrowed anywhere on the way shows; and an order that is lost, doubled
or routed to a chip where its customer is not moves that customer from
one `c_count` to another — two rows of the answer.  A customer the outer
join drops leaves the `c_count` = 0 row short.
"""

from __future__ import annotations

import re

import numpy as np

_SPECIAL = re.compile(r"special.*requests", re.S)  # LIKE's % spans lines


def tolerance(row_counts: dict) -> float:
    return 0.0


def build(data: dict) -> dict[str, np.ndarray]:
    cust, orders = data["customer"], data["orders"]
    comments = orders["o_comment"]
    match = np.fromiter((_SPECIAL.search(t) is not None for t in comments),
                        dtype=bool, count=len(comments))
    keep = ~match
    n_keys = int(cust["c_custkey"].max()) + 1
    per_key = np.bincount(orders["o_custkey"][keep], minlength=n_keys)
    per_customer = per_key[cust["c_custkey"]]  # customers without orders: 0
    custdist = np.bincount(per_customer)
    c_count = np.flatnonzero(custdist)
    custdist = custdist[c_count]
    order = np.lexsort((-c_count, -custdist))
    return {"c_count": c_count[order].astype(np.int64),
            "custdist": custdist[order].astype(np.int64),
            "orders_kept": np.array([int(keep.sum())], dtype=np.int64),
            "customers": np.array([len(per_customer)], dtype=np.int64)}


def compare(rows: list[tuple], ref: dict, tol: float):
    """Returns (mismatches, None): there is no error to report beside
    them, every value is exact."""
    want = list(zip(ref["c_count"].tolist(), ref["custdist"].tolist()))
    bad = []
    if len(rows) != len(want):
        bad.append(f"q13 returned {len(rows)} rows, reference has "
                   f"{len(want)}")
    for i, (got, exp) in enumerate(zip(rows, want)):
        if tuple(got) != exp:
            bad.append(f"q13 row {i}: {tuple(got)} vs {exp}")
            if len(bad) >= 8:
                break
    return bad, None
