"""Plain reference for `statements/tpcds_q57.sql` (TPC-DS query 57 with
YEAR = 1999): numpy over the benchmark's own generated arrays, in
float64, with nothing of `citus_tpu`.  The joins are index lookups
(every surrogate key is its row number, the date's its Julian day);
then `v1`: the five-key group-by, the mean of the monthly sums over each
(category, brand, call center, year) and each month's rank in its
(category, brand, call center) by year and month; `v2`: each row of
`v1` beside the rows ranked one before and one after it; the filter,
the order and the limit.

It keeps EVERY row of `v2` in the year the statement selects, not the
hundred the statement returns: the comparison needs the rows beside the
cut to judge near-ties, and the rows beside the 10 % threshold to judge
the filter."""

from __future__ import annotations

import numpy as np

from .common import close, rel_err

YEAR = 1999
THRESHOLD = 0.1
LIMIT = 100
KEYS = ("i_category", "i_brand", "cc_name", "d_year", "d_moy")
VALUES = ("avg_monthly_sales", "sum_sales", "psum", "nsum")

# The limit on a returned sum's, neighbour's or partition mean's
# relative error: TPC-DS Q89's (`tpcds_q89.REL_TOL`), set there from
# two readings and read again for this statement at SF10 (PERF.md §4):
# the engine keeps DECIMAL columns, the group sums and the window's mean
# in float32 (`compute_dtype`); the same groups summed in float16, the
# nearest precision below, and in bfloat16 are refused on every seed.
REL_TOL = 1e-4


def tolerance(row_counts: dict) -> float:
    """`REL_TOL` at every scale: a group's rows grow with the scale,
    the engine's error with their logarithm (a tree-ordered segmented
    scan) and a sequential 16-bit sum's with their square root."""
    return REL_TOL


def _sequential_sums(gid: np.ndarray, price: np.ndarray, n_groups: int,
                     acc_dtype) -> np.ndarray:
    """Each group's sum accumulated in `acc_dtype` one row after the
    other, in row order: one vector step a row position, over all the
    groups at once."""
    order = np.argsort(gid, kind="stable")
    g = gid[order]
    p = price[order].astype(acc_dtype)
    start = np.searchsorted(g, np.arange(n_groups))
    pos = np.arange(len(g)) - start[g]
    sums = np.zeros(n_groups, dtype=acc_dtype)
    for j in range(int(pos.max()) + 1 if len(pos) else 0):
        at = pos == j
        sums[g[at]] = (sums[g[at]] + p[at]).astype(acc_dtype)
    return sums.astype(np.float64)


def v1(data: dict, acc_dtype=np.float64) -> dict[str, np.ndarray]:
    """Every row of `v1`, in (category, brand, call center, year, month)
    order, with its monthly sum, its (category, brand, call center,
    year) mean of monthly sums and its rank in (category, brand, call
    center) by year and month; the sums taken in `acc_dtype` (float64
    for the reference; a lower precision shows what the comparison
    refuses)."""
    cs, item, cc, dd = (data["catalog_sales"], data["item"],
                        data["call_center"], data["date_dim"])
    d_idx = cs["cs_sold_date_sk"] - dd["d_date_sk"][0]
    year = dd["d_year"][d_idx].astype(np.int64)
    moy = dd["d_moy"][d_idx].astype(np.int64)
    keep = ((year == YEAR) | ((year == YEAR - 1) & (moy == 12))
            | ((year == YEAR + 1) & (moy == 1)))
    i_idx = cs["cs_item_sk"][keep] - 1
    c_idx = cs["cs_call_center_sk"][keep] - 1
    price = cs["cs_sales_price"][keep]
    # dictionary codes of the three text keys, from the dimensions
    dims = [(item["i_category"], i_idx), (item["i_brand"], i_idx),
            (cc["cc_name"], c_idx)]
    values, codes = [], []
    for col, idx in dims:
        vals, inv = np.unique(np.asarray(col).astype(str),
                              return_inverse=True)
        values.append(vals)
        codes.append(inv[idx])
    month = (year[keep] - (YEAR - 1)) * 12 + moy[keep] - 1  # 0 … 35
    key = np.zeros(len(month), dtype=np.int64)
    for vals, code in zip(values, codes):
        key = key * len(vals) + code
    part = key
    key = key * 36 + month
    uniq, first, gid = np.unique(key, return_index=True, return_inverse=True)
    if acc_dtype == np.float64:
        sums = np.bincount(gid, weights=price, minlength=len(uniq))
    else:
        sums = _sequential_sums(gid, price, len(uniq), acc_dtype)
    out = {name: vals[code[first]]
           for name, vals, code in zip(KEYS, values, codes)}
    out["d_year"] = year[keep][first]
    out["d_moy"] = moy[keep][first]
    out["sum_sales"] = sums
    # the windows: groups are in (partition, year, month) order
    p_of = part[first]
    new_part = np.concatenate([[True], p_of[1:] != p_of[:-1]])
    part_start = np.maximum.accumulate(
        np.where(new_part, np.arange(len(uniq)), 0))
    out["rn"] = np.arange(len(uniq)) - part_start + 1
    _, ypid = np.unique(p_of * 3 + (out["d_year"] - (YEAR - 1)),
                        return_inverse=True)
    out["avg_monthly_sales"] = (np.bincount(ypid, weights=sums)
                                / np.bincount(ypid))[ypid]
    out["group_rows"] = np.bincount(gid, minlength=len(uniq))
    out["part_id"] = np.cumsum(new_part) - 1
    return out


def v2(rows: dict) -> dict[str, np.ndarray]:
    """The rows of `v1` in the statement's year that have a row ranked
    one before (`psum`) and one after (`nsum`) in their partition."""
    pid, rn = rows["part_id"], rows["rn"]
    n = len(rn)
    i = np.flatnonzero(rows["d_year"] == YEAR)
    i = i[(i > 0) & (i < n - 1)]
    i = i[(pid[i - 1] == pid[i]) & (pid[i + 1] == pid[i])
          & (rn[i - 1] == rn[i] - 1) & (rn[i + 1] == rn[i] + 1)]
    out = {k: rows[k][i] for k in KEYS}
    out["avg_monthly_sales"] = rows["avg_monthly_sales"][i]
    out["sum_sales"] = rows["sum_sales"][i]
    out["psum"] = rows["sum_sales"][i - 1]
    out["nsum"] = rows["sum_sales"][i + 1]
    return out


def build(data: dict) -> dict[str, np.ndarray]:
    return v2(v1(data))


def answer(ref: dict) -> list[tuple]:
    """The statement's rows from `build`'s answer (in float64, or in a
    lower precision): the filter, `order by sum_sales -
    avg_monthly_sales, cc_name`, the first hundred."""
    s, a = ref["sum_sales"], ref["avg_monthly_sales"]
    with np.errstate(divide="ignore", invalid="ignore"):
        ratio = np.where(a > 0, np.abs(s - a) / a, -np.inf)
    idx = np.flatnonzero(ratio > THRESHOLD)
    idx = idx[np.lexsort((ref["cc_name"][idx], (s - a)[idx]))][:LIMIT]
    return [tuple(ref[k][i] for k in KEYS)
            + tuple(float(ref[v][i]) for v in VALUES) for i in idx]


def _key(r) -> tuple:
    return (str(r[0]), str(r[1]), str(r[2]), int(r[3]), int(r[4]))


def compare(rows: list[tuple], ref: dict, tol: float):
    """The engine's hundred rows are right when each is a row of the
    reference's `v2` whose mean, sum and two neighbours' sums agree to
    `tol`, whose ratio passes the filter, in `sum - mean` order, and no
    row left out belongs before the last one kept — each up to the
    tolerance:

    * a row whose ratio |sum - mean| / mean lies within 4·tol of 0.1
      may be in or out: the ratio's numerator and denominator each
      carry up to tol of their magnitude, and the mean is the larger;
    * rows whose ORDER BY key `sum - mean` ties within
      tol · (|sum| + |mean|) compare as a set, at the hundredth place
      too, and their `cc_name` order is not checked.

    Returns (mismatches, largest relative error of a returned value)."""
    s, a = ref["sum_sales"], ref["avg_monthly_sales"]
    with np.errstate(divide="ignore", invalid="ignore"):
        ratio = np.where(a > 0, np.abs(s - a) / a, -np.inf)
    band = 4.0 * tol
    sure_in = (ratio > THRESHOLD + band) & (a > 0)
    maybe = np.abs(ratio - THRESHOLD) <= band
    key = s - a
    slack = tol * (np.abs(s) + np.abs(a))
    index = {_key(tuple(ref[k][i] for k in KEYS)): i for i in range(len(s))}
    bad, err, kept = [], 0.0, []
    if len(rows) > LIMIT:
        return [f"q57 returned {len(rows)} rows, the limit is {LIMIT}"], None
    for r in rows:
        gk = _key(r)
        i = index.get(gk)
        if i is None:
            bad.append(f"q57 row {gk} is not in the reference")
            continue
        kept.append(i)
        for j, v in enumerate(VALUES):
            err = max(err, rel_err(r[5 + j], ref[v][i]))
            if not close(r[5 + j], ref[v][i], tol):
                bad.append(f"q57 row {gk}: {v} {r[5 + j]} vs {ref[v][i]}")
        if not (sure_in[i] or maybe[i]):
            bad.append(f"q57 row {gk} fails the filter: ratio {ratio[i]}")
    if len(set(kept)) != len(kept):
        bad.append("q57 repeats a row")
    for i, j in zip(kept, kept[1:]):
        if key[i] > key[j] + slack[i] + slack[j]:
            bad.append(f"q57 order broken: {key[i]} before {key[j]}")
    if bad:
        return bad, err
    n_sure, n_maybe = int(sure_in.sum()), int(maybe.sum())
    want_lo = min(LIMIT, n_sure)
    want_hi = min(LIMIT, n_sure + n_maybe)
    if not want_lo <= len(rows) <= want_hi:
        bad.append(f"q57 returned {len(rows)} rows, the reference "
                   f"{want_lo}..{want_hi}")
    elif len(rows) == LIMIT:
        # nothing left out belongs before the last row kept
        last = key[kept[-1]] - slack[kept[-1]]
        left = np.ones(len(s), dtype=bool)
        left[kept] = False
        early = np.flatnonzero(left & sure_in & (key + slack < last))
        if len(early):
            i = int(early[0])
            bad.append(f"q57 left out row {i} with key {key[i]} before "
                       f"the last kept {last}")
    else:
        missing = np.flatnonzero(sure_in)
        missing = missing[~np.isin(missing, kept)]
        if len(missing):
            bad.append(f"q57 left out {len(missing)} qualifying rows")
    return bad, err
