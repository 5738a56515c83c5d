"""The benchmark's TPC-DS catalog deployment (`tpcds-sf10-catalog-1chip`,
cell `tpcds10cs.q57`) on the CPU at a small scale, and what its
statement needs of the engine: windows of several PARTITION BY clauses
in one block, windows that read grouped rows in their ORDER BY order
with no sort, and multi-key joins whose keys pack into one int64.  The
data set module, the plain reference and its comparison, the engine
against the reference and against sqlite on one device and on four,
and the cell through the harness itself (benchmark/run.py `main`; the
platform check and the data directory are overridden from here, never
through an option of the harness, and the configuration is the cell's
own but for its scale factor)."""

import json
import os
import re
import sqlite3
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import citus_tpu  # noqa: E402
from benchmark import run as harness  # noqa: E402
from benchmark.datasets import tpcds, tpcds_catalog as ds  # noqa: E402
from benchmark.references import tpcds_q57 as ref  # noqa: E402
from citus_tpu.errors import PlanningError  # noqa: E402

CELL = "tpcds10cs.q57"
SCALE = 0.01
SEED = 2_147_483_777  # past 32 signed bits, as a benchmark seed may be
NEW_READERS = ("window_sorts", "join_sort_operands",
               "intermediate_string_values")


def _sql() -> str:
    with open(os.path.join(ROOT, "benchmark", "statements",
                           "tpcds_q57.sql")) as f:
        return f.read()


def _v1_text() -> str:
    sql = _sql()
    return sql[sql.index("(") + 1:sql.index("),\nv2 as")]


def _v2_text() -> str:
    sql = _sql()
    body = sql[sql.index("v2 as (") + len("v2 as ("):
               sql.index(")\nselect * from v2")]
    return f"with v1 as ({_v1_text()}) {body}"


@pytest.fixture(scope="module")
def data():
    return ds.generate({"scale_factor": SCALE}, SEED)


# -- the data set ---------------------------------------------------------

def test_data_set_is_deterministic_and_the_seed_moves_only_measures(data):
    again = ds.generate({"scale_factor": SCALE}, SEED)
    other = ds.generate({"scale_factor": SCALE}, 5)
    assert ds.row_counts(data) == ds.row_counts(other) == {
        "date_dim": 73_049, "item": 600, "call_center": 6,
        "catalog_sales": 14_415}

    def same(a, b) -> bool:
        return list(a) == list(b)

    for table, cols in data.items():
        for c, a in cols.items():
            assert same(a, again[table][c]), (table, c)
            if c not in ds.MEASURES:
                assert same(a, other[table][c]), (table, c)
    fact, fact2 = data[ds.FACT], other[ds.FACT]
    assert len(fact) == 34 and len(data["call_center"]) == 31
    for c in ds.MEASURES:
        assert np.array_equal(np.sort(fact[c]), np.sort(fact2[c]))
        assert not np.array_equal(fact[c], fact2[c])
    # every answer moves with the seed, no row of v1 and no count does
    a, b = ref.v1(data), ref.v1(other)
    for k in ref.KEYS + ("rn",):
        assert np.array_equal(a[k], b[k])
    assert not np.allclose(a["sum_sales"], b["sum_sales"])
    assert len(ref.answer(ref.build(data))) == ref.LIMIT


def test_item_and_date_dim_are_the_store_channels_rows(data):
    store = tpcds.generate({"scale_factor": SCALE}, SEED)
    for table in ("item", "date_dim"):
        assert data[table].keys() == store[table].keys()
        for c in data[table]:
            assert list(data[table][c]) == list(store[table][c]), (table, c)


def test_sf10_counts_are_the_configuration_rows():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "tpcds-sf10-catalog-1chip.json")) as f:
        cfg = json.load(f)
    assert ds.table_rows(cfg["dataset_params"]["scale_factor"]) \
        == cfg["rows"]
    widths = ds.column_widths()
    assert widths["cs_sales_price"] == 4 and widths["cc_name"] == 4
    assert len(widths) == 28 + 22 + 31 + 34  # every column of the four


def test_data_set_and_reference_import_nothing_of_the_program():
    code = ("import sys; sys.path.insert(0, %r); "
            "import benchmark.datasets.tpcds_catalog, "
            "benchmark.references.tpcds_q57; "
            "print([m for m in sys.modules if m.startswith('citus_tpu')])"
            % ROOT)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, cwd=ROOT)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"


# -- the reference ----------------------------------------------------------

def test_reference_v2_holds_the_rows_with_both_neighbours(data):
    rows = ref.v1(data)
    got = ref.build(data)
    by_key = {tuple(rows[k][i] for k in ref.KEYS[:3]) + (int(rows["rn"][i]),):
              i for i in range(len(rows["rn"]))}
    assert len(got["sum_sales"]) > ref.LIMIT
    for j in range(len(got["sum_sales"])):
        part = tuple(got[k][j] for k in ref.KEYS[:3])
        i = next(i for i in (by_key.get(part + (r,)) for r in range(1, 15))
                 if i is not None and rows["d_moy"][i] == got["d_moy"][j]
                 and rows["d_year"][i] == got["d_year"][j])
        rn = int(rows["rn"][i])
        assert got["psum"][j] == rows["sum_sales"][by_key[part + (rn - 1,)]]
        assert got["nsum"][j] == rows["sum_sales"][by_key[part + (rn + 1,)]]
        if j > 20:
            break


@pytest.mark.parametrize("precision", ["bfloat16", "float16"])
def test_lower_precision_sums_fail_the_comparison(data, precision):
    """The readings below the configuration's float32: the same groups
    summed in bfloat16 or in float16 are not correct by the comparison
    that decides `correct` — by the limit on a sum or a mean — while the
    float64 answer is."""
    import ml_dtypes

    want = ref.build(data)
    tol = ref.tolerance(ds.row_counts(data))
    bad, err = ref.compare(ref.answer(want), want, tol)
    assert bad == [] and err == 0.0
    dtype = {"bfloat16": ml_dtypes.bfloat16, "float16": np.float16}
    low = ref.v2(ref.v1(data, dtype[precision]))
    bad, err = ref.compare(ref.answer(low), want, tol)
    assert any(" vs " in b for b in bad) and err > tol


def test_comparison_refuses_what_it_should(data):
    want = ref.build(data)
    tol = ref.tolerance(ds.row_counts(data))
    rows = ref.answer(want)
    assert ref.compare(rows[:-1], want, tol)[0]  # a row short
    assert ref.compare(rows[::-1], want, tol)[0]  # out of order
    wrong = [r[:7] + (r[7] * (1 + 10 * tol), r[8]) for r in rows]
    assert ref.compare(wrong, want, tol)[0]      # a neighbour's sum
    assert ref.compare(rows, want, tol)[0] == []


# -- the engine against the reference ----------------------------------------

@pytest.fixture(scope="module", params=[1, 4])
def loaded(request, tmp_path_factory, data):
    d = tmp_path_factory.mktemp(f"tpcds_cs{request.param}")
    s = citus_tpu.connect(data_dir=str(d / "d"), n_devices=request.param,
                          serving_result_cache_bytes=0)
    assert ds.load(s, data, {"shard_count": 8}) == ds.row_counts(data)
    yield s
    s.close()


def test_q57_text_against_the_reference(loaded, data):
    want = ref.build(data)
    tol = ref.tolerance(ds.row_counts(data))
    rows = loaded.execute(_sql()).rows()   # capacity feedback converges
    before = loaded.stats.counters.snapshot()
    rows = loaded.execute(_sql()).rows()
    after = loaded.stats.counters.snapshot()

    def moved(name):
        return after[name] - before[name]

    assert len(rows) == ref.LIMIT
    bad, err = ref.compare(rows, want, tol)
    assert bad == [] and err < tol
    v1_rows = len(ref.v1(data)["rn"])
    assert moved("subplans_executed") == 2       # v1 and v2
    assert moved("window_over_group_total") == 2
    # v1's and v2's three text columns, typed and interned on the host
    assert moved("intermediate_string_values_total") \
        == 3 * moved("intermediate_rows_total")
    assert moved("intermediate_rows_total") > v1_rows
    # two self-joins, each a one-operand sort of its packed key
    assert moved("join_sort_operands_total") == 2
    one = loaded.executor.mesh.devices.size == 1
    # on one device both windows read the groups in order; on four the
    # lower node exchanges by (category, brand, call center) and both sort
    assert moved("window_sorts_total") == (0 if one else 2)
    assert moved("window_presorted_total") == (2 if one else 0)


def test_q57_explain_shows_stacked_windows_and_packed_joins(loaded):
    one = loaded.executor.mesh.devices.size == 1
    v1 = "\n".join(r[0] for r in loaded.execute(
        "explain " + _v1_text()).rows())
    windows = [ln for ln in v1.splitlines() if "WindowAgg" in ln]
    assert len(windows) == 2
    # the fewest partition keys lowest: rank's three under avg's four
    assert "avg(" in windows[0] and "rank(" in windows[1]
    assert "partition by g0, g1, g2, g3" in windows[0]
    assert "partition by g0, g1, g2 order by g3, g4" in windows[1]
    if one:
        assert "groups in partition order and ORDER BY order" in windows[1]
        assert "sorts" not in v1
    else:
        assert "all_to_all partitions, sorts" in windows[1]
        assert "device-local partitions, sorts" in windows[0]
    # the group keys in the windows' order
    assert re.search(r"keys: 0\.i_category, 0\.i_brand, 3\.cc_name, "
                     r"2\.d_year, 2\.d_moy", v1)
    v2 = "\n".join(r[0] for r in loaded.execute(
        "explain " + _v2_text()).rows())
    joins = [ln for ln in v2.splitlines() if " Join on " in ln]
    assert len(joins) == 2
    assert all("packed key" in ln and "fused lookup" in ln for ln in joins)


def test_q57_windows_sort_only_on_a_mesh(loaded):
    """On one device the groups reach both windows in order and nothing
    under `ct.window` sorts; on four the lower window's exchange
    scatters them and both windows sort."""
    loaded.execute(_sql()).rows()
    texts = [e[0].as_text()
             for e in loaded.executor.plan_cache._entries.values()]
    inner = [t for t in texts if "ct.window" in t]
    assert inner
    for text in inner:
        assert "f64[" not in text
        sorts = [ln for ln in text.splitlines()
                 if re.search(r"\bsort\(", ln) and "ct.window" in ln]
        assert bool(sorts) == (loaded.executor.mesh.devices.size > 1)


# -- windows against sqlite ---------------------------------------------------

def _rows(rows, digits=6):
    out = [tuple(None if x is None else
                 round(float(x), digits) if not isinstance(x, str)
                 else x for x in r) for r in rows]
    return sorted(out, key=lambda r: [(v is None, 0 if v is None else v)
                                      for v in r])


def _pin_group_by(mp, bucketed):
    """Pin the planner's pick past the flat grid: the bucketed grid
    wherever a group-by's keys pack, or the sort path everywhere."""
    import citus_tpu.ops.groupby as G

    mp.setattr(G, "group_bucket_eligible", lambda total, rows: bucketed)


@pytest.fixture(scope="module", params=[1, 4])
def grouped_session(request, tmp_path_factory):
    d = tmp_path_factory.mktemp(f"q57win{request.param}")
    s = citus_tpu.connect(data_dir=str(d / "d"), n_devices=request.param,
                          compute_dtype="float64",
                          serving_result_cache_bytes=0)
    con = sqlite3.connect(":memory:")
    rows = [(i, i % 4, (i * 7) % 50, (i * 11) % 60,
             None if i % 13 == 0 else (i * 3) % 9, float((i * 17) % 101),
             float(i % 7) / 4)
            for i in range(1, 4001)]
    s.execute("create table t (k bigint, a bigint, b bigint, c bigint, "
              "n bigint, f double precision, x double precision)")
    s.create_distributed_table("t", "k", shard_count=8)
    s.execute("insert into t values " + ",".join(
        "(" + ",".join("null" if v is None else str(v) for v in r) + ")"
        for r in rows))
    con.execute("create table t (k, a, b, c, n, f, x)")
    con.executemany("insert into t values (?,?,?,?,?,?,?)", rows)
    yield s, con
    s.close()


@pytest.fixture()
def grouped(grouped_session, monkeypatch):
    """`t` groups by (a, b, c) into 4 x 50 x 60 slots, past the flat
    grid: the sort path, whose groups leave in packed-key order, as
    Q57's group-by takes it at SF10 (pinned: at this size the planner
    would take the bucketed grid).  `n` is a nullable order key."""
    _pin_group_by(monkeypatch, False)
    return grouped_session


def _check(grouped, sql, lite=None):
    s, con = grouped
    got = _rows(s.execute(sql).rows())
    want = _rows(con.execute(lite or sql).fetchall())
    assert got == want, f"{sql}\n{got[:5]} vs {want[:5]}"


def _moved(s, sql, *names):
    before = s.stats.counters.snapshot()
    s.execute(sql).rows()
    after = s.stats.counters.snapshot()
    return [after[n] - before[n] for n in names]


PRESORTED = {
    "ranks_with_ties": "select a, b, c, rank() over (partition by a "
                       "order by b), dense_rank() over (partition by a "
                       "order by b) from t group by a, b, c",
    "row_number": "select a, b, c, row_number() over (partition by a "
                  "order by b, c) from t group by a, b, c",
    "running_sum_of_sums": "select a, b, c, sum(sum(f)) over (partition "
                           "by a order by b) from t group by a, b, c",
    "having_inside_partitions": "select a, b, c, count(*), rank() over "
                                "(partition by a order by b), row_number() "
                                "over (partition by a order by b, c), "
                                "sum(count(*)) over (partition by a "
                                "order by b) from t group by a, b, c "
                                "having sum(f) > 60",
    "two_partition_clauses": "select a, b, c, sum(f), avg(sum(f)) over "
                             "(partition by a, b), rank() over (partition "
                             "by a order by b, c) from t group by a, b, c",
}


@pytest.mark.parametrize("name", sorted(PRESORTED))
def test_window_over_groups_in_order_against_sqlite(grouped, name):
    s, _ = grouped
    _check(grouped, PRESORTED[name])
    sorts, presorted = _moved(s, PRESORTED[name], "window_sorts_total",
                              "window_presorted_total")
    if s.executor.mesh.devices.size == 1:
        assert sorts == 0 and presorted >= 1
    else:
        assert presorted == 0 and sorts >= 1


def test_nullable_order_key_reads_nulls_last(grouped):
    """A nullable ORDER BY key's groups pack with their NULL lane last,
    as PostgreSQL's ASC orders (sqlite orders NULLs first: told to
    order them last)."""
    s, _ = grouped
    sql = ("select a, n, c, b, rank() over (partition by a order by n), "
           "count(*) over (partition by a order by n) from t "
           "group by a, n, c, b")
    lite = sql.replace("order by n)", "order by n nulls last)")
    _check(grouped, sql, lite)
    sorts, = _moved(s, sql, "window_sorts_total")
    assert sorts == (0 if s.executor.mesh.devices.size == 1 else 1)


@pytest.mark.parametrize("kernel", ["sort", "bucketed"])
def test_null_order_key_after_its_partitions_only_value(grouped, kernel,
                                                        monkeypatch):
    """A partition whose nullable ORDER BY key holds only its smallest
    value and NULL: the NULL groups' value lane holds filler equal to
    that value (the raw lane on the sort path, the range's base on the
    bucketed grid), and they must still open a peer group of their
    own."""
    s, _ = grouped
    con = sqlite3.connect(":memory:")
    rows = [(i, i % 3, (None if i % 5 == 0 else 0) if i % 3 == 0
             else None if i % 13 == 0 else (i * 3) % 9, i % 1000)
            for i in range(1, 3001)]
    s.execute("create table tn (k bigint, a bigint, n bigint, c bigint)")
    try:
        s.execute("insert into tn values " + ",".join(
            "(" + ",".join("null" if v is None else str(v) for v in r) + ")"
            for r in rows))
        con.execute("create table tn (k, a, n, c)")
        con.executemany("insert into tn values (?,?,?,?)", rows)
        _pin_group_by(monkeypatch, kernel == "bucketed")
        sql = ("select a, n, c, rank() over (partition by a order by n), "
               "dense_rank() over (partition by a order by n), "
               "sum(count(*)) over (partition by a order by n) from tn "
               "group by a, n, c")
        lite = sql.replace("order by n)", "order by n nulls last)")
        _check((s, con), sql, lite)
        sorts, bucketed = _moved(s, sql, "window_sorts_total",
                                 "groupby_bucketed_total")
        assert sorts == (0 if s.executor.mesh.devices.size == 1 else 1)
        assert bucketed == (kernel == "bucketed")
    finally:
        s.execute("drop table tn")


def test_stale_range_of_an_order_key_sorts_and_answers(grouped):
    """A group key whose range went stale packs rows out of range: they
    are counted (dense_oob), the host runs the statement again without
    packing, and there the window sorts — the groups no longer leave in
    its ORDER BY order — and still answers as sqlite does."""
    from citus_tpu.planner.plan import AggregateNode, WindowNode
    from citus_tpu.sql.parser import parse_one

    s, con = grouped
    sql = PRESORTED["ranks_with_ties"]
    plan, _cleanup = s._plan_select(parse_one(sql))
    win = plan.root
    assert isinstance(win, WindowNode)
    agg = win.input
    assert isinstance(agg, AggregateNode)
    assert win.presorted == (s.executor.mesh.devices.size == 1)
    if win.presorted:
        assert [c for g, c in agg.group_keys][:2] == ["g0", "g1"]
        assert agg.nulls_last == (1,)
    # `b` spans 0..49: claim 0..24 (the sort path is pinned, so the
    # range lives in `key_ranges` alone)
    assert agg.bucket_keys is None
    base, extent, has_null = agg.key_ranges[1]
    agg.key_ranges = (agg.key_ranges[0], (base, extent // 2, has_null),
                      *agg.key_ranges[2:])
    result = s.executor.execute_plan(plan)
    assert result.retries >= 1
    got = _rows(tuple(r[:5]) for r in result.rows())
    assert got == _rows(con.execute(sql).fetchall())


NOT_PRESORTED = {
    "order_by_an_aggregate": "select a, b, c, rank() over (partition by a "
                             "order by sum(f)) from t group by a, b, c",
    "descending": "select a, b, c, rank() over (partition by a order by "
                  "b desc) from t group by a, b, c",
    "a_float_key": "select a, x, c, dense_rank() over (partition by a "
                   "order by x) from t group by a, x, c",
}


@pytest.mark.parametrize("name", sorted(NOT_PRESORTED))
def test_window_that_must_sort_still_sorts_and_is_right(grouped, name):
    s, _ = grouped
    _check(grouped, NOT_PRESORTED[name])
    sorts, presorted = _moved(s, NOT_PRESORTED[name], "window_sorts_total",
                              "window_presorted_total")
    assert sorts == 1 and presorted == 0


def test_two_partition_clauses_over_rows(grouped):
    """Windows of two PARTITION BY clauses over ungrouped rows: two
    stacked nodes, the second reading the first's columns through."""
    _check(grouped, "select k, row_number() over (partition by a order by "
                    "k), sum(f) over (partition by b), count(*) over "
                    "(partition by a, b order by k) from t")


def test_still_refused(grouped):
    s, _ = grouped
    with pytest.raises(PlanningError, match="DISTINCT"):
        s.execute("select distinct a, rank() over (partition by a "
                  "order by b) from t")
    s.execute("create table ts (k bigint, name text)")
    try:
        with pytest.raises(PlanningError, match="string"):
            s.execute("select k, rank() over (order by name) from ts")
    finally:
        s.execute("drop table ts")


# -- Q47 over the store channel's rows, against sqlite ------------------------

Q47 = """with v1 as (
  select i_category, i_brand, s_store_name, s_company_name, d_year, d_moy,
         sum(ss_sales_price) sum_sales,
         avg(sum(ss_sales_price)) over
           (partition by i_category, i_brand, s_store_name, s_company_name,
                         d_year) avg_monthly_sales,
         rank() over
           (partition by i_category, i_brand, s_store_name, s_company_name
            order by d_year, d_moy) rn
  from item, store_sales, date_dim, store
  where ss_item_sk = i_item_sk and ss_sold_date_sk = d_date_sk
    and ss_store_sk = s_store_sk
    and (d_year = 1999 or (d_year = 1999-1 and d_moy = 12)
         or (d_year = 1999+1 and d_moy = 1))
  group by i_category, i_brand, s_store_name, s_company_name, d_year, d_moy),
v2 as (
  select v1.i_category, v1.i_brand, v1.s_store_name, v1.s_company_name,
         v1.d_year, v1.d_moy, v1.avg_monthly_sales, v1.sum_sales,
         v1_lag.sum_sales psum, v1_lead.sum_sales nsum
  from v1, v1 v1_lag, v1 v1_lead
  where v1.i_category = v1_lag.i_category
    and v1.i_category = v1_lead.i_category
    and v1.i_brand = v1_lag.i_brand and v1.i_brand = v1_lead.i_brand
    and v1.s_store_name = v1_lag.s_store_name
    and v1.s_store_name = v1_lead.s_store_name
    and v1.s_company_name = v1_lag.s_company_name
    and v1.s_company_name = v1_lead.s_company_name
    and v1.rn = v1_lag.rn + 1 and v1.rn = v1_lead.rn - 1)
select * from v2
where d_year = 1999 and avg_monthly_sales > 0
  and case when avg_monthly_sales > 0
           then abs(sum_sales - avg_monthly_sales) / avg_monthly_sales
           else null end > 0.1
order by sum_sales - avg_monthly_sales, 3
limit 100"""


@pytest.mark.parametrize("n_devices", [1, 4])
def test_q47_text_against_sqlite(tmp_path, n_devices):
    data = tpcds.generate({"scale_factor": SCALE}, SEED)
    s = citus_tpu.connect(data_dir=str(tmp_path / "d"), n_devices=n_devices,
                          compute_dtype="float64",
                          serving_result_cache_bytes=0)
    con = sqlite3.connect(":memory:")
    reads = {"store_sales": ["ss_item_sk", "ss_sold_date_sk",
                             "ss_store_sk", "ss_sales_price"],
             "item": ["i_item_sk", "i_category", "i_brand"],
             "date_dim": ["d_date_sk", "d_year", "d_moy"],
             "store": ["s_store_sk", "s_store_name", "s_company_name"]}
    try:
        assert tpcds.load(s, data, {"shard_count": 8}) \
            == tpcds.row_counts(data)
        for table, cols in reads.items():
            con.execute(f"create table {table} ({', '.join(cols)})")
            con.executemany(
                f"insert into {table} values ({','.join('?' * len(cols))})",
                zip(*[[None if v is None else
                       v.item() if hasattr(v, "item") else v
                       for v in data[table][c]] for c in cols]))
        got = s.execute(Q47).rows()
        want = con.execute(Q47).fetchall()
        assert len(got) == len(want) == 100
        by_key = {tuple(r[:4]) + (int(r[4]), int(r[5])): r[6:] for r in want}
        for r in got:
            values = by_key[tuple(r[:4]) + (int(r[4]), int(r[5]))]
            assert np.allclose(r[6:], values, rtol=1e-9), (r, values)
        # the order: sum - mean ascending
        keys = [r[7] - r[6] for r in got]
        assert keys == sorted(keys)
    finally:
        s.close()


# -- multi-key joins with a packed key ---------------------------------------

def test_packed_bounds_match_the_lexsort_form():
    """`ops.join._bounds` with packed key ranges finds each probe row's
    matches where the lexsort form does: NULL (non-matchable) keys on
    both sides, duplicates on the build side, probe keys out of range;
    a build row outside the ranges (stale statistics) is counted and
    matches nothing."""
    import jax.numpy as jnp

    from citus_tpu.ops.join import (_bounds, build_sort_operands,
                                     packed_sort_fits)

    rng = np.random.default_rng(3)
    m, n = 500, 700
    b = [rng.integers(0, 7, m), rng.integers(-3, 5, m), rng.integers(10, 13, m)]
    p = [rng.integers(0, 8, n), rng.integers(-4, 5, n), rng.integers(9, 13, n)]
    bm = rng.random(m) > 0.1
    pm = rng.random(n) > 0.1
    pack = ((0, 7), (-3, 8), (10, 3))
    ref_order, lo, hi, oob = _bounds([jnp.asarray(k) for k in b],
                                     jnp.asarray(bm),
                                     [jnp.asarray(k) for k in p], None)
    order, plo, phi, poob = _bounds([jnp.asarray(k) for k in b],
                                    jnp.asarray(bm),
                                    [jnp.asarray(k) for k in p], None, pack)
    assert int(oob) == int(poob) == 0
    want = [sorted(np.asarray(ref_order)[int(x):int(y)].tolist())
            for x, y in zip(lo, hi)]
    got = [sorted(np.asarray(order)[int(x):int(y)].tolist())
           for x, y in zip(plo, phi)]
    assert got == want
    assert any(len(w) > 1 for w in want) and any(not w for w in want)
    # a stale range: build keys past the second key's declared range
    stale = ((0, 7), (-3, 6), (10, 3))
    _o, slo, shi, soob = _bounds([jnp.asarray(k) for k in b],
                                 jnp.asarray(bm),
                                 [jnp.asarray(k) for k in p], None, stale)
    outside = bm & ((b[1] < -3) | (b[1] >= 3))
    assert int(soob) == int(outside.sum()) > 0
    for i in range(n):
        for r in np.asarray(_o)[int(slo[i]):int(shi[i])]:
            assert not outside[r]
            assert all(b[j][r] == p[j][i] for j in range(3))
    assert build_sort_operands(3, pack) == 1
    assert build_sort_operands(3, None) == 4
    # a key and a position past 63 bits keep the lexsort
    assert packed_sort_fits(pack, m)
    assert not packed_sort_fits(((0, 1 << 50), (0, 1 << 12)), m)


@pytest.mark.parametrize("n_devices", [1, 4])
def test_multi_key_join_with_nulls_against_sqlite(tmp_path, n_devices):
    s = citus_tpu.connect(data_dir=str(tmp_path / "d"), n_devices=n_devices,
                          serving_result_cache_bytes=0)
    con = sqlite3.connect(":memory:")
    left = [(i, i % 6, None if i % 11 == 0 else i % 9, i * 3)
            for i in range(1, 400)]
    right = [(j, j % 6, None if j % 7 == 0 else (j * 5) % 9, j)
             for j in range(1, 150)]
    try:
        for name, rows in (("l", left), ("r", right)):
            s.execute(f"create table {name} (id bigint, p bigint, "
                      f"q bigint, v bigint)")
            s.create_distributed_table(name, "id", shard_count=8)
            s.execute(f"insert into {name} values " + ",".join(
                "(" + ",".join("null" if v is None else str(v) for v in r)
                + ")" for r in rows))
            con.execute(f"create table {name} (id, p, q, v)")
            con.executemany(f"insert into {name} values (?,?,?,?)", rows)
        sql = ("select l.id, r.id, l.v + r.v from l, r "
               "where l.p = r.p and l.q = r.q + 1")
        plan = "\n".join(r[0] for r in s.execute("explain " + sql).rows())
        assert "packed key" in plan
        before = s.stats.counters.snapshot()
        got = _rows(s.execute(sql).rows())
        after = s.stats.counters.snapshot()
        assert got == _rows(con.execute(sql).fetchall())
        assert after["join_sort_operands_total"] \
            - before["join_sort_operands_total"] == 1
    finally:
        s.close()


@pytest.mark.parametrize("n_devices", [1, 4])
def test_correlated_keys_claim_uniqueness_and_retry_once(tmp_path,
                                                         n_devices):
    """Two keys one of which determines the other: their ndvs multiply
    up to the build side's rows, so the planner claims it unique and
    fuses a lookup.  The duplicates are found at runtime: one retry
    takes the expansion path, at most one more grows its pairs'
    capacity past the estimate (one device's slack may hold them), and
    the sizes are kept, so the next execution retries no more."""
    from citus_tpu.sql.parser import parse_one

    s = citus_tpu.connect(data_dir=str(tmp_path / "d"), n_devices=n_devices,
                          serving_result_cache_bytes=0)
    con = sqlite3.connect(":memory:")
    probe = [(i, i % 100, i % 100 % 3, i) for i in range(1, 1001)]
    build = [(j, j % 100, j % 100 % 3, j * 7) for j in range(1, 301)]
    try:
        for name, rows in (("pa", probe), ("pb", build)):
            s.execute(f"create table {name} (id bigint, k1 bigint, "
                      f"k2 bigint, v bigint)")
            s.create_distributed_table(name, "id", shard_count=8)
            s.execute(f"insert into {name} values " + ",".join(
                "(" + ",".join(str(v) for v in r) + ")" for r in rows))
            con.execute(f"create table {name} (id, k1, k2, v)")
            con.executemany(f"insert into {name} values (?,?,?,?)", rows)
        sql = ("select pa.id, pb.id, pa.v + pb.v from pa, pb "
               "where pa.k1 = pb.k1 and pa.k2 = pb.k2")
        plan = "\n".join(r[0] for r in s.execute("explain " + sql).rows())
        assert "fused lookup" in plan
        qplan, _cleanup = s._plan_select(parse_one(sql))
        result = s.executor.execute_plan(qplan)
        assert 1 <= result.retries <= 2
        want = _rows(con.execute(sql).fetchall())
        assert len(want) == 3000
        assert _rows(result.rows()) == want
        again = s.executor.execute_plan(s._plan_select(parse_one(sql))[0])
        assert again.retries == 0
        assert _rows(again.rows()) == want
    finally:
        s.close()


# -- the cell through the harness --------------------------------------------

@pytest.fixture(scope="module")
def data_root(tmp_path_factory):
    return str(tmp_path_factory.mktemp("benchdata"))


@pytest.mark.parametrize("trace", (0, 1))
def test_cell_runs_through_the_harness(monkeypatch, capsys, data_root,
                                       trace):
    class TinyCell(harness.Cell):
        def __init__(self, workload):
            super().__init__(workload)
            self.config["dataset_params"]["scale_factor"] = SCALE

    monkeypatch.setattr(harness, "Cell", TinyCell)
    monkeypatch.setattr(harness, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(harness, "DATA_ROOT", data_root)
    rc = harness.main(["--workload", CELL, "--seed", str(SEED),
                       "--seconds", "2", "--trace", str(trace)])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert rc == 0
    phases = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert phases["start"]["chips_used"] == 1
    assert phases["data"]["reused_data"] == bool(trace)  # the second run
    assert phases["data"]["rows"] == {"date_dim": 73_049, "item": 600,
                                      "call_center": 6,
                                      "catalog_sales": 14_415}
    window = phases["window"]
    assert window["wrong"] == 0 and window["errors"] == 0
    assert window["compiled_in_window"] == 0
    assert not any(window["programs"].values())
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0
    got = {name: m["value"] for name, m in result["metrics"].items()}
    if trace:
        assert got["window_compiles"] == 0
        assert got["window_sorts"] == 0
        assert got["join_sort_operands"] == 2
        # three text columns of v1 and of v2
        assert got["intermediate_string_values"] > 0
        assert got["intermediate_string_values"] % 3 == 0
    else:
        assert set(got) == {"stmts_per_s", "latency_p50_ms", "setup_s"}


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_is_silent_without_its_source(name):
    """The parent runs the new cell's traced run with these readers laid
    over it: on a program without the counter each returns None and
    does not raise."""
    from types import SimpleNamespace

    cell = SimpleNamespace(config={"n_devices": 1})
    run = SimpleNamespace(window={"counters": {}, "profile": None},
                          records=[{"t1": 0.0, "spans": {"plan": 1.0}}],
                          trace_dir="/nonexistent", cell=cell)
    assert harness.layer_metric(run, name) is None
    run = SimpleNamespace(window={}, records=[], trace_dir="/nonexistent",
                          cell=cell)
    assert harness.layer_metric(run, name) is None


@pytest.mark.parametrize("name,counter,value", [
    ("window_sorts", "window_sorts_total", 0),
    ("join_sort_operands", "join_sort_operands_total", 4),
    ("intermediate_string_values", "intermediate_string_values_total",
     3 * 4798)])
def test_new_reader_reads_a_hand_built_run(name, counter, value):
    from types import SimpleNamespace

    run = SimpleNamespace(
        window={"counters": {counter: 2 * value}, "profile": None},
        records=[{"t1": 0.0}] * 2, trace_dir="/nonexistent",
        cell=SimpleNamespace(config={"n_devices": 1}))
    assert harness.layer_metric(run, name) == value


def test_cell_loads_and_entries_fit():
    cell = harness.Cell(CELL)
    assert cell.chips == 1 and cell.config["n_devices"] == 1
    assert cell.config["dataset"] == "tpcds_catalog"
    assert [st["name"] for st in cell.statements] == ["tpcds_q57"]
    assert {m["name"] for m in cell.metrics("end_to_end")} \
        == {"stmts_per_s", "latency_p50_ms", "setup_s"}
    per_layer = {m["name"]: m for m in cell.metrics("per_layer")}
    for name in NEW_READERS:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["source"] == "program_counter"
    assert per_layer["intermediate_string_values"]["layer"] \
        == "recursive planning and intermediate results"
    entry = next(c for c in cell.bench["configs"]
                 if c["name"] == "tpcds-sf10-catalog-1chip")
    assert entry["source"] == cell.config["source"]
    assert 1 <= len(entry["source"]) <= 200
    assert set(entry["reduced"]) == set(cell.config["reduced_why"])
    assert len(cell.entry["why"]) <= 200
    assert sum(w["chips"] == 4 for w in cell.bench["workloads"]) == 2
