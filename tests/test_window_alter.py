"""Window functions + ALTER TABLE (VERDICT round-2 item 8).

Window results cross-check against sqlite (which implements the same
default frame: RANGE UNBOUNDED PRECEDING .. CURRENT ROW with peers)."""

import sqlite3

import pytest

import citus_tpu
from citus_tpu.errors import CatalogError, PlanningError


@pytest.fixture()
def sess(tmp_path):
    s = citus_tpu.connect(data_dir=str(tmp_path / "d"), n_devices=4,
                          compute_dtype="float64")
    s.execute("create table w (k bigint, g bigint, v bigint, "
              "f double precision)")
    s.create_distributed_table("w", "k", shard_count=8)
    rows = [(i, i % 5, (i * 37) % 100, float((i * 13) % 50))
            for i in range(1, 301)]
    rows[10] = (11, 1, rows[11][2], rows[11][3])  # duplicate order values
    s.execute("insert into w values "
              + ",".join(f"({a},{b},{c},{d})" for a, b, c, d in rows))
    con = sqlite3.connect(":memory:")
    con.execute("create table w (k, g, v, f)")
    con.executemany("insert into w values (?,?,?,?)", rows)
    yield s, con
    s.close()


def _check(sess_con, sql, tol=1e-9):
    s, con = sess_con
    got = sorted(tuple(None if x is None else round(float(x), 6)
                       for x in r) for r in s.execute(sql).rows())
    want = sorted(tuple(None if x is None else round(float(x), 6)
                        for x in r) for r in con.execute(sql).fetchall())
    assert got == want, f"{sql}\n{got[:5]} vs {want[:5]}"


def test_row_number_rank_dense_rank(sess):
    _check(sess, "select k, row_number() over (partition by g order by v, k) "
                 "from w")
    _check(sess, "select k, rank() over (partition by g order by v) from w")
    _check(sess, "select k, dense_rank() over (partition by g order by v) "
                 "from w")


def test_running_and_whole_partition_aggregates(sess):
    _check(sess, "select k, sum(v) over (partition by g order by k) from w")
    _check(sess, "select k, sum(v) over (partition by g) from w")
    _check(sess, "select k, count(*) over (partition by g order by v) "
                 "from w")
    _check(sess, "select k, min(f) over (partition by g order by k), "
                 "max(v) over (partition by g order by k) from w")
    _check(sess, "select k, avg(v) over (partition by g) from w", tol=1e-6)


def test_window_desc_and_global_partition(sess):
    _check(sess, "select k, row_number() over (order by v desc, k) from w")
    _check(sess, "select k, sum(v) over (order by k) from w")


def test_window_over_dist_column_partition(sess):
    # partition by the distribution column: device-local, no shuffle
    _check(sess, "select k, count(*) over (partition by k) from w")


def test_window_with_join_and_mixed_select(sess):
    s, con = sess
    s.execute("create table d (g bigint, name bigint)")
    s.execute("select create_reference_table('d')")
    s.execute("insert into d values (0,100),(1,101),(2,102),(3,103),(4,104)")
    con.execute("create table d (g, name)")
    con.executemany("insert into d values (?,?)",
                    [(0, 100), (1, 101), (2, 102), (3, 103), (4, 104)])
    _check(sess, "select k, name, v + row_number() over "
                 "(partition by w.g order by k) from w, d where w.g = d.g")


def test_window_restrictions(sess):
    s, _ = sess
    # legal: one window node a PARTITION BY clause, stacked
    _check(sess, "select k, row_number() over (partition by g order by k), "
                 "row_number() over (partition by v order by k) from w")
    with pytest.raises(PlanningError, match="DISTINCT"):
        s.execute("select distinct g, row_number() over (partition by g "
                  "order by k) from w")
    with pytest.raises(PlanningError, match="OVER"):
        s.execute("select row_number() from w")
    # legal since PR 40: the window runs over the groups
    _check(sess, "select g, sum(count(*)) over (partition by g) "
                 "from w group by g")
    with pytest.raises(PlanningError, match="GROUP BY"):
        s.execute("select g, sum(v) over (partition by k) "
                  "from w group by g")
    with pytest.raises(PlanningError, match="nested"):
        s.execute("select g, sum(sum(v) over ()) over () from w group by g")
    with pytest.raises(PlanningError, match="HAVING"):
        s.execute("select g from w group by g "
                  "having sum(sum(v)) over () > 1")


def test_window_string_keys_rejected(tmp_path):
    """Dictionary codes are insertion-ordered, not lexicographic: ranking
    or min/max over a string column must be a planning error, not a
    silently wrong answer.  PARTITION BY strings (equality only) works."""
    s = citus_tpu.connect(data_dir=str(tmp_path / "ws"), n_devices=4,
                          compute_dtype="float64")
    try:
        s.execute("create table ws (k bigint, name text, v bigint)")
        s.create_distributed_table("ws", "k", shard_count=4)
        s.execute("insert into ws values (1,'zeta',10),(2,'alpha',20),"
                  "(3,'zeta',30),(4,'beta',40)")
        with pytest.raises(PlanningError, match="string"):
            s.execute("select rank() over (order by name) from ws")
        with pytest.raises(PlanningError, match="string"):
            s.execute("select min(name) over (partition by k) from ws")
        # equality-only use of strings is fine
        r = s.execute("select name, sum(v) over (partition by name) "
                      "from ws order by name, sum")
        assert [tuple(x) for x in r.rows()] == [
            ("alpha", 20), ("beta", 40), ("zeta", 40), ("zeta", 40)]
        # count over strings is order-insensitive → allowed
        r = s.execute("select count(name) over (partition by k) from ws")
        assert r.row_count == 4
    finally:
        s.close()


def test_alter_table_add_drop_rename(sess):
    s, _ = sess
    s.execute("alter table w add column extra bigint")
    r = s.execute("select count(*), count(extra) from w")
    assert [int(x) for x in r.rows()[0]] == [300, 0]  # backfilled NULL
    s.execute("insert into w (k, g, v, f, extra) values (1000, 0, 5, 1.0, 7)")
    r2 = s.execute("select count(extra), sum(extra) from w")
    assert [int(x) for x in r2.rows()[0]] == [1, 7]
    # filters over the mixed old/new stripes
    r3 = s.execute("select k from w where extra = 7")
    assert [int(x[0]) for x in r3.rows()] == [1000]

    s.execute("alter table w drop column extra")
    with pytest.raises(Exception):
        s.execute("select extra from w")
    with pytest.raises(CatalogError, match="distribution column"):
        s.execute("alter table w drop column k")

    s.execute("alter table w rename column v to val")
    r4 = s.execute("select sum(val) from w")
    assert int(r4.rows()[0][0]) > 0
    s.execute("insert into w (k, g, val, f) values (1001, 0, 9, 1.0)")
    r5 = s.execute("select sum(val) from w where k = 1001")
    assert int(r5.rows()[0][0]) == 9


def test_alter_rename_distribution_column(tmp_path):
    s = citus_tpu.connect(data_dir=str(tmp_path / "d2"), n_devices=4,
                          compute_dtype="float64")
    s.execute("create table rn (a bigint, b bigint)")
    s.create_distributed_table("rn", "a", shard_count=4)
    s.execute("insert into rn values (1, 10), (2, 20)")
    s.execute("alter table rn rename column a to aa")
    assert s.catalog.table("rn").distribution_column == "aa"
    r = s.execute("select b from rn where aa = 2")
    assert int(r.rows()[0][0]) == 20
    s.execute("insert into rn values (3, 30)")
    assert int(s.execute("select sum(b) from rn").rows()[0][0]) == 60
    s.close()


def test_rename_add_collision_and_null_partitions(tmp_path):
    s = citus_tpu.connect(data_dir=str(tmp_path / "d3"), n_devices=4,
                          compute_dtype="float64")
    s.execute("create table c (k bigint, a bigint, b bigint)")
    s.create_distributed_table("c", "k", shard_count=4)
    s.execute("insert into c values (1, 100, 5), (2, 200, 7), "
              "(3, null, 5), (4, null, 7)")
    # rename a -> b2, then re-add a: must read NULL, not the old data
    s.execute("alter table c rename column a to a2")
    s.execute("alter table c add column a bigint")
    r = s.execute("select k, a, a2 from c order by k limit 2")
    assert [tuple(x) for x in r.rows()] == [(1, None, 100), (2, None, 200)]
    # drop then re-add: old values must not resurrect either
    s.execute("alter table c drop column a2")
    s.execute("alter table c add column a2 bigint")
    r2 = s.execute("select count(a2) from c")
    assert int(r2.rows()[0][0]) == 0
    # NULL expression partitions: all-NULL rows form ONE partition / peer
    r3 = s.execute("select k, count(*) over (partition by a + b) from c "
                   "where k >= 3")
    assert sorted(int(x[1]) for x in r3.rows()) == [2, 2]
    r4 = s.execute("select k, rank() over (order by a + b) from c "
                   "where k >= 3")
    assert sorted(int(x[1]) for x in r4.rows()) == [1, 1]
    # column named 'over' / 'partition' still parses
    s.execute("create table soft (over bigint, partition bigint)")
    s.create_distributed_table("soft", "over", shard_count=2)
    s.execute("insert into soft values (1, 2)")
    assert int(s.execute("select partition from soft where over = 1")
               .rows()[0][0]) == 2
    s.close()


# -- windows over grouped rows (PR 40), against sqlite ------------------------
# every window operand an aggregate, a group key or an expression of those;
# the windows run after GROUP BY and HAVING.  `big` groups by keys whose
# slot space is past the flat grid, so the groups take the sort path (or,
# pinned, the bucketed grid) and reach the window already in partition
# order: the path that skips the window's sort.

OVER_GROUPS = {
    "avg_of_sums": "select g, h, sum(v), avg(sum(v)) over (partition by g) "
                   "from w group by g, h",
    "running_sum_of_sums": "select g, sum(v), sum(sum(v)) over (order by g) "
                           "from w group by g",
    "rank_by_sum": "select g, h, rank() over (partition by g "
                   "order by sum(v)) from w group by g, h",
    "having_first": "select g, h, count(*), sum(count(*)) over "
                    "(partition by h) from w group by g, h "
                    "having sum(v) > 1000",
    "derived_filtered": "select * from (select g, h, sum(f) s, avg(sum(f)) "
                        "over (partition by h) a from w group by g, h) t "
                        "where abs(s - a) > 10",
    "big_presorted": "select k, v, sum(f), avg(sum(f)) over (partition by v),"
                     " count(*) over (partition by v) from big group by k, v",
    "big_having_two_keys": "select k, v, sum(f), max(sum(f)) over "
                           "(partition by v, g) from big group by k, v, g "
                           "having sum(f) > 10",
}


@pytest.fixture(scope="module", params=[1, 4])
def grouped(request, tmp_path_factory):
    d = tmp_path_factory.mktemp(f"grouped{request.param}")
    s = citus_tpu.connect(data_dir=str(d / "d"), n_devices=request.param,
                          compute_dtype="float64",
                          serving_result_cache_bytes=0)
    con = sqlite3.connect(":memory:")
    rows = [(i, i % 5, i % 3, (i * 37) % 100, float((i * 13) % 50))
            for i in range(1, 301)]
    big = [(i, i % 5, (i * 37) % 100, float((i * 13) % 50))
           for i in range(1, 3001)]
    for name, cols, data in (("w", "k, g, h, v, f", rows),
                             ("big", "k, g, v, f", big)):
        typed = ", ".join(f"{c} bigint" for c in cols.split(", ")[:-1])
        s.execute(f"create table {name} ({typed}, f double precision)")
        s.create_distributed_table(name, "k", shard_count=8)
        s.execute(f"insert into {name} values "
                  + ",".join(str(r) for r in data))
        con.execute(f"create table {name} ({cols})")
        con.executemany(f"insert into {name} values "
                        f"({','.join('?' * len(data[0]))})", data)
    yield s, con
    s.close()


@pytest.mark.parametrize("name", sorted(OVER_GROUPS))
def test_window_over_groups(grouped, name):
    _check(grouped, OVER_GROUPS[name])


def test_window_over_groups_bucketed_grid(grouped, monkeypatch):
    """`big`'s groups on the bucketed grid (pinned: its 3,000 rows
    occupy too little of the 3,001 x 101 slots for the planner to take
    it) reach the window as the sort path's do."""
    import citus_tpu.ops.groupby as G

    s, _ = grouped
    monkeypatch.setattr(G, "group_bucket_eligible", lambda total, rows: True)
    _check(grouped, OVER_GROUPS["big_presorted"])
    before = s.stats.counters.snapshot()["groupby_bucketed_total"]
    s.execute(OVER_GROUPS["big_presorted"]).rows()
    assert s.stats.counters.snapshot()["groupby_bucketed_total"] \
        == before + 1


def test_window_over_groups_plan_and_counters(grouped):
    """The window stage sits above the aggregate in the one program; on
    one device the groups reach it in partition order (the partition
    key leads the group keys), it runs no sort of its own, and the
    counters see it."""
    s, _ = grouped
    sql = OVER_GROUPS["big_presorted"]
    plan = "\n".join(r[0] for r in s.execute("explain " + sql).rows())
    assert "WindowAgg" in plan and "GroupAggregate" in plan
    assert plan.index("WindowAgg") < plan.index("GroupAggregate")
    before = s.stats.counters.snapshot()
    s.execute(sql).rows()
    after = s.stats.counters.snapshot()
    assert after["window_over_group_total"] \
        - before["window_over_group_total"] == 1
    assert after["window_rows_total"] - before["window_rows_total"] > 0
    from citus_tpu.planner.plan import AggregateNode, WindowNode

    node, _cleanup = s._plan_select(__import__(
        "citus_tpu.sql.parser", fromlist=["parse_one"]).parse_one(sql))
    win = node.root
    assert isinstance(win, WindowNode) and isinstance(win.input,
                                                      AggregateNode)
    n_dev = s.executor.mesh.devices.size
    assert win.presorted == (n_dev == 1)
    if n_dev == 1:
        # the partition key (v) leads the group keys
        assert win.input.group_keys[0][0].column == "v"


@pytest.mark.parametrize("arg", ["v - 50", "f - 25.5", "null"])
def test_abs(grouped, arg):
    """abs() on the device (a WHERE) and on the host (a select over a
    derived table), NULL in, NULL out."""
    _check(grouped, f"select k, abs({arg}) from w where abs({arg}) >= 0 "
                    f"or {arg} is null")
    _check(grouped, f"select a from (select abs({arg}) a from w) t")
