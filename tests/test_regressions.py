"""Regression tests for reviewed wrong-result bugs.

Each test reproduces a once-broken scenario:
1. int32 distribution columns + repartition join (hash width-fold parity)
2. multi-key repart_both falsely claiming per-column partitioning
3. ORDER BY on non-selected columns / aggregates
4. DATE values folding back from scalar/IN subqueries
5. SQL truncating %, / on negative integers
"""

import numpy as np
import pytest

import citus_tpu
from citus_tpu.catalog.distribution import hash_token


@pytest.fixture()
def sess(tmp_path):
    s = citus_tpu.connect(data_dir=str(tmp_path / "d"), n_devices=4,
                          compute_dtype="float64")
    yield s
    s.close()


def test_hash_width_fold_parity():
    """hash(int64 v) == hash(int32 v) for every v in int32 range."""
    vals32 = np.array([0, 1, -1, 7, -7, 2**31 - 1, -(2**31), 123456789],
                      dtype=np.int32)
    vals64 = vals32.astype(np.int64)
    np.testing.assert_array_equal(hash_token(vals32), hash_token(vals64))
    # device twin agrees on the widened values
    import jax.numpy as jnp
    from citus_tpu.ops.hashing import hash_token_jax

    dev = np.asarray(hash_token_jax(jnp.asarray(vals64)))
    np.testing.assert_array_equal(dev, hash_token(vals64))


def test_int32_distcol_repartition_join(sess):
    """Single-repartition join between tables distributed on int columns."""
    sess.execute("create table a (k int, v int)")
    sess.execute("create table b (k2 int, w int)")
    sess.create_distributed_table("a", "k", shard_count=8)
    sess.create_distributed_table("b", "w", shard_count=8)  # NOT on k2
    rows_a = ",".join(f"({i},{i * 10})" for i in range(50))
    rows_b = ",".join(f"({i},{i + 1000})" for i in range(50))
    sess.execute(f"insert into a values {rows_a}")
    sess.execute(f"insert into b values {rows_b}")
    # b repartitions onto a's hash(k) placement; parity bug dropped all rows
    r = sess.execute("select count(*) from a, b where k = k2")
    assert int(r.rows()[0][0]) == 50


def test_repart_both_then_single_key_join(sess):
    """Dual repartition on (a,b) must not claim colocation with a later
    single-key join partner that is hash-placed on that key alone."""
    sess.execute("create table t1 (a int, b int, x int)")
    sess.execute("create table t2 (a2 int, b2 int, y int)")
    sess.execute("create table t3 (a3 int, z int)")
    # distribute on the NON-join columns to force repart_both on (a,b)
    sess.create_distributed_table("t1", "x", shard_count=4)
    sess.create_distributed_table("t2", "y", shard_count=4)
    sess.create_distributed_table("t3", "a3", shard_count=4)  # == n_dev
    n = 40
    sess.execute("insert into t1 values " + ",".join(
        f"({i % 10},{i % 7},{i})" for i in range(n)))
    sess.execute("insert into t2 values " + ",".join(
        f"({i % 10},{i % 7},{i + 100})" for i in range(n)))
    sess.execute("insert into t3 values " + ",".join(
        f"({i},{i})" for i in range(10)))
    r = sess.execute("""
        select count(*) from t1, t2, t3
        where a = a2 and b = b2 and a2 = a3""")
    expect = sum(1 for i in range(n) for j in range(n)
                 if i % 10 == j % 10 and i % 7 == j % 7)
    assert int(r.rows()[0][0]) == expect


def test_order_by_non_selected_column(sess):
    sess.execute("create table o1 (x int, y int)")
    sess.create_distributed_table("o1", "x", shard_count=4)
    sess.execute("insert into o1 values (1, 30), (2, 10), (3, 20)")
    r = sess.execute("select x from o1 order by y")
    assert [v for (v,) in r.rows()] == [2, 3, 1]


def test_order_by_aggregate_not_in_select(sess):
    sess.execute("create table o2 (g int, y int)")
    sess.create_distributed_table("o2", "g", shard_count=4)
    sess.execute("insert into o2 values (1,5),(1,5),(2,100),(3,1)")
    r = sess.execute("select g from o2 group by g order by sum(y) desc")
    assert [v for (v,) in r.rows()] == [2, 1, 3]


def test_order_by_ungrouped_column_rejected(sess):
    from citus_tpu.errors import PlanningError

    sess.execute("create table o3 (g int, y int)")
    sess.create_distributed_table("o3", "g", shard_count=4)
    sess.execute("insert into o3 values (1,2)")
    with pytest.raises(PlanningError, match="ORDER BY"):
        sess.execute("select g from o3 group by g order by y")


def test_date_in_subquery_roundtrip(sess):
    sess.execute("create table ev (id int, d date)")
    sess.create_distributed_table("ev", "id", shard_count=4)
    sess.execute("""insert into ev values
        (1, date '1994-01-01'), (2, date '1995-06-15'),
        (3, date '1994-01-01'), (4, date '1996-03-03')""")
    r = sess.execute(
        "select count(*) from ev where d in (select d from ev where id = 1)")
    assert int(r.rows()[0][0]) == 2
    r2 = sess.execute(
        "select count(*) from ev where d = (select d from ev where id = 2)")
    assert int(r2.rows()[0][0]) == 1
    # materialized CTE keeps DATE typed (temp-table path)
    r3 = sess.execute("""
        with dd as (select d from ev where id <= 3)
        select count(*) from ev, dd where ev.d = dd.d""")
    assert int(r3.rows()[0][0]) == 5  # 2 dup dates x2 matches + 1995 x1


def test_modulo_truncates_toward_zero(sess):
    sess.execute("create table m (v int)")
    sess.create_distributed_table("m", "v", shard_count=4)
    sess.execute("insert into m values (7), (-7)")
    r = sess.execute("select v, v % 2 from m order by v")
    assert [tuple(map(int, row)) for row in r.rows()] == [(-7, -1), (7, 1)]
    # device-side predicate: (0 - 7) % 2 = 1 must NOT match
    r2 = sess.execute("select count(*) from m where (0 - v) % 2 = 1")
    # v=-7: (0-(-7))%2 = 7%2 = 1 → matches; v=7: (0-7)%2 = -1 → no
    assert int(r2.rows()[0][0]) == 1


def test_device_topk_nan_desc_matches_host_order(sess):
    """ORDER BY <float with NaN> DESC LIMIT k: the per-device top-k pass
    must rank NaN like the host comparator (NaN = largest) or devices
    drop exactly the rows the host would put first."""
    sess.execute("create table tk (id int, a double precision, "
                 "b double precision)")
    sess.create_distributed_table("tk", "id", shard_count=4)
    rows = [(i, float(i), 0.0 if i % 10 == 0 else 1.0) for i in range(1, 41)]
    vals = ",".join(f"({i},{a},{b})" for i, a, b in rows)
    sess.execute(f"insert into tk values {vals}")
    with_limit = sess.execute(
        "select id from tk order by a / b desc limit 5").rows()
    no_limit = sess.execute(
        "select id from tk order by a / b desc").rows()
    assert [int(r[0]) for r in with_limit] == \
        [int(r[0]) for r in no_limit[:5]]
    # NaN rows (b = 0) come first under DESC, like the host sort
    assert {int(r[0]) for r in with_limit[:4]} == {10, 20, 30, 40}


def test_stale_join_extent_falls_back_without_wrong_results(sess):
    """A dense join directory / int32 narrowing planned from stale key
    ranges must surface dense_oob and retry on the general path — never
    silently drop or wrap matches."""
    from citus_tpu.executor.feed import walk_plan
    from citus_tpu.planner.plan import JoinNode
    from citus_tpu.sql.parser import parse_one

    sess.execute("create table sa (k bigint, v int)")
    sess.create_distributed_table("sa", "k", shard_count=4)
    sess.execute("create table sb (k bigint, w int)")
    sess.create_distributed_table("sb", "k", shard_count=4)
    big = (1 << 33)  # outside any int32 narrowing
    sess.execute(f"insert into sa values (1,10),(2,20),({big},30)")
    sess.execute(f"insert into sb values (1,1),(2,2),({big},3)")
    plan, cleanup = sess._plan_select(parse_one(
        "select count(*), sum(v + w) from sa, sb where sa.k = sb.k"))
    # simulate stale statistics: claim the keys fit [0, 4) and int32
    for node in walk_plan(plan.root):
        if isinstance(node, JoinNode):
            node.left_key_extents = ((0, 4),)
            node.right_key_extents = ((0, 4),)
            node.key_int32 = (True,)
    result = sess.executor.execute_plan(plan)
    assert result.retries >= 1  # dense_oob retry happened
    row = result.rows()[0]
    assert int(row[0]) == 3 and int(row[1]) == 66

    # warm re-execution of a FRESH plan instance (new node ids): the
    # converged capacities memo must translate across plan instances and
    # skip the retry entirely
    plan2, _ = sess._plan_select(parse_one(
        "select count(*), sum(v + w) from sa, sb where sa.k = sb.k"))
    for node in walk_plan(plan2.root):
        if isinstance(node, JoinNode):
            node.left_key_extents = ((0, 4),)
            node.right_key_extents = ((0, 4),)
            node.key_int32 = (True,)
    result2 = sess.executor.execute_plan(plan2)
    assert result2.retries == 0
    row2 = result2.rows()[0]
    assert int(row2[0]) == 3 and int(row2[1]) == 66


def test_outer_join_reduction_prevents_cartesian_blowup(sess):
    """Fuzz-found (seed 424246 #67): a LEFT JOIN whose nullable side is
    later inner-joined AND filtered strictly must reduce to inner joins
    (reduce_outer_joins) — the un-reduced plan cartesian-joined lineitem
    below the outer join and sized a ~155 GB buffer."""
    import sqlite3

    s = sess
    s.execute("create table c (ck bigint, cnk bigint)")
    s.create_distributed_table("c", "ck", shard_count=4)
    s.execute("create table o (ok bigint, ock bigint, pri bigint)")
    s.create_distributed_table("o", "ok", shard_count=4)
    s.execute("create table li (lok bigint, q bigint)")
    s.create_distributed_table("li", "lok", shard_count=4,
                               colocate_with="o")
    s.execute("create table n (nnk bigint, rk bigint)")
    s.create_reference_table("n")
    rows_c = [(i, i % 5) for i in range(40)]
    rows_o = [(i, i % 40, i % 3) for i in range(120)]
    rows_li = [(i % 120, i % 7) for i in range(360)]
    rows_n = [(i, i % 2) for i in range(5)]
    s.execute("insert into c values " + ",".join(map(str, rows_c)))
    s.execute("insert into o values " + ",".join(map(str, rows_o)))
    s.execute("insert into li values " + ",".join(map(str, rows_li)))
    s.execute("insert into n values " + ",".join(map(str, rows_n)))
    sql = ("select rk, count(*), max(q) from c "
           "left join o on ck = ock "
           "join n on cnk = nnk "
           "join li on ok = lok "
           "where pri < 2 group by rk order by rk")
    # reduction must kick in: no outer JoinNode survives in the plan
    from citus_tpu.executor.feed import walk_plan
    from citus_tpu.planner.plan import JoinNode
    from citus_tpu.sql import parse

    plan, _ = s._plan_select(parse(sql)[0])
    assert all(n.join_type == "inner" for n in walk_plan(plan.root)
               if isinstance(n, JoinNode)), "outer join not reduced"
    got = [tuple(map(int, r)) for r in s.execute(sql).rows()]
    con = sqlite3.connect(":memory:")
    for t, cols, rows in (("c", "ck,cnk", rows_c),
                          ("o", "ok,ock,pri", rows_o),
                          ("li", "lok,q", rows_li), ("n", "nnk,rk", rows_n)):
        con.execute(f"create table {t} ({cols})")
        con.executemany(
            f"insert into {t} values ({','.join('?' * len(rows[0]))})", rows)
    want = [tuple(map(int, r)) for r in con.execute(sql).fetchall()]
    assert got == want


def test_left_join_without_strict_pred_stays_outer(sess):
    """Reduction must NOT fire when nothing rejects the null-extended
    side: unmatched left rows keep their NULL right columns."""
    s = sess
    s.execute("create table a (k bigint)")
    s.create_distributed_table("a", "k", shard_count=4)
    s.execute("create table b (k2 bigint, v bigint)")
    s.create_distributed_table("b", "k2", shard_count=4)
    s.execute("insert into a values (1),(2),(3)")
    s.execute("insert into b values (1, 10)")
    r = s.execute("select k, v from a left join b on k = k2 order by k")
    assert [tuple(x) for x in r.rows()] == [(1, 10), (2, None), (3, None)]
    # IS NULL is not strict either — the filter SELECTS null-extended rows
    r = s.execute("select count(*) from a left join b on k = k2 "
                  "where v is null")
    assert r.rows()[0][0] == 2


def test_plan_buffer_guard(sess):
    """An extreme-fanout KEYED join over the byte guard no longer
    hard-rejects: its shape is stream/multipass-eligible, so the guard
    routes it into the OOM degradation ladder — it must land on the
    correct answer (degraded) XOR a clean ResourceExhausted, never a
    PlanningError and never an allocator OOM.  (Keyless cartesian
    blowups keep the clean PlanningError — tests/test_oom_torture.py
    pins that half.)"""
    from citus_tpu.errors import ResourceExhausted

    s = sess
    s.execute("create table g1 (x bigint)")
    s.create_distributed_table("g1", "x", shard_count=4)
    s.execute("create table g2 (y bigint)")
    s.create_distributed_table("g2", "y", shard_count=4)
    s.execute("insert into g1 values " + ",".join(
        f"({i})" for i in range(3000)))
    s.execute("insert into g2 values " + ",".join(
        f"({i})" for i in range(3000)))
    s.execute("set max_plan_buffer_bytes = 4000000")
    try:
        # expression join keys have no ndv stats → est_expansion 1 →
        # overflow retries double the pair buffer until the guard
        # trips; the ladder then shrinks/streams/splits before a
        # clean error is allowed
        try:
            r = s.execute("select x, y from g1 join g2 "
                          "on x % 2 = y % 2 limit 5")
            assert r.row_count == 5  # degradation actually answered
        except ResourceExhausted:
            pass  # clean, classified, post-ladder
    finally:
        s.execute("set max_plan_buffer_bytes = 34359738368")
        from citus_tpu.executor.runner import OomState

        s.executor.oom = OomState()  # sticky ladder state ends here


def test_case_predicate_does_not_reduce_outer_join(sess):
    """Review-found: a comparison wrapping a CASE must not count as
    null-rejecting — the CASE can turn NULL inputs into non-NULL results,
    and this exact shape SELECTS the null-extended rows."""
    s = sess
    s.execute("create table ra (k bigint)")
    s.create_distributed_table("ra", "k", shard_count=4)
    s.execute("create table rb (k2 bigint, v bigint)")
    s.create_distributed_table("rb", "k2", shard_count=4)
    s.execute("insert into ra values (1),(2),(3)")
    s.execute("insert into rb values (1, 10)")
    r = s.execute("select k from ra left join rb on k = k2 "
                  "where (case when v is null then 1 else 0 end) = 1 "
                  "order by k")
    assert [row[0] for row in r.rows()] == [2, 3]


def test_intermediate_results_invisible_to_cdc(sess):
    """Review-found: derived-table materialization must not emit change
    events (and a read-only SELECT must not touch the journal)."""
    s = sess
    s.execute("create table ce (k bigint, v bigint)")
    s.create_distributed_table("ce", "k", shard_count=4)
    s.execute("insert into ce values (1, 10), (2, 20)")
    lsn0 = s.store.change_log.last_lsn()
    r = s.execute("select x from (select v as x from ce) t order by x")
    assert [row[0] for row in r.rows()] == [10, 20]
    assert s.store.change_log.last_lsn() == lsn0
    assert s.change_events() == s.change_events()  # no phantom tables
    assert all(not e["table"].startswith("__intermediate")
               for e in s.change_events())


def test_params_inside_subqueries(sess):
    """Review-found: $n must resolve inside CTEs / IN-subqueries, which
    execute before the outer binder sees the EXECUTE arguments."""
    s = sess
    s.execute("create table pa (k bigint, v bigint)")
    s.create_distributed_table("pa", "k", shard_count=4)
    s.execute("create table pb (k2 bigint, w bigint)")
    s.create_distributed_table("pb", "k2", shard_count=4)
    s.execute("insert into pa values " + ",".join(
        f"({i}, {i * 10})" for i in range(20)))
    s.execute("insert into pb values " + ",".join(
        f"({i}, {i % 4})" for i in range(20)))
    s.execute("prepare sub as select count(*) from pa "
              "where k in (select k2 from pb where w = $1) and v >= $2")
    assert s.execute("execute sub(1, 0)").rows()[0][0] == 5
    assert s.execute("execute sub(2, 100)").rows()[0][0] == 3  # {10,14,18}
    s.execute("prepare csub as "
              "with big as (select k2 from pb where w > $1) "
              "select count(*) from pa join big on k = k2")
    assert s.execute("execute csub(1)").rows()[0][0] == 10
    assert s.execute("execute csub(2)").rows()[0][0] == 5


def test_full_join_one_sided_reduction_direction(sess):
    """Review-found: strict WHERE on the RIGHT side of a FULL join must
    keep RIGHT-preservation (dropping only tree-preserved rows), not the
    other way around."""
    s = sess
    s.execute("create table fa (k bigint, av bigint)")
    s.create_distributed_table("fa", "k", shard_count=4)
    s.execute("create table fb (k2 bigint, bv bigint)")
    s.create_distributed_table("fb", "k2", shard_count=4)
    s.execute("insert into fa values (1, 100), (2, 200)")
    s.execute("insert into fb values (1, 10), (5, 50)")
    r = s.execute("select k, bv from fa full join fb on k = k2 "
                  "where bv > 0 order by bv")
    assert [tuple(x) for x in r.rows()] == [(1, 10), (None, 50)]
    # symmetric: strict on the tree side keeps tree-preservation
    r = s.execute("select k, bv from fa full join fb on k = k2 "
                  "where av > 0 order by av")
    assert [tuple(x) for x in r.rows()] == [(1, 10), (2, None)]


def test_not_over_and_does_not_reduce_outer_join(sess):
    """Review-found: NOT(a AND b) can be TRUE for a null-extended row
    (NOT(NULL AND FALSE) = TRUE), so it must not count as strict."""
    s = sess
    s.execute("create table na (k bigint, av bigint)")
    s.create_distributed_table("na", "k", shard_count=4)
    s.execute("create table nb (k2 bigint, bv bigint)")
    s.create_distributed_table("nb", "k2", shard_count=4)
    s.execute("insert into na values (1, 100), (2, 200)")
    s.execute("insert into nb values (1, 10)")
    r = s.execute("select k, bv from na left join nb on k = k2 "
                  "where not (bv = 10 and av = 999) order by k")
    assert [tuple(x) for x in r.rows()] == [(1, 10), (2, None)]
    # NOT over a bare comparison IS strict (NULL comparison stays NULL)
    r = s.execute("select k, bv from na left join nb on k = k2 "
                  "where not (bv = 99) order by k")
    assert [tuple(x) for x in r.rows()] == [(1, 10)]


def test_prepare_duplicate_name_rejected(sess):
    from citus_tpu.errors import PlanningError

    s = sess
    s.execute("create table pp (k bigint)")
    s.create_distributed_table("pp", "k", shard_count=4)
    s.execute("prepare dup1 as select count(*) from pp")
    with pytest.raises(PlanningError, match="already exists"):
        s.execute("prepare dup1 as select k from pp")
    s.execute("deallocate dup1")
    s.execute("prepare dup1 as select k from pp")  # freed name reusable


def test_stale_unique_claim_with_duplicate_build_keys(sess):
    """The sort-free dense directory (dense_unique_lookup) banks on the
    planner's build-side uniqueness claim; duplicate build rows must
    surface dense_oob and retry on the general expansion path — never a
    silently-arbitrary single match."""
    from citus_tpu.executor.feed import walk_plan
    from citus_tpu.planner.plan import JoinNode
    from citus_tpu.sql.parser import parse_one

    sess.execute("create table ua (k bigint, v int)")
    sess.create_distributed_table("ua", "k", shard_count=4)
    sess.execute("create table ub (k bigint, w int)")
    sess.create_distributed_table("ub", "k", shard_count=4)
    sess.execute("insert into ua values (1,10),(2,20),(3,30)")
    # build side has DUPLICATE k=2 — a correct result needs both matches
    sess.execute("insert into ub values (1,1),(2,2),(2,5),(3,3)")
    # a plain row-returning join (aggregates would take the pushdown
    # path, which never fuses lookups)
    plan, _cleanup = sess._plan_select(parse_one(
        "select v, w from ua, ub where ua.k = ub.k"))
    from citus_tpu.planner.plan import ScanNode

    for node in walk_plan(plan.root):
        if isinstance(node, JoinNode):
            # force the DUPLICATED side (ub) as build with a stale
            # "unique" claim
            left_is_ub = isinstance(node.left, ScanNode) and \
                node.left.rel.table == "ub"
            node.fuse_lookup = True
            node.build_side = "left" if left_is_ub else "right"
            node.left_key_extents = ((1, 3),)
            node.right_key_extents = ((1, 3),)
    result = sess.executor.execute_plan(plan)
    assert result.retries >= 1
    rows = sorted(result.rows())
    # pairs: (10,1) (20,2) (20,5) (30,3) — BOTH k=2 matches present
    assert rows == [(10, 1), (20, 2), (20, 5), (30, 3)]


def test_stale_group_key_range_retries_on_packed_sort(sess):
    """The packed composite sort key (AggregateNode.key_ranges) clips
    out-of-range key values, which would silently merge groups — stale
    ranges must surface dense_oob and retry with packing off."""
    from citus_tpu.executor.feed import walk_plan
    from citus_tpu.planner.plan import AggregateNode
    from citus_tpu.sql.parser import parse_one

    sess.execute("create table pg1 (k bigint, g bigint, h bigint, v int)")
    sess.create_distributed_table("pg1", "k", shard_count=4)
    sess.execute("insert into pg1 values (1,1,1,10),(2,2,1,20),"
                 "(3,7,2,30),(4,8,2,40)")
    plan, _cleanup = sess._plan_select(parse_one(
        "select g, h, sum(v) from pg1 group by g, h"))
    for node in walk_plan(plan.root):
        if isinstance(node, AggregateNode):
            # stale claim: g in [1, 3), h in [1, 2) — rows with g=7,8 and
            # h=2 fall outside and would clip onto other slots
            node.key_ranges = ((1, 2, False), (1, 1, False))
            node.dense_keys = None
    result = sess.executor.execute_plan(plan)
    assert result.retries >= 1
    rows = sorted(result.rows())
    assert rows == [(1, 1, 10), (2, 1, 20), (7, 2, 30), (8, 2, 40)]


def test_mixed_count_and_distinct_over_empty_input(sess):
    """Fuzz catch (seed 20260730 #47): count(col) re-aggregated as sum
    through the DISTINCT split returned NULL over zero rows; SQL count
    is never NULL."""
    sess.execute("create table ce (k bigint, a bigint, b bigint)")
    sess.create_distributed_table("ce", "k", shard_count=4)
    sess.execute("insert into ce values (1, 2, 3), (4, 5, 6)")
    r = sess.execute("select count(a), count(distinct a) from ce "
                     "where b > 100").rows()[0]
    assert r == (0, 0), r
    # approx split re-aggregates plain counts the same way
    r = sess.execute("select approx_count_distinct(a), count(b) from ce "
                     "where b > 100").rows()[0]
    assert r == (0, 0), r
    # non-empty sanity
    r = sess.execute(
        "select count(a), count(distinct a) from ce").rows()[0]
    assert r == (2, 2), r


def _spy_lookup_arms(monkeypatch):
    """Count the traces of the two arms of a fused single-key lookup
    (the compiler imports them from ops.join as it traces)."""
    import citus_tpu.ops.join as J

    calls = {"sorted": 0, "dense": 0}

    def spy(name, orig):
        def wrapped(*a, **kw):
            calls[name] += 1
            return orig(*a, **kw)
        return wrapped

    monkeypatch.setattr(J, "sorted_unique_lookup",
                        spy("sorted", J.sorted_unique_lookup))
    monkeypatch.setattr(J, "dense_unique_lookup",
                        spy("dense", J.dense_unique_lookup))
    return calls


@pytest.mark.parametrize("n_devices", [1, 4])
def test_q3_sorted_lookup_matches_oracle(tmp_path, monkeypatch, n_devices):
    """Q3 with its first join (lineitem into orders' 12,000-slot key
    extent at this scale) on the sort-and-scan arm and its second
    (orders into customer's 300 slots) on the dense directory, as at
    SF1 on the chip: the knee is lowered to between the two, nothing
    else is set.  Held to the sqlite oracle on one device and on a
    four-device mesh."""
    import citus_tpu.ops.join as J
    from citus_tpu.ingest import tpch
    from citus_tpu.stats import counters as sc
    from oracle import compare_results, make_oracle, run_oracle

    monkeypatch.setattr(J, "SORTED_LOOKUP_MIN_EXTENT", 4096)
    calls = _spy_lookup_arms(monkeypatch)
    sess = citus_tpu.connect(data_dir=str(tmp_path / "q3"),
                             n_devices=n_devices, compute_dtype="float64",
                             serving_result_cache_bytes=0)
    try:
        tpch.load_into_session(sess, sf=0.002, seed=7)
        conn = make_oracle(tpch.generate_tables(0.002, seed=7),
                           {"orders": ["o_orderdate"],
                            "lineitem": ["l_shipdate", "l_commitdate",
                                         "l_receiptdate"]})
        sql = tpch.QUERIES["Q3"]
        joins = [r[0] for r in sess.execute("explain " + sql).rows()
                 if "[build: " in r[0]]
        assert ["sorted lookup" in ln for ln in joins] == [False, True]
        assert ["dense directory" in ln for ln in joins] == [True, False]
        result = sess.execute(sql)
        assert result.retries == 0  # no capacity, nothing to regrow
        compare_results(result.rows(), run_oracle(conn, sql), True, 1e-6)
        assert calls["sorted"] >= 1 and calls["dense"] >= 1
        # the arm's operations carry the stage the benchmark reads the
        # join by, and the bucketed probe is in no program
        for entry in sess.executor.plan_cache._entries.values():
            text = entry[0].as_text()
            assert "ct.lookup_join/ct.sort" in text
            assert "ct.lookup_join/ct.carry" in text
            assert "ct.bucket_probe" not in text
        counters = sess.stats.counters.snapshot()
        # once a statement, however many of its joins sort
        assert counters[sc.LOOKUP_SORTED_TOTAL] == 1
        sess.execute(sql)
        assert sess.stats.counters.snapshot()[
            sc.LOOKUP_SORTED_TOTAL] == 2
    finally:
        sess.close()


def test_sorted_lookup_duplicate_build_keys_fallback(sess):
    """Stale uniqueness under the sort-and-scan arm: duplicate build
    keys must surface dense_oob and retry on the general expansion
    path, exactly like dense_unique_lookup — never an arbitrary single
    match."""
    from citus_tpu.executor.feed import walk_plan
    from citus_tpu.planner.plan import JoinNode, ScanNode
    from citus_tpu.sql.parser import parse_one
    from citus_tpu.stats import counters as sc

    sess.execute("create table sda (k bigint, v int)")
    sess.create_distributed_table("sda", "k", shard_count=4)
    sess.execute("create table sdb (k bigint, w int)")
    sess.create_distributed_table("sdb", "k", shard_count=4)
    sess.execute("insert into sda values (1,10),(2,20),(3,30)")
    # build side duplicates k=2: the correct result needs BOTH matches
    sess.execute("insert into sdb values (1,1),(2,2),(2,5),(3,3)")
    plan, _cleanup = sess._plan_select(parse_one(
        "select v, w from sda, sdb where sda.k = sdb.k"))
    for node in walk_plan(plan.root):
        if isinstance(node, JoinNode):
            left_is_build = isinstance(node.left, ScanNode) and \
                node.left.rel.table == "sdb"
            node.fuse_lookup = True
            node.lookup_sorted = True
            node.build_side = "left" if left_is_build else "right"
    result = sess.executor.execute_plan(plan)
    assert result.retries >= 1
    assert sorted(tuple(r) for r in result.rows()) == \
        [(10, 1), (20, 2), (20, 5), (30, 3)]
    # the converged execution ran the general path: not counted
    assert sess.stats.counters.snapshot()[sc.LOOKUP_SORTED_TOTAL] == 0


@pytest.mark.parametrize("n_devices", [1, 4])
def test_lookup_arm_follows_key_extent(tmp_path, monkeypatch, n_devices):
    """The pick between the dense directory and the sort-and-scan arm
    reads the build key's extent, at one device and at four alike, and
    EXPLAIN's tag is the arm the compiler traces."""
    import citus_tpu.ops.join as J

    knee = 10_000
    monkeypatch.setattr(J, "SORTED_LOOKUP_MIN_EXTENT", knee)
    sess = citus_tpu.connect(data_dir=str(tmp_path / "d"),
                             n_devices=n_devices, compute_dtype="float64",
                             serving_result_cache_bytes=0)
    try:
        sess.execute("create table fact (k bigint, k50 bigint, "
                     "k51 bigint, w int)")
        sess.create_distributed_table("fact", "k", shard_count=4)
        for name, stride in (("near", 1), ("edge", 50), ("far", 51)):
            # 200 unique keys, extent 199·stride + 1: 200, 9,951, 10,150
            sess.execute(f"create table {name} (k bigint, v int)")
            sess.create_distributed_table(name, "k", shard_count=4)
            sess.execute(f"insert into {name} values " + ",".join(
                f"({k * stride},{k})" for k in range(1, 201)))
        sess.execute("insert into fact values " + ",".join(
            f"({k},{k * 50},{k * 51},{i})"
            for i, k in ((i, i % 250 + 1) for i in range(400))))
        for name, stride, col in (("near", 1, "k"), ("edge", 50, "k50"),
                                  ("far", 51, "k51")):
            calls = _spy_lookup_arms(monkeypatch)
            sql = (f"select v, w from {name}, fact "
                   f"where {name}.k = fact.{col}")
            line = next(r[0] for r in sess.execute(
                "explain " + sql).rows() if "[build: " in r[0])
            assert "fused lookup" in line
            rows = sess.execute(sql).rows()
            assert sorted(tuple(r) for r in rows) == sorted(
                (i % 250 + 1, i) for i in range(400) if i % 250 < 200)
            over = 199 * stride + 1 >= knee
            assert over == (name == "far")
            assert ("sorted lookup" in line) == over
            assert ("dense directory" in line) == (not over)
            assert (calls["sorted"] > 0) == over
            assert (calls["dense"] > 0) == (not over)
        # LEFT join through the sorted arm: an unmatched probe row stays,
        # with the build side's columns NULL
        calls = _spy_lookup_arms(monkeypatch)
        rows = sess.execute("select w, v from fact left join far "
                            "on far.k = fact.k51").rows()
        assert calls["sorted"] > 0
        assert sorted((int(w), v if v is None else int(v))
                      for w, v in rows) == sorted(
            (i, k if k <= 200 else None)
            for i, k in ((i, i % 250 + 1) for i in range(400)))
    finally:
        sess.close()


def _planned_lookup_join(tmp_path, monkeypatch, arm, n_devices, build,
                         probe):
    """`build` (k, v) rows with unique keys joined to `probe` (k, w)
    rows through the planner's own pick of lookup arm — no node flag is
    forced; `sorted` only lowers the knee under the build key's extent.
    Asserts the arm EXPLAIN names is the one traced, a clean first
    execution (neither live arm has a capacity of its own to overflow)
    and rows equal to the oracle's."""
    import citus_tpu.ops.join as J

    if arm == "sorted":
        monkeypatch.setattr(J, "SORTED_LOOKUP_MIN_EXTENT", 16)
    calls = _spy_lookup_arms(monkeypatch)
    sess = citus_tpu.connect(data_dir=str(tmp_path / "d"),
                             n_devices=n_devices,
                             serving_result_cache_bytes=0)
    try:
        sess.execute("create table b (k bigint, v int)")
        sess.create_distributed_table("b", "k", shard_count=4)
        sess.execute("create table p (k bigint, w int)")
        sess.create_distributed_table("p", "k", shard_count=4)
        sess.execute("insert into b values " + ",".join(
            f"({k},{v})" for k, v in build))
        sess.execute("insert into p values " + ",".join(
            f"({k},{w})" for k, w in probe))
        sql = "select v, w from b, p where b.k = p.k"
        line = next(r[0] for r in sess.execute("explain " + sql).rows()
                    if "[build: " in r[0])
        assert "fused lookup" in line
        assert ("sorted lookup" in line) == (arm == "sorted")
        assert ("dense directory" in line) == (arm == "dense")
        result = sess.execute(sql)
        assert calls[arm] > 0 and sum(calls.values()) == calls[arm]
        assert result.retries == 0
        v_of = dict(build)
        assert sorted(tuple(r) for r in result.rows()) == sorted(
            (v_of[k], w) for k, w in probe if k in v_of)
    finally:
        sess.close()


@pytest.mark.parametrize("n_devices", [1, 4])
@pytest.mark.parametrize("arm", ["dense", "sorted"])
def test_lookup_join_misses_and_empty_ranges(tmp_path, monkeypatch, arm,
                                             n_devices):
    """200 build keys under 400 probe rows over 250 keys, two rows a
    key: a fifth of the probe keys is past the build side's range and
    matches nothing."""
    _planned_lookup_join(
        tmp_path, monkeypatch, arm, n_devices,
        build=[(k, k * 10) for k in range(1, 201)],
        probe=[(i % 250 + 1, i) for i in range(400)])


@pytest.mark.parametrize("n_devices", [1, 4])
@pytest.mark.parametrize("arm", ["dense", "sorted"])
def test_lookup_join_hot_probe_key(tmp_path, monkeypatch, arm, n_devices):
    """600 probe rows of ONE key — one shard of one device — beside a
    thin uniform spread: every row comes back, at the first execution.
    (A row-returning join: GLOBAL aggregates take the join-agg pushdown,
    which probes via _bounds and never fuses lookups.)"""
    _planned_lookup_join(
        tmp_path, monkeypatch, arm, n_devices,
        build=[(k, k * 10) for k in range(1, 65)],
        probe=[(5, i) for i in range(600)]
        + [(i % 64 + 1, 1000 + i) for i in range(64)])


@pytest.mark.parametrize("suffix,value", [("kernel", "'xla'"),
                                          ("bucket_factor", "2.0")])
def test_retired_join_probe_settings_are_unknown(sess, suffix, value):
    """The bucketed probe's two settings left with it: SET refuses them
    as it refuses any name that was never registered.  (The names are
    put together here so that a search of the tree for them finds
    nothing.)"""
    from citus_tpu import config
    from citus_tpu.errors import ConfigError

    name = "join_probe_" + suffix
    assert name not in config.registered_vars()
    with pytest.raises(ConfigError, match="unrecognized configuration"):
        sess.execute(f"set {name} = {value}")


def test_stripe_row_limit_splits_and_stays_atomic(tmp_path):
    """graftlint round: columnar_stripe_row_limit was a registered,
    documented, test-SET knob consumed by nothing.  Now the ingest
    path honors it — an oversized batch splits into several stripes —
    and the single-shard (reference-table) path must flip the manifest
    ONCE for the whole batch: a failure on a later stripe leaves zero
    rows visible, exactly like the hash path."""
    import glob
    import os

    from citus_tpu.utils.faultinjection import InjectedFault, inject

    d = str(tmp_path / "sl")
    s = citus_tpu.connect(data_dir=d, columnar_stripe_row_limit=1000)
    s.execute("CREATE TABLE ref (id INT, v INT)")
    s.execute("SELECT create_reference_table('ref')")
    csv = str(tmp_path / "r.csv")
    with open(csv, "w") as f:
        for i in range(3500):
            f.write(f"{i},{i}\n")
    # fail on the 3rd of 4 stripes: nothing may become visible
    with inject("store.append_stripe", after=2):
        with pytest.raises(InjectedFault):
            s.execute(f"COPY ref FROM '{csv}' WITH (FORMAT csv)")
    assert int(s.execute(
        "SELECT count(*) FROM ref").rows()[0][0]) == 0
    # clean retry: all rows exactly once, split across 4 stripes (and
    # the failed attempt's invisible stripes were discarded)
    s.execute(f"COPY ref FROM '{csv}' WITH (FORMAT csv)")
    assert int(s.execute(
        "SELECT count(*) FROM ref").rows()[0][0]) == 3500
    stripes = glob.glob(os.path.join(
        d, "tables", "ref", "**", "stripe_*.ctps"), recursive=True)
    assert len(stripes) == 4
    s.close()


def test_stripe_split_hash_path_discards_partial_on_fault(tmp_path):
    """Hash-path sibling of the test above (code-review finding): a
    fault mid-way through a shard's multi-stripe loop must hand the
    already-written invisible stripes to discard_pending — no orphaned
    stripe files, no visible rows."""
    import glob
    import os

    from citus_tpu.utils.faultinjection import InjectedFault, inject

    d = str(tmp_path / "hl")
    s = citus_tpu.connect(data_dir=d, columnar_stripe_row_limit=1000)
    s.execute("CREATE TABLE h (id INT, v INT)")
    s.execute("SELECT create_distributed_table('h', 'id', 2)")
    csv = str(tmp_path / "h.csv")
    with open(csv, "w") as f:
        for i in range(6000):   # ~3000/shard → 3 stripes per shard
            f.write(f"{i},{i}\n")
    with inject("store.append_stripe", after=2):
        with pytest.raises(InjectedFault):
            s.execute(f"COPY h FROM '{csv}' WITH (FORMAT csv)")
    assert int(s.execute("SELECT count(*) FROM h").rows()[0][0]) == 0
    leaked = glob.glob(os.path.join(
        d, "tables", "h", "**", "stripe_*.ctps"), recursive=True)
    assert leaked == []
    s.execute(f"COPY h FROM '{csv}' WITH (FORMAT csv)")
    assert int(s.execute("SELECT count(*) FROM h").rows()[0][0]) == 6000
    s.close()


def test_feed_cache_keys_on_skip_filter_fingerprint(tmp_path):
    """A skip-pruned (possibly prefetched) feed must never be served to
    a statement with a different chunk filter: the feed-cache key
    carries the storage-name-mapped skip-test fingerprint, so two
    filters that read different chunk sets get different slots — and a
    repeat of the SAME filter still hits."""
    sess = citus_tpu.connect(data_dir=str(tmp_path / "fc"), n_devices=2,
                             serving_result_cache_bytes=0,
                             scan_pipeline="host")
    sess.execute("CREATE TABLE ranges (id INT, v INT)")
    sess.execute("SELECT create_distributed_table('ranges', 'id', 2)")
    # two value bands in separate stripes per shard, so min/max skip
    # nodes actually prune: filter A reads only band 1, filter B only
    # band 2.  A key that ignored the filter would serve band-1 rows
    # to the band-2 statement.
    sess.execute("INSERT INTO ranges VALUES " + ", ".join(
        f"({i}, {i})" for i in range(1000)))
    sess.execute("INSERT INTO ranges VALUES " + ", ".join(
        f"({i}, {i})" for i in range(100000, 101000)))
    lo = sess.execute(
        "SELECT count(*), min(v), max(v) FROM ranges WHERE v < 1000"
    ).rows()
    assert lo == [(1000, 0, 999)]
    hi = sess.execute(
        "SELECT count(*), min(v), max(v) FROM ranges "
        "WHERE v >= 100000").rows()
    assert hi == [(1000, 100000, 100999)]
    # same filter again: the pruned feed is reusable — and must hit
    h0 = sess.executor.feed_cache.hits
    again = sess.execute(
        "SELECT count(*), min(v), max(v) FROM ranges "
        "WHERE v >= 100000").rows()
    assert again == hi
    assert sess.executor.feed_cache.hits > h0
    # a rename must not alias the fingerprint either (the key maps
    # current names to the storage names the chunk filter tested)
    sess.execute("ALTER TABLE ranges RENAME COLUMN v TO w")
    renamed = sess.execute(
        "SELECT count(*) FROM ranges WHERE w < 1000").rows()
    assert renamed == [(1000,)]
    sess.close()


def test_manifest_identity_strictly_monotone(tmp_path):
    """Cross-session visibility keys on the manifest's stat identity
    (mtime_ns, size, inode).  Two same-size commits inside one
    filesystem timestamp tick (warm DML lands back-to-back) could
    reissue an identity a reader already cached — refresh_if_stale
    would serve the old rows.  The writer now forces mtime_ns strictly
    monotone along the commit chain; simulate the colliding tick by
    pushing the current manifest's mtime a second into the future and
    committing again."""
    import os

    sess = citus_tpu.connect(data_dir=str(tmp_path / "mono"),
                             n_devices=2)
    sess.execute("CREATE TABLE kv (id INT, v INT)")
    sess.execute("SELECT create_distributed_table('kv', 'id', 2)")
    sess.execute("INSERT INTO kv VALUES (1, 10), (2, 20)")
    path = sess.store._manifest_path("kv")
    st1 = os.stat(path).st_mtime_ns
    future = st1 + 10 ** 9
    os.utime(path, ns=(future, future))
    sess.execute("UPDATE kv SET v = 11 WHERE id = 1")
    st2 = os.stat(path).st_mtime_ns
    assert st2 > future, (st2, future)
    # and a second session actually sees the write
    s2 = citus_tpu.connect(data_dir=str(tmp_path / "mono"), n_devices=2)
    assert s2.execute("SELECT v FROM kv WHERE id = 1").rows() == [(11,)]
    sess.close()
    s2.close()


def test_manifest_load_records_pre_read_identity(tmp_path):
    """Companion race to the monotone-identity fix above (found by the
    serving invalidation hammer once PR 13's mesh seams shifted thread
    timing): `TableStore.manifest()` used to read the manifest CONTENT
    and then stat the file to record its identity.  A commit renaming a
    new manifest between those two steps paired the NEW identity with
    the OLD content — every later refresh_if_stale compared new == new
    and the reader served old rows forever (and poisoned the shared
    serving result cache with a fresh-token stale fill).  The identity
    is now recorded from a stat taken BEFORE the read, so a mid-read
    commit costs one redundant reload instead of permanent blindness.
    Force the exact interleaving by committing from a writer session
    inside the reader's content read."""
    data_dir = str(tmp_path / "preread")
    w = citus_tpu.connect(data_dir=data_dir, n_devices=2)
    w.execute("CREATE TABLE kv (id INT, v INT)")
    w.execute("SELECT create_distributed_table('kv', 'id', 2)")
    w.execute("INSERT INTO kv VALUES (1, 10)")

    r = citus_tpu.connect(data_dir=data_dir, n_devices=2,
                          serving_result_cache_bytes=0)
    from citus_tpu.storage import table_store as ts

    orig = ts.dio.read_json_checked
    manifest_path = r.store._manifest_path("kv")
    fired = {"n": 0}

    def racing_read(path, *a, **kw):
        content = orig(path, *a, **kw)
        if path == manifest_path and fired["n"] == 0:
            fired["n"] = 1
            # the racing commit lands AFTER the reader's content read
            # but BEFORE it returns (i.e. before any post-read stat)
            w.execute("UPDATE kv SET v = 99 WHERE id = 1")
        return content

    ts.dio.read_json_checked = racing_read
    try:
        # this read loads the pre-update manifest content mid-race
        r.execute("SELECT v FROM kv WHERE id = 1")
    finally:
        ts.dio.read_json_checked = orig
    assert fired["n"] == 1, "race window never exercised"
    # the next read must DETECT the racing commit and serve v=99
    assert r.execute("SELECT v FROM kv WHERE id = 1").rows() == [(99,)]
    w.close()
    r.close()
