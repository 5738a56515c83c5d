"""Bucketed dense-grid aggregation (ops/groupby.py): end-to-end tests.

Oracle contract: with `group_by_kernel` forced onto the bucketed path,
every GROUP BY shape must return exactly what the sort path returns —
nulls form their own groups, filtered-out rows never contribute,
all-duplicate keys collapse to one group, empty inputs yield zero
groups.  Stale planner key ranges retry onto the sort path (dense_oob
protocol), hot buckets overflow + regrow (count-then-emit), and the
observability surfaces (EXPLAIN tag, groupby_bucketed_total counter,
EXPLAIN ANALYZE "Caches:" line, citus_stat_activity cache columns,
executor.agg_bucket_fill fault point) all show the path."""

import pytest

import citus_tpu
import citus_tpu.ops.groupby as G
from citus_tpu.executor.feed import walk_plan
from citus_tpu.planner.plan import AggregateNode
from citus_tpu.sql.parser import parse_one
from citus_tpu.utils.faultinjection import InjectedFault, inject


@pytest.fixture()
def sess(tmp_path):
    s = citus_tpu.connect(data_dir=str(tmp_path / "d"), n_devices=4,
                          compute_dtype="float64")
    yield s
    s.close()


def _force_bucketed_groupby(plan, specs):
    """Flip every aggregate in `plan` onto the bucketed dense-grid path
    with the given (base, extent, has_null) specs (the test analogue of
    the planner's structural annotation; group_by_kernel='bucketed'
    must also be set so agg_bucket_shape accepts it on the CPU mesh)."""
    total = 1
    for _b, extent, _hn in specs:
        total *= extent + 1
    for node in walk_plan(plan.root):
        if isinstance(node, AggregateNode) and node.group_keys:
            node.bucket_keys = tuple(specs)
            node.bucket_total = total
            node.dense_keys = None
            node.key_ranges = tuple(specs)
    return total


def _sorted(rows):
    """NULL-safe row sort (None has no < against ints)."""
    return sorted((tuple(r) for r in rows),
                  key=lambda t: tuple((x is None, x) for x in t))


def _rows(sess, sql):
    return _sorted(sess.execute(sql).rows())


class TestOracleParity:
    """Forced-bucketed results == sort-path results, per shape."""

    def _parity(self, sess, monkeypatch, sql, specs, tile=64):
        monkeypatch.setattr(G, "GROUP_TILE_SLOTS", tile)
        sess.execute("set group_by_kernel = 'sort'")
        want = _rows(sess, sql)
        sess.execute("set group_by_kernel = 'bucketed'")
        plan, _cleanup = sess._plan_select(parse_one(sql))
        _force_bucketed_groupby(plan, specs)
        result = sess.executor.execute_plan(plan)
        assert result.retries == 0, "clean bucketed execution expected"
        assert _sorted(result.rows()) == want
        return result

    def test_mixed_aggregates(self, sess, monkeypatch):
        sess.execute("create table ga (k bigint, g bigint, v int)")
        sess.create_distributed_table("ga", "k", shard_count=4)
        sess.execute("insert into ga values " + ",".join(
            f"({i},{i % 211},{i % 37 - 18})" for i in range(900)))
        self._parity(
            sess, monkeypatch,
            "select g, count(*), sum(v), min(v), max(v), avg(v) "
            "from ga group by g",
            [(0, 211, False)])

    def test_null_keys_form_their_own_group(self, sess, monkeypatch):
        sess.execute("create table gn (k bigint, g bigint, v int)")
        sess.create_distributed_table("gn", "k", shard_count=4)
        vals = ",".join(
            f"({i},{'null' if i % 5 == 0 else i % 97},"
            f"{'null' if i % 7 == 0 else i})" for i in range(400))
        sess.execute("insert into gn values " + vals)
        # count(v) skips NULL v; the NULL-g group must survive the grid
        self._parity(sess, monkeypatch,
                     "select g, count(v), sum(v) from gn group by g",
                     [(0, 97, True)])

    def test_invalid_rows_never_contribute(self, sess, monkeypatch):
        sess.execute("create table gf (k bigint, g bigint, v int)")
        sess.create_distributed_table("gf", "k", shard_count=4)
        sess.execute("insert into gf values " + ",".join(
            f"({i},{i % 113},{i})" for i in range(500)))
        self._parity(sess, monkeypatch,
                     "select g, count(*), sum(v) from gf "
                     "where v % 3 = 0 group by g",
                     [(0, 113, False)])

    def test_all_duplicate_keys_one_group(self, sess, monkeypatch):
        sess.execute("create table gd (k bigint, g bigint, v int)")
        sess.create_distributed_table("gd", "k", shard_count=4)
        sess.execute("insert into gd values " + ",".join(
            f"({i},42,{i})" for i in range(300)))
        r = self._parity(sess, monkeypatch,
                         "select g, count(*), sum(v) from gd group by g",
                         [(0, 200, False)])
        assert r.row_count == 1

    def test_empty_input(self, sess, monkeypatch):
        sess.execute("create table ge (k bigint, g bigint, v int)")
        sess.create_distributed_table("ge", "k", shard_count=4)
        sess.execute("insert into ge values (1, 5, 10)")
        self._parity(sess, monkeypatch,
                     "select g, count(*), sum(v) from ge "
                     "where v > 1000 group by g",
                     [(0, 300, False)])

    def test_multi_key_composite_slot(self, sess, monkeypatch):
        sess.execute("create table gm (k bigint, g bigint, h bigint, "
                     "v int)")
        sess.create_distributed_table("gm", "k", shard_count=4)
        sess.execute("insert into gm values " + ",".join(
            f"({i},{i % 53},{i % 7},{i})" for i in range(600)))
        self._parity(sess, monkeypatch,
                     "select g, h, count(*), max(v) from gm "
                     "group by g, h",
                     [(0, 53, False), (0, 7, False)])

    def test_pallas_kernel_parity(self, sess, monkeypatch):
        sess.execute("create table gp (k bigint, g bigint, v int)")
        sess.create_distributed_table("gp", "k", shard_count=4)
        sess.execute("insert into gp values " + ",".join(
            f"({i},{i % 131},{i})" for i in range(500)))
        monkeypatch.setattr(G, "GROUP_TILE_SLOTS", 64)
        sess.execute("set group_by_kernel = 'sort'")
        want = _rows(sess, "select g, count(*), sum(v) from gp group by g")
        # bucketed_pallas on the CPU backend degrades to the XLA
        # formulation (compiled pallas_call is interpret-only there) —
        # the config must execute, not crash, and match the oracle
        sess.execute("set group_by_kernel = 'bucketed_pallas'")
        plan, _cleanup = sess._plan_select(parse_one(
            "select g, count(*), sum(v) from gp group by g"))
        _force_bucketed_groupby(plan, [(0, 131, False)])
        result = sess.executor.execute_plan(plan)
        assert sorted(tuple(r) for r in result.rows()) == want


def test_stale_key_ranges_retry_on_sort_path(sess, monkeypatch):
    """Rows whose key falls outside the planned range would alias a
    wrong grid slot — they must surface dense_oob and the host must
    recompile on the sort path (dense_off disables agg_bucket_shape),
    never return aliased groups."""
    monkeypatch.setattr(G, "GROUP_TILE_SLOTS", 16)
    sess.execute("create table gs (k bigint, g bigint, v int)")
    sess.create_distributed_table("gs", "k", shard_count=4)
    # g values 1..120, but the stale claim says extent 40
    sess.execute("insert into gs values " + ",".join(
        f"({i},{i % 120 + 1},{i % 9})" for i in range(360)))
    sess.execute("set group_by_kernel = 'bucketed'")
    sql = "select g, count(*), sum(v) from gs group by g"
    plan, _cleanup = sess._plan_select(parse_one(sql))
    _force_bucketed_groupby(plan, [(1, 40, False)])
    result = sess.executor.execute_plan(plan)
    assert result.retries >= 1
    sess.execute("set group_by_kernel = 'sort'")
    assert sorted(tuple(r) for r in result.rows()) == _rows(sess, sql)


def test_hot_bucket_overflow_regrows_and_converges(sess, monkeypatch):
    """Extreme skew: nearly every row lands in ONE slot's bucket while
    the initial per-bucket capacity assumes uniformity — the overflow
    must be REPORTED and the retry must regrow to a complete answer
    (count-then-emit; rows are never silently dropped)."""
    monkeypatch.setattr(G, "GROUP_TILE_SLOTS", 16)
    sess.execute("set agg_bucket_capacity_factor = 1.0")
    sess.execute("set group_by_kernel = 'bucketed'")
    sess.execute("create table gh (k bigint, g bigint, v int)")
    sess.create_distributed_table("gh", "k", shard_count=4)
    rows = [f"({i},7,1)" for i in range(3000)]
    rows += [f"({10000 + i},{i % 120},1)" for i in range(120)]
    sess.execute("insert into gh values " + ",".join(rows))
    sql = "select g, count(*) from gh group by g"
    plan, _cleanup = sess._plan_select(parse_one(sql))
    _force_bucketed_groupby(plan, [(0, 120, False)])
    result = sess.executor.execute_plan(plan)
    assert result.retries >= 1  # the hot bucket overflowed and regrew
    got = dict(tuple(r) for r in result.rows())
    assert got[7] == 3000 + 1  # skewed rows + one spread row (7 % 120)
    assert sum(got.values()) == 3120


def test_planner_annotates_structural_eligibility(sess, monkeypatch):
    """Past DENSE_GROUP_LIMIT with a materializable, occupied slot
    space the planner stores bucket_keys/bucket_total; the AUTO pick
    stays off on the CPU backend (measurement gate), so the sort path
    runs unless group_by_kernel forces the grid."""
    from citus_tpu.planner.plan import DistributedPlanner

    monkeypatch.setattr(DistributedPlanner, "DENSE_GROUP_LIMIT", 16)
    sess.execute("create table gz (k bigint, g bigint, v int)")
    sess.create_distributed_table("gz", "k", shard_count=4)
    sess.execute("insert into gz values " + ",".join(
        f"({i},{i % 90},{i})" for i in range(400)))
    plan, _cleanup = sess._plan_select(parse_one(
        "select g, count(*) from gz group by g"))
    aggs = [n for n in walk_plan(plan.root)
            if isinstance(n, AggregateNode)]
    assert aggs
    for node in aggs:
        assert node.dense_keys is None
        assert node.bucket_keys is not None
        assert node.bucket_total == 91  # extent 90 + reserved null slot
        assert node.group_bucketed is False  # CPU backend: auto = sort

    # sparse key space (occupancy below 1/4) must NOT be eligible
    sess.execute("create table gz2 (k bigint, g bigint)")
    sess.create_distributed_table("gz2", "k", shard_count=4)
    sess.execute("insert into gz2 values (1, 0), (2, 40000)")
    plan2, _cleanup = sess._plan_select(parse_one(
        "select g, count(*) from gz2 group by g"))
    for node in walk_plan(plan2.root):
        if isinstance(node, AggregateNode):
            assert node.bucket_keys is None


def test_explain_shows_bucketed_tag(sess, monkeypatch):
    from citus_tpu.planner.plan import DistributedPlanner

    monkeypatch.setattr(DistributedPlanner, "DENSE_GROUP_LIMIT", 16)
    sess.execute("create table gx (k bigint, g bigint, v int)")
    sess.create_distributed_table("gx", "k", shard_count=4)
    sess.execute("insert into gx values " + ",".join(
        f"({i},{i % 80},{i})" for i in range(400)))
    sql = "explain select g, count(*) from gx group by g"
    plain = "\n".join(sess.execute(sql).columns["QUERY PLAN"])
    assert "bucketed group-by" not in plain  # CPU auto pick: sort
    sess.execute("set group_by_kernel = 'bucketed'")
    tagged = "\n".join(sess.execute(sql).columns["QUERY PLAN"])
    assert "bucketed group-by" in tagged


def test_groupby_bucketed_counter(sess, monkeypatch):
    from citus_tpu.planner.plan import DistributedPlanner
    from citus_tpu.stats import counters as sc

    monkeypatch.setattr(DistributedPlanner, "DENSE_GROUP_LIMIT", 16)
    monkeypatch.setattr(G, "GROUP_TILE_SLOTS", 32)
    sess.execute("create table gc (k bigint, g bigint, v int)")
    sess.create_distributed_table("gc", "k", shard_count=4)
    sess.execute("insert into gc values " + ",".join(
        f"({i},{i % 64},{i})" for i in range(300)))
    sess.execute("set group_by_kernel = 'bucketed'")
    before = sess.stats.counters.snapshot()[sc.GROUPBY_BUCKETED_TOTAL]
    sess.execute("select g, count(*) from gc group by g")
    after = sess.stats.counters.snapshot()[sc.GROUPBY_BUCKETED_TOTAL]
    assert after == before + 1


def test_agg_bucket_fault_point_armed(sess, monkeypatch):
    """executor.agg_bucket_fill fires while building the bucketed pack
    (trace time, like executor.plan_cache_fill) and surfaces as a clean
    InjectedFault — the seam the chaos soak also arms."""
    monkeypatch.setattr(G, "GROUP_TILE_SLOTS", 32)
    sess.execute("create table gi (k bigint, g bigint, v int)")
    sess.create_distributed_table("gi", "k", shard_count=4)
    sess.execute("insert into gi values " + ",".join(
        f"({i},{i % 50},{i})" for i in range(200)))
    sess.execute("set group_by_kernel = 'bucketed'")
    plan, _cleanup = sess._plan_select(parse_one(
        "select g, count(*) from gi group by g"))
    _force_bucketed_groupby(plan, [(0, 50, False)])
    with inject("executor.agg_bucket_fill"):
        with pytest.raises(InjectedFault):
            sess.executor.execute_plan(plan)
    # disarmed: the same plan executes cleanly
    result = sess.executor.execute_plan(plan)
    assert result.row_count == 50


def test_explain_analyze_caches_line(sess):
    sess.execute("create table cl (k bigint, v int)")
    sess.create_distributed_table("cl", "k", shard_count=4)
    sess.execute("insert into cl values (1, 10), (2, 20)")
    sql = "explain analyze select k, sum(v) from cl group by k"
    first = "\n".join(sess.execute(sql).columns["QUERY PLAN"])
    assert "Caches: plan-cache hits=" in first
    assert "feed-cache hits=" in first
    # warm re-run of the same statement: the plan cache must HIT now
    second = [line for line in sess.execute(sql).columns["QUERY PLAN"]
              if line.startswith("Caches:")][0]
    assert "plan-cache hits=1 misses=0" in second


def test_stat_activity_cache_columns(sess):
    sess.execute("create table ca (k bigint, v int)")
    sess.create_distributed_table("ca", "k", shard_count=4)
    sess.execute("insert into ca values (1, 10)")
    r = sess.execute("select citus_stat_activity()")
    for col in ("plan_cache_hits", "plan_cache_misses",
                "feed_cache_hits", "feed_cache_misses"):
        assert col in r.column_names
    # the in-flight statement (this citus_stat_activity call) has a
    # fresh baseline: its own deltas are small non-negative ints
    for i in range(r.row_count):
        assert r.columns["plan_cache_hits"][i] >= 0
        assert r.columns["feed_cache_misses"][i] >= 0
