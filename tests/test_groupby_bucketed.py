"""Bucketed dense-grid aggregation (ops/groupby.py): end-to-end tests.

Oracle contract: with `group_by_kernel` forced onto the bucketed path,
every GROUP BY shape must return exactly what the sort path returns —
nulls form their own groups, filtered-out rows never contribute,
all-duplicate keys collapse to one group, empty inputs yield zero
groups, under every distribution of the key over the buckets (the
pack is sized by the rows: no per-bucket capacity, no retry).  Stale
planner key ranges retry onto the sort path (dense_oob protocol), and
the observability surfaces (EXPLAIN tag, groupby_bucketed_total counter,
EXPLAIN ANALYZE "Caches:" line, citus_stat_activity cache columns,
executor.agg_bucket_fill fault point) all show the path."""

import pytest

import citus_tpu
import citus_tpu.ops.groupby as G
from citus_tpu.executor.feed import build_feeds, walk_plan
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


# key distributions over the buckets of a 5-tile slot space (TILE = 64
# slots; keys 0..299 in slots 1..300 and the NULL key, None, in slot 0:
# tiles 0..4), as (g, rows with that key) pairs.  Chunks of CHUNK rows.
TILE, CHUNK, EXTENT = 64, 16, 300
DISTRIBUTIONS = {
    # (a) every row in one bucket, the null group with them
    "one_bucket": ([(None, 40), (0, 500), (7, 3), (62, 61)], CHUNK),
    # (b) every row in the LAST bucket
    "last_bucket": ([(255, 200), (298, 1), (299, 77)], CHUNK),
    # (c) buckets holding exactly C, C + 1, C - 1 and 0 rows (tile 3
    # stays empty)
    "chunk_edges": ([(3, CHUNK), (70, CHUNK + 1), (130, 5),
                     (131, CHUNK - 6), (290, 2)], CHUNK),
    # (d) uniform keys
    "uniform": ([(g, 3) for g in range(EXTENT)] + [(None, 3)], CHUNK),
    # (e) an input whose slots are no whole number of chunks (feeds are
    # cut in 128-row classes, and 48 divides no such size below 384)
    "ragged_input": ([(g, 1 + g % 5) for g in range(0, EXTENT, 3)], 48),
    # the default chunk, far over the input: one chunk a bucket
    "default_chunk": ([(g, 2) for g in range(EXTENT)] + [(None, 9)], None),
}


@pytest.fixture()
def sess1(tmp_path):
    """One device and float32 on it, so that a bucket's count is what
    the case says and sum(f) takes the one-hot matmul."""
    s = citus_tpu.connect(data_dir=str(tmp_path / "d1"), n_devices=1,
                          compute_dtype="float32")
    yield s
    s.close()


def _close(got, want):
    """Row sets equal, floats to the kernel's own tolerance
    (tests/test_ops.py: rtol 1e-4, atol 1e-4)."""
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert len(g) == len(w)
        for a, b in zip(g, w):
            if isinstance(b, float):
                assert a == pytest.approx(b, rel=1e-4, abs=1e-4)
            else:
                assert a == b


@pytest.mark.parametrize("where", ["", " where v > 100000"],
                         ids=["rows", "all_invalid"])
@pytest.mark.parametrize("case", list(DISTRIBUTIONS))
def test_chunked_grid_matches_sort_path(sess1, monkeypatch, case, where):
    """The chunked grid against the sort path, value for value: count,
    int64 sum, float32 sum, min, max over a nullable key, under each
    distribution of the key over the buckets — and (f) with every row
    filtered out."""
    dist, chunk = DISTRIBUTIONS[case]
    monkeypatch.setattr(G, "GROUP_TILE_SLOTS", TILE)
    if chunk is not None:
        monkeypatch.setattr(G, "GROUP_CHUNK_ROWS", chunk)
    sess1.execute("create table gq (k bigint, g bigint, v int, f float)")
    sess1.create_distributed_table("gq", "k", shard_count=2)
    rows, k = [], 0
    for g, count in dist:
        for _ in range(count):
            v = "null" if k % 11 == 0 else k % 97 - 40
            rows.append(f"({k},{'null' if g is None else g},{v},"
                        f"{(k % 23) * 0.25})")
            k += 1
    sess1.execute("insert into gq values " + ",".join(rows))
    sql = ("select g, count(*), count(v), sum(v), sum(f), min(v), max(v) "
           f"from gq{where} group by g")
    sess1.execute("set group_by_kernel = 'sort'")
    want = _rows(sess1, sql)
    assert bool(want) == (where == "")
    sess1.execute("set group_by_kernel = 'bucketed'")
    plan, _cleanup = sess1._plan_select(parse_one(sql))
    _force_bucketed_groupby(plan, [(0, EXTENT, True)])
    result = sess1.executor.execute_plan(plan)
    assert result.retries == 0
    _close(_sorted(result.rows()), want)


def test_hot_bucket_compiles_once_and_never_retries(sess, monkeypatch):
    """Extreme skew: nearly every row lands in ONE slot's bucket.  The
    pack is sized by the input's slots, not by its fullest bucket, so
    the statement compiles one program, returns at its first execution
    and counts n_dev x (NC x C) packed slots, whatever the key does."""
    from citus_tpu.executor.execcache import exec_cache_for
    from citus_tpu.stats import counters as sc

    monkeypatch.setattr(G, "GROUP_TILE_SLOTS", 16)
    monkeypatch.setattr(G, "GROUP_CHUNK_ROWS", 32)
    sess.execute("set group_by_kernel = 'bucketed'")
    sess.execute("create table gh (k bigint, g bigint, v int)")
    sess.create_distributed_table("gh", "k", shard_count=4)
    rows = [f"({i},7,1)" for i in range(3000)]
    rows += [f"({10000 + i},{i % 120},1)" for i in range(120)]
    sess.execute("insert into gh values " + ",".join(rows))
    sql = "select g, count(*) from gh group by g"
    plan, _cleanup = sess._plan_select(parse_one(sql))
    _force_bucketed_groupby(plan, [(0, 120, False)])
    cache = exec_cache_for(sess.executor.store.data_dir)
    compiles0 = cache.snapshot()["compiles_total"]
    misses0 = sess.executor.plan_cache.misses
    slots0 = sess.stats.counters.snapshot()[sc.AGG_BUCKET_SLOTS_TOTAL]
    result = sess.executor.execute_plan(plan)
    assert result.retries == 0
    assert cache.snapshot()["compiles_total"] == compiles0 + 1
    assert sess.executor.plan_cache.misses == misses0 + 1
    got = dict(tuple(r) for r in result.rows())
    assert got[7] == 3000 + 1  # skewed rows + one spread row (7 % 120)
    assert sum(got.values()) == 3120
    # the group-by reads the scan's feed: n slots a device, 8 tiles
    ex = sess.executor
    (feed,) = build_feeds(plan, ex.catalog, ex.store, ex.mesh).values()
    nc, chunk = G.group_pack_shape(feed.capacity, 8)
    assert (nc, chunk) == (-(-feed.capacity // 32) + 8, 32)
    slots = sess.stats.counters.snapshot()[sc.AGG_BUCKET_SLOTS_TOTAL]
    assert slots - slots0 == 4 * nc * chunk


def test_buffer_estimate_counts_the_chunked_pack(sess, monkeypatch):
    """The guard's estimate (`max_plan_buffer_bytes`, the regrow
    budget) sees the pack at its input's slots in whole chunks and a
    chunk a tile more — and only where the bucketed grid runs."""
    from citus_tpu.executor.compiler import Capacities
    from citus_tpu.executor.runner import _plan_buffer_bytes

    monkeypatch.setattr(G, "GROUP_TILE_SLOTS", 16)
    monkeypatch.setattr(G, "GROUP_CHUNK_ROWS", 32)
    sess.execute("create table gb (k bigint, g bigint, v int)")
    sess.create_distributed_table("gb", "k", shard_count=4)
    plan, _cleanup = sess._plan_select(parse_one(
        "select g, count(*) from gb group by g"))
    _force_bucketed_groupby(plan, [(0, 120, False)])  # 121 slots: 8 tiles
    (agg,) = [n for n in walk_plan(plan.root)
              if isinstance(n, AggregateNode)]
    caps = Capacities({}, {}, {}, False, {id(agg.input): 1024})
    nc, chunk = G.group_pack_shape(1024, 8)
    assert (nc, chunk) == (1024 // 32 + 8, 32)
    width = (len(agg.out_columns) + 2) * 8 * plan.n_devices
    assert _plan_buffer_bytes(plan, caps, "bucketed") == nc * chunk * width
    assert _plan_buffer_bytes(plan, caps, "sort") < 1024 * width


def test_retired_bucket_capacity_setting_is_unknown(sess):
    """The per-bucket capacity's setting left with it: SET refuses it
    as it refuses any name that was never registered."""
    from citus_tpu import config
    from citus_tpu.errors import ConfigError

    name = "agg_bucket_capacity_factor"
    assert name not in config.registered_vars()
    assert len(config.registered_vars()) == 64
    with pytest.raises(ConfigError, match="unrecognized configuration"):
        sess.execute(f"set {name} = 2.0")


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
