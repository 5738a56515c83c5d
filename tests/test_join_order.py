"""The order in which `_plan_inner_joins` joins the relations of a
statement (PR 34): strategy rank first, as ever; within one rank the
candidate whose join keeps the fewest rows, by the one estimate that
also sizes the join (`DistributedPlanner._estimate_join`); ties to the
smaller build side, then to the relation index.

The parent ranked candidates of one rank by their filtered build side
alone, so SSB Q4.1's order moved with the absolute size of the
dimensions (build `est_rows` in brackets):

    SF0.05  supplier (20), customer (300), dwdate (2,556), part (3,333)
    SF1     supplier (400), dwdate (2,556), customer (6,000), part (66,666)
    SF10    dwdate (2,556), supplier (4,000), customer (60,000),
            part (266,666)

and the unfiltered calendar, which removes no row, was probed over
1.8 M slots at SF1 and over all 60.0 M at SF10.  No test saw it: the
tier-1 files plan at SF0.05.  Here SF1 and SF10 are planned from the
paper's row counts without loading them."""

import pytest
from test_ssb import PARAMS as SSB_PARAMS  # sets sys.path for `benchmark`
from test_ssb import STATEMENTS as SSB_STATEMENTS
from test_ssb import statement_text as ssb_text

import citus_tpu
from benchmark.datasets import ssb
from citus_tpu.executor.cache import node_fingerprint
from citus_tpu.executor.feed import walk_plan
from citus_tpu.ingest import tpch
from citus_tpu.planner.plan import JoinNode, ScanNode
from citus_tpu.sql.parser import parse_one
from citus_tpu.stats import counters as sc

# a dimension's key and the fact column that refers to it: 1..rows at
# any scale (`dwdate`'s is yyyymmdd, 61,130 slots at any scale)
KEYS = {"c_custkey": "customer", "lo_custkey": "customer",
        "s_suppkey": "supplier", "lo_suppkey": "supplier",
        "p_partkey": "part", "lo_partkey": "part"}


def plan_of(sess, sql: str):
    return sess._plan_select(parse_one(sql))[0]


def join_order(plan) -> list:
    """[(table, strategy) ...]: the relation the joins start from, then
    each joined relation with its join's strategy, in join order."""
    node = next(nd for nd in walk_plan(plan.root) if isinstance(nd, JoinNode))
    joined = []
    while isinstance(node, JoinNode):
        # left-deep: one child is the join below (or the first scan)
        below, new = ((node.right, node.left)
                      if isinstance(node.right, JoinNode)
                      else (node.left, node.right))
        joined.append((new, node.strategy))
        node = below
    return [(node.rel.table, None)] + [
        (scan.rel.table, strategy) for scan, strategy in reversed(joined)]


def tables(plan) -> list:
    return [t for t, _ in join_order(plan)]


@pytest.fixture(scope="module")
def star(tmp_path_factory):
    sess = citus_tpu.connect(
        data_dir=str(tmp_path_factory.mktemp("order_ssb")),
        n_devices=1, serving_result_cache_bytes=0)
    ssb.load(sess, ssb.generate(SSB_PARAMS, 11), SSB_PARAMS)
    yield sess
    sess.close()


@pytest.fixture(scope="module")
def tpch_on(tmp_path_factory):
    """devices -> a session over TPC-H at SF 0.002."""
    sessions = {}

    def get(n_devices: int):
        if n_devices not in sessions:
            sess = citus_tpu.connect(
                data_dir=str(tmp_path_factory.mktemp(f"order_h{n_devices}_")),
                n_devices=n_devices, serving_result_cache_bytes=0)
            tpch.load_into_session(sess, sf=0.002, seed=7, shard_count=8)
            sessions[n_devices] = sess
        return sessions[n_devices]

    yield get
    for sess in sessions.values():
        sess.close()


@pytest.fixture
def at_scale(monkeypatch):
    """at_scale(sf): the planner's statistics say SSB at `sf` by the
    paper's row counts — tables and the key extents that follow from
    them; every other extent and distinct count stays the loaded
    SF0.05 session's.  Nothing is loaded."""
    from citus_tpu.session import _StoreStats

    real_rows, real_extent = _StoreStats.table_rows, _StoreStats.column_extent

    def set_scale(sf: float) -> None:
        rows = dict(ssb.table_rows(sf), lineorder=int(6_000_000 * sf))
        monkeypatch.setattr(
            _StoreStats, "table_rows",
            lambda self, table: rows.get(table) or real_rows(self, table))
        monkeypatch.setattr(
            _StoreStats, "column_extent",
            lambda self, table, column, dtype: (
                (1, rows[KEYS[column]]) if column in KEYS
                else real_extent(self, table, column, dtype)))

    return set_scale


@pytest.mark.parametrize("sf", (None, 1.0, 10.0),
                         ids=("sf0.05-loaded", "sf1", "sf10"))
def test_q4_1_order_is_one_at_every_scale(star, at_scale, sf):
    """`supplier` (keeps 1 in 5), `customer` (1 in 5, the larger build
    side), `part` (1 in 3 by the OR's default), `dwdate` (keeps all):
    the parent's three orders are in this file's docstring."""
    if sf is not None:
        at_scale(sf)
    plan = plan_of(star, ssb_text("q4_1"))
    assert tables(plan) == ["lineorder", "supplier", "customer", "part",
                            "dwdate"]
    joins = [nd for nd in walk_plan(plan.root) if isinstance(nd, JoinNode)]
    assert all(nd.fuse_lookup and nd.strategy == "broadcast" for nd in joins)
    # the estimate that ordered them is the one on the nodes: the
    # fraction each lookup keeps, the rows that sizes its output
    keeps = [round(nd.est_keep, 2) for nd in reversed(joins)]
    assert keeps == [0.2, 0.2, 0.33, 1.0]
    rows = [nd.est_rows for nd in reversed(joins)]
    assert rows == sorted(rows, reverse=True) and rows[2] == rows[3]
    if sf is not None:
        fact = int(6_000_000 * sf)
        assert rows[:2] == [fact // 5, fact // 25]


@pytest.mark.parametrize("sf", (None, 1.0, 10.0),
                         ids=("sf0.05-loaded", "sf1", "sf10"))
@pytest.mark.parametrize("name", SSB_STATEMENTS)
def test_unfiltered_dimension_is_never_probed_before_a_filtered_one(
        star, at_scale, name, sf):
    if sf is not None:
        at_scale(sf)
    plan = plan_of(star, ssb_text(name))
    scans = {nd.rel.table: nd for nd in walk_plan(plan.root)
             if isinstance(nd, ScanNode)}
    order = tables(plan)
    assert order[0] == "lineorder" and set(order) == set(scans)
    filtered = [scans[t].filter is not None for t in order[1:]]
    # every filtered dimension first, then the unfiltered ones
    assert filtered == sorted(filtered, reverse=True), order


def test_tie_falls_to_build_size_then_relation_index(star):
    """Q4.1's `supplier` and `customer` each keep 1 in 5, so the rows
    out tie and the smaller build side goes first; two lookups of one
    calendar tie in everything but the relation index, which follows
    the FROM list.  Planning twice gives one fingerprint."""
    plan = plan_of(star, ssb_text("q4_1"))
    first, second = [nd for nd in walk_plan(plan.root)
                     if isinstance(nd, JoinNode)][-1:-3:-1]
    assert first.est_keep == second.est_keep == 0.2
    assert first.right.est_rows < second.right.est_rows
    assert (first.right.rel.table, second.right.rel.table) == (
        "supplier", "customer")
    two = ("select count(*) from lineorder, dwdate {a}, dwdate {b} "
           "where lo_orderdate = d1.d_datekey "
           "and lo_commitdate = d2.d_datekey")
    for a, b in (("d1", "d2"), ("d2", "d1")):
        plan = plan_of(star, two.format(a=a, b=b))
        keys = [str(nd.left_keys[0]) for nd in walk_plan(plan.root)
                if isinstance(nd, JoinNode)]
        # root first: the FROM list's second calendar is joined last
        assert ["lo_commitdate" in k for k in keys] == [b == "d2", a == "d2"]
    for sql in (ssb_text("q4_1"), two.format(a="d1", b="d2")):
        prints = {node_fingerprint(plan_of(star, sql).root)
                  for _ in range(2)}
        assert len(prints) == 1


@pytest.mark.parametrize("n_devices", (1, 4))
def test_q3_keeps_its_order(tpch_on, n_devices):
    """`orders` is the only relation with an edge to `lineitem`, so
    strategy rank decides before the estimate is read."""
    order = join_order(plan_of(tpch_on(n_devices), tpch.Q3))
    assert order == [("lineitem", None), ("orders", "local"),
                     ("customer", "local" if n_devices == 1
                      else "repart_left")]


def test_strategy_rank_precedes_the_estimate(tpch_on):
    """On four devices `orders` joins `lineitem` where it lies and
    keeps every row; `supplier` would keep 1 in 3 and must be
    repartitioned for: the co-located join still comes first, so a
    reorder cannot add a shuffle.  On one device both are local joins
    and the selective one goes first."""
    sql = ("select count(*) from lineitem, orders, supplier "
           "where l_orderkey = o_orderkey and l_suppkey = s_suppkey "
           "and s_acctbal < 0")
    assert join_order(plan_of(tpch_on(4), sql)) == [
        ("lineitem", None), ("orders", "local"), ("supplier", "repart_left")]
    assert join_order(plan_of(tpch_on(1), sql)) == [
        ("lineitem", None), ("supplier", "local"), ("orders", "local")]


def test_selective_relation_is_repartitioned_for_first(tpch_on):
    """Within the repartition rank too: on four devices `part` and
    `supplier` each need `lineitem` shuffled to them; `part` keeps the
    rows of a few names, `supplier` all, so the second shuffle moves
    what `part` kept (TPC-H Q9's inner block: 7.63 MB of all_to_all a
    statement became 3.14 MB at SF 0.005).  The parent shuffled for
    `supplier`, the smaller build side, first."""
    sql = ("select count(*) from lineitem, part, supplier "
           "where l_partkey = p_partkey and l_suppkey = s_suppkey "
           "and p_name like '%green%'")
    plan = plan_of(tpch_on(4), sql)
    assert join_order(plan) == [
        ("lineitem", None), ("part", "repart_left"),
        ("supplier", "repart_left")]
    part, supplier = (nd.right for nd in reversed(
        [nd for nd in walk_plan(plan.root) if isinstance(nd, JoinNode)]))
    assert supplier.est_rows < part.est_rows  # the parent's criterion


def test_non_unique_build_side_is_ranked_by_its_expansion(tpch_on):
    """`partsupp` holds four rows a part key: joined on `ps_partkey`
    alone it is no lookup and multiplies the rows by four, so `orders`
    — the larger build side, which the parent therefore joined second
    — goes first: it keeps every row, and not four for each."""
    from citus_tpu.session import _StoreDicts, _StoreStats

    sess = tpch_on(1)
    sql = ("select count(*) from lineitem, partsupp, orders "
           "where l_partkey = ps_partkey and l_orderkey = o_orderkey")
    plan = plan_of(sess, sql)
    assert tables(plan) == ["lineitem", "orders", "partsupp"]
    by_table = {nd.right.rel.table: nd for nd in walk_plan(plan.root)
                if isinstance(nd, JoinNode)}
    fanned, looked_up = by_table["partsupp"], by_table["orders"]
    assert not fanned.fuse_lookup and fanned.est_keep is None
    assert fanned.est_expansion == pytest.approx(4.0, rel=0.05)
    assert fanned.right.est_rows < looked_up.right.est_rows
    assert looked_up.fuse_lookup and looked_up.est_keep == 1.0
    # the helper's answer for the candidate the loop turned down at its
    # first step is what ranked it
    planner = citus_tpu.planner.plan.DistributedPlanner(
        sess.catalog, _StoreStats(sess.store), 1, True,
        dicts=_StoreDicts(sess.store))
    lineitem = looked_up.left
    est = planner._estimate_join(lineitem, fanned.right, fanned.left_keys,
                                 fanned.right_keys)
    assert est.keep is None and not est.fuse_lookup
    assert est.rows == int(lineitem.est_rows * est.expansion)
    assert est.rows > looked_up.est_rows == lineitem.est_rows


def _moved(sess, sql: str, name: str) -> int:
    sess.execute(sql).rows()  # converge capacities before counting
    before = sess.stats.counters.snapshot()
    sess.execute(sql).rows()
    return sess.stats.counters.snapshot()[name] - before[name]


def test_probe_slots_counter_sums_q4_1s_four_probes(star):
    """`lookup_probe_slots_total` moves by the probe side's static size
    at each of the converged program's four lookups: the widths its
    compiler recorded for the `join_out` stages, whose first is the
    whole fact feed."""
    moved = _moved(star, ssb_text("q4_1"), sc.LOOKUP_PROBE_SLOTS_TOTAL)
    entry = list(star.executor.plan_cache._entries.values())[-1]
    widths = [w for _, kind, w in entry[2] if kind == "join_out"]
    assert len(widths) == 4 and max(widths) == 300_160
    assert moved == sum(widths) == entry[4][2]
    # each lookup probes what the ones before it kept (root first here)
    assert widths == sorted(widths) and widths[-2] < widths[-1]


def test_probe_slots_counter_stays_on_a_statement_without_a_join(tpch_on):
    assert _moved(tpch_on(1), tpch.Q1, sc.LOOKUP_PROBE_SLOTS_TOTAL) == 0
