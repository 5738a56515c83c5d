"""The Star Schema Benchmark on the Citus star layout (`lineorder`
hash-distributed, four reference-table dimensions): all thirteen
statements through `Session.execute`, on one device and on a mesh of
four, held exactly to the benchmark's plain numpy reference
(benchmark/references/ssb.py) on two seeds; the shape of Q4.1's plan and
the counters that say which lookup arm ran and what the feed cache
served; and the data set's seed rule (same shapes, other answers).

Here under the lookup arms SSB SF1 picks (every join on the dense
directory).  tests/test_ssb_sf10.py runs the cases that take the `arms`
fixture again under those SF10 picks (`customer` and `part` by sort and
scan) — in a file of its own because the driver gives a file to one
worker process, and XLA's CPU backend does not survive both sets'
compilations in one process (pytest.ini)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import citus_tpu  # noqa: E402
from benchmark.datasets import ssb  # noqa: E402
from benchmark.references import ssb as ssb_ref  # noqa: E402
from citus_tpu.stats import counters as sc  # noqa: E402

# 300 k fact rows, 1,500 customers, 100 suppliers, 10,000 parts: the
# smallest at which every statement but none of the city pairs of Q3.3
# and Q3.4 returns rows, and at which the planner builds every lookup on
# the dimension's side as it does at SF1
PARAMS = {"scale_factor": 0.05, "shard_count": 8}
SEEDS = (11, 2_147_483_659)  # the second: past 32 signed bits
STATEMENTS = sorted(ssb_ref.QUERIES)


def statement_text(name: str) -> str:
    with open(os.path.join(ROOT, "benchmark", "statements",
                           f"ssb_{name}.json")) as f:
        st = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "statements", st["sql"])) as f:
        return f.read()


def plan_joins(sess, sql: str) -> list:
    from citus_tpu.executor.feed import walk_plan
    from citus_tpu.planner.plan import JoinNode
    from citus_tpu.sql.parser import parse_one

    plan, _cleanup = sess._plan_select(parse_one(sql))
    return [nd for nd in walk_plan(plan.root) if isinstance(nd, JoinNode)]


@pytest.fixture
def arms():
    """How many of Q4.1's four fused lookups sort and scan: none at
    this file's scale, as at SF1 (no key extent reaches
    ops.join.SORTED_LOOKUP_MIN_EXTENT).  tests/test_ssb_sf10.py
    overrides this fixture."""
    return 0


@pytest.fixture(scope="module")
def rows_of():
    """seed -> the generated tables, made once a module."""
    made = {}

    def get(seed: int) -> dict:
        if seed not in made:
            made[seed] = ssb.generate(PARAMS, seed)
        return made[seed]

    return get


@pytest.fixture(scope="module")
def loaded(tmp_path_factory, rows_of):
    """(devices, seed) -> a session over the loaded star schema; each
    made on first use and closed with the module."""
    sessions = {}

    def get(n_devices: int, seed: int):
        key = (n_devices, seed)
        if key not in sessions:
            sess = citus_tpu.connect(
                data_dir=str(tmp_path_factory.mktemp(f"ssb{n_devices}_")),
                n_devices=n_devices, serving_result_cache_bytes=0)
            data = rows_of(seed)
            assert ssb.load(sess, data, PARAMS) == ssb.row_counts(data)
            sessions[key] = sess
        return sessions[key]

    yield get
    for sess in sessions.values():
        sess.close()


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("n_devices", (1, 4))
@pytest.mark.parametrize("name", STATEMENTS)
def test_statement_matches_reference(loaded, rows_of, name, n_devices, seed,
                                     arms):
    sess = loaded(n_devices, seed)
    want = ssb_ref.answer(ssb_ref.QUERIES[name], rows_of(seed))
    rows = sess.execute(statement_text(name)).rows()
    bad, _ = ssb_ref.compare(rows, want, 0.0)
    assert bad == []
    if name not in ("q3_3", "q3_4"):
        assert len(want["c0"]) > 0, "the scale is too small to say anything"


def test_reference_compare_is_exact():
    """One off in a sum, a swapped pair under the order, a float where
    an integer is due and a missing row are each a mismatch; rows tied
    under the order may come either way round."""
    import numpy as np

    ref = {"c0": np.array(["A", "B", "C"]), "c1": np.array([1992, 1992, 1993]),
           "c2": np.array([7, 7, 5], dtype=np.int64),
           "order_cols": np.array([1, 2])}  # order by c1, c2
    good = [("A", 1992, 7), ("B", 1992, 7), ("C", 1993, 5)]
    assert ssb_ref.compare(good, ref)[0] == []
    assert ssb_ref.compare([good[1], good[0], good[2]], ref)[0] == []
    assert ssb_ref.compare([good[0], good[2], good[1]], ref)[0]
    assert ssb_ref.compare(good[:2], ref)[0]
    assert ssb_ref.compare([good[0], good[1], ("C", 1993, 6)], ref)[0]
    assert ssb_ref.compare([good[0], good[1], ("C", 1993, 5.0)], ref)[0]
    assert ssb_ref.compare([good[0], good[0], good[2]], ref)[0]


def test_q4_1_plan_and_counters(loaded, arms):
    """Four broadcast joins, each a fused lookup.  `d_datekey` is
    `yyyymmdd`, 2,556 rows over 61,130 slots at any scale factor, under
    ops.join.SORTED_LOOKUP_MIN_EXTENT like the other three keys at SF1
    — so there the sorted arm is in no SSB plan.  At SF10 `customer` and
    `part` sort and scan, `supplier` and `dwdate` stay on the dense
    directory: both arms in one fragment.  One execution says which in
    the counters, by statement and by join."""
    n_sorted = arms
    sess = loaded(1, SEEDS[0])
    sql = statement_text("q4_1")
    plan = [r[0] for r in sess.execute("explain " + sql).rows()]
    joins = [line for line in plan if "Join" in line]
    assert len(joins) == 4
    assert all("Broadcast Join" in j and "fused lookup" in j for j in joins)
    # the join that keeps the fewest rows first (PR 34; EXPLAIN prints
    # the root first): `supplier` and `customer` keep 1 in 5, `part` 1
    # in 3, the unfiltered `dwdate` every row and is probed last, over
    # what the other three kept
    assert [(j.split(" = ")[1].split(")")[0].split(".")[1],
             j.split("est keep ")[1][:4]) for j in joins] == [
        ("d_datekey", "1.00"), ("p_partkey", "0.33"),
        ("c_custkey", "0.20"), ("s_suppkey", "0.20")]
    assert sum("sorted lookup" in j for j in joins) == n_sorted
    assert sum("dense directory" in j for j in joins) == 4 - n_sorted
    keys = ("c_custkey", "s_suppkey", "p_partkey", "d_datekey")
    by_key = {next(k for k in keys if k in str(nd.right_keys[0])): nd
              for nd in plan_joins(sess, sql)}
    assert {k for k, nd in by_key.items() if nd.lookup_sorted} == (
        {"c_custkey", "p_partkey"} if n_sorted else set())
    date_join = by_key["d_datekey"]
    assert date_join.build_side == "right"
    assert date_join.right_key_extents == (
        (19920101, 19981230 - 19920101 + 1),)
    sess.execute(sql).rows()  # converge capacities before counting
    before = sess.stats.counters.snapshot()
    assert len(sess.execute(sql).rows()) == 35
    after = sess.stats.counters.snapshot()
    moved = {k: after[k] - before[k] for k in (
        sc.LOOKUP_SORTED_TOTAL, sc.LOOKUP_DENSE_TOTAL,
        sc.LOOKUP_SORTED_JOINS_TOTAL, sc.LOOKUP_DENSE_JOINS_TOTAL,
        sc.BROADCAST_JOINS_TOTAL, sc.CAPACITY_RETRIES)}
    # the four probes: the fact feed's 300,160 slots, then what each
    # lookup kept (the parent's order: 300,160 + 90,624 + 2 × 18,560)
    assert after[sc.LOOKUP_PROBE_SLOTS_TOTAL] \
        - before[sc.LOOKUP_PROBE_SLOTS_TOTAL] == 300_160 + 90_624 + 18_560 \
        + 4_992
    assert moved == {sc.LOOKUP_SORTED_TOTAL: min(n_sorted, 1),
                     sc.LOOKUP_DENSE_TOTAL: 1,
                     sc.LOOKUP_SORTED_JOINS_TOTAL: n_sorted,
                     sc.LOOKUP_DENSE_JOINS_TOTAL: 4 - n_sorted,
                     sc.BROADCAST_JOINS_TOTAL: 4, sc.CAPACITY_RETRIES: 0}
    # each arm's operations carry the sub-scope the benchmark's
    # stage_lookup_dense_ms and stage_lookup_sorted_ms read them by
    text = list(sess.executor.plan_cache._entries.values())[-1][0].as_text()
    assert "ct.lookup_join/ct.dense" in text
    assert ("ct.lookup_join/ct.sort" in text) == bool(n_sorted)


def test_q4_1_feeds_stay_resident(loaded, arms):
    """The feed cache answers every scan of a repeated statement: the
    first execution on an empty cache builds the five feeds and counts
    their device bytes as missed, the second is served all of them and
    builds none."""
    sess = loaded(1, SEEDS[0])
    sql = statement_text("q4_1")
    cache = sess.executor.feed_cache
    cache.clear()
    snaps = [sess.stats.counters.snapshot()]
    for _ in range(2):
        sess.execute(sql).rows()
        snaps.append(sess.stats.counters.snapshot())
    (hit1, miss1), (hit2, miss2) = (
        tuple(b[k] - a[k] for k in (sc.FEED_CACHE_HIT_BYTES_TOTAL,
                                    sc.FEED_CACHE_MISS_BYTES_TOTAL))
        for a, b in zip(snaps, snaps[1:]))
    assert len(cache) == 5 and miss1 == cache.total_bytes
    # six int32 fact columns and the validity byte, at the least
    assert miss1 > (6 * 4 + 1) * 300_145
    assert hit1 == 0
    assert (hit2, miss2) == (miss1, 0)


def test_q4_1_program_gathers_what_is_read(loaded):
    """Columns cross a compaction or a lookup as a row index and are
    gathered where they are first read (PR 32).  At this scale the
    first `join_out` compaction packs 300,160 probe slots into 90,624,
    and PR 32's parent gathered six times at that size: the five
    fact columns through the compaction and the next lookup's probe.
    Now two: the one column read at that size (the next join's key) and
    that probe — the other four cross the next compaction as an index.
    Each statement says so in the two counters.  At the second
    compaction's 18,560 slots six gathers became four when the
    unfiltered `dwdate` moved to the end of the join order (PR 34): its
    probe and `lo_orderdate` are read at the third's 4,992."""
    import re

    sess = loaded(1, SEEDS[0])
    sql = statement_text("q4_1")
    sess.execute(sql).rows()  # converge capacities
    before = sess.stats.counters.snapshot()
    sess.execute(sql).rows()
    after = sess.stats.counters.snapshot()
    carried, gathered = (after[k] - before[k] for k in (
        sc.DEFERRED_COLUMNS_TOTAL, sc.DEFERRED_GATHERS_TOTAL))
    assert carried > gathered > 0
    text = list(sess.executor.plan_cache._entries.values())[-1][0].as_text()
    sizes = [int(m.group(1)) for m in re.finditer(
        r"= \w+\[(\d+)(?:,1)?\]\S* gather\(", text)]
    first_compaction = 90_624
    assert "ct.join_out/ct.compact" in text
    assert f"[{first_compaction}]" in text
    assert sizes.count(first_compaction) == 2    # PR 32's parent: 6
    assert max(sizes) == 300_160                 # the first probe, alone
    assert {n: sizes.count(n) for n in (300_160, 90_624, 18_560)} == {
        300_160: 1, 90_624: 2, 18_560: 4}        # PR 34's parent: 1, 2, 6
    # every gather of a carried column stands under the sub-scope the
    # benchmark's stage_deferred_ms reads
    assert "ct.join_out/ct.deferred" in text
    assert "ct.agg_grid/ct.deferred" in text
    assert not re.search(r"ct\.compact/[^\"]*gather", text)


def test_seeds_share_shapes_not_answers(loaded, rows_of):
    """`--seed` permutes the fact table's measure tuples: row counts,
    key extents and the plan (its fingerprint) stay, every answer
    moves."""
    import numpy as np

    from citus_tpu.executor.cache import node_fingerprint
    from citus_tpu.sql.parser import parse_one

    a, b = (rows_of(s) for s in SEEDS)
    assert ssb.row_counts(a) == ssb.row_counts(b)
    for table, cols in a.items():
        for col, arr in cols.items():
            if col in ssb.MEASURES:
                assert (np.sort(arr) == np.sort(b[table][col])).all()
            if col in ssb.MEASURES or col == "lo_ordtotalprice":
                assert (arr != b[table][col]).any()
            else:
                assert (arr == b[table][col]).all(), col
    sa, sb = (loaded(1, s) for s in SEEDS)
    sql = statement_text("q4_1")
    prints = [node_fingerprint(sess._plan_select(parse_one(sql))[0].root)
              for sess in (sa, sb)]
    assert prints[0] == prints[1]
    extents = [[(nd.left_key_extents, nd.right_key_extents)
                for nd in plan_joins(sess, sql)] for sess in (sa, sb)]
    assert extents[0] == extents[1]
    assert sa.execute(sql).rows() != sb.execute(sql).rows()
