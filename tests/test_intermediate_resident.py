"""An intermediate result (a derived table's, a CTE's, a set operation's,
the percentile shim's rows) lives in memory from the combine to the
outer statement's feed (PR 38): the store holds the typed arrays
(`TableStore.hold_resident`), nothing under `tables/__intermediate_*`
is written, and nothing a dead process left there is read.  On the
parent each result was one durable stripe, written, read back and
deleted, and a leftover directory's stripe was read beside the new
one: wrong rows, silently."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import citus_tpu  # noqa: E402
from citus_tpu.catalog.catalog import (  # noqa: E402
    INTERMEDIATE_PREFIX,
    TEMP_ID_BASE,
)

N = 600


def make_rows() -> dict[str, np.ndarray]:
    rng = np.random.default_rng(38)
    ids = np.arange(N, dtype=np.int64)
    return {
        "id": ids,
        "g": rng.integers(0, 7, N),
        "a": rng.integers(-2**40, 2**40, N),
        "f": np.round(rng.gamma(2.0, 50.0, N), 3),
        "s": np.array([f"name-{v}" for v in rng.integers(0, 23, N)]),
        "d": np.array(["1995-01-01"], dtype="datetime64[D]")
        + rng.integers(0, 400, N),
        # NULL in one row of five
        "n": np.where(ids % 5 == 0, -1, rng.integers(0, 9, N)),
    }


def _sql_value(col: str, v) -> str:
    if col == "n" and v == -1:
        return "null"
    if col == "s":
        return f"'{v}'"
    if col == "d":
        return f"date '{v}'"
    return str(v)


@pytest.fixture(scope="module")
def rows():
    return make_rows()


@pytest.fixture(scope="module", params=(1, 4))
def sess(request, rows, tmp_path_factory):
    s = citus_tpu.connect(
        data_dir=str(tmp_path_factory.mktemp(f"resident{request.param}")),
        n_devices=request.param, serving_result_cache_bytes=0)
    s.execute("create table ev (id bigint, g int, a bigint, "
              "f double precision, s text, d date, n int)")
    s.execute("select create_distributed_table('ev', 'id')")
    cols = list(rows)
    for lo in range(0, N, 200):
        s.execute("insert into ev values " + ", ".join(
            "(" + ", ".join(_sql_value(c, rows[c][i]) for c in cols) + ")"
            for i in range(lo, min(lo + 200, N))))
    yield s
    s.close()


def intermediate_entries(data_dir: str) -> list[str]:
    return [e for e in os.listdir(os.path.join(data_dir, "tables"))
            if e.startswith(INTERMEDIATE_PREFIX)]


class DropWatch:
    """`_drop_temp` wrapped: what `tables/` holds of intermediate
    results while the outer statement's rows are still live (the drop
    runs in its `finally`), and how many results were stored."""

    def __init__(self, sess):
        self.sess = sess
        self.seen: list[str] = []
        self.drops = 0

    def __enter__(self):
        inner = self.sess._drop_temp

        def watched(name):
            self.seen.extend(intermediate_entries(self.sess.data_dir))
            self.drops += 1
            # the rows are the store's, in memory, until this drop
            assert name in self.sess.catalog.tables
            inner(name)

        self.sess._drop_temp = watched
        return self

    def __exit__(self, *exc):
        del self.sess._drop_temp


# -- the four producers ------------------------------------------------------

def ref_derived(r):
    per_g = np.bincount(r["g"], minlength=7)
    cnts, n = np.unique(per_g[per_g > 0], return_counts=True)
    return [(int(c), int(k)) for c, k in zip(cnts, n)]


def ref_cte(r):
    sums = [int(r["a"][r["g"] == g].sum()) for g in np.unique(r["g"])]
    return [(len(sums), sum(sums), max(sums))]


def ref_union(r):
    return [(int(g),) for g in
            np.union1d(r["g"][r["id"] < 40], r["n"][r["n"] > 6])]


def ref_percentile(r):
    """A group's median as a band: the sketch answers for some rank
    beside the middle of its ≈ 86 values, to 1 % of the value (and
    slack at a bucket's edge)."""
    return [(int(g), tuple(np.quantile(r["f"][r["g"] == g], (0.45, 0.55))
                           * (0.985, 1.015)))
            for g in np.unique(r["g"])]


PRODUCERS = {
    "derived_table": (
        "select cnt, count(*) from (select g, count(*) as cnt from ev "
        "group by g) as x group by cnt order by cnt", ref_derived, 1),
    "cte": (
        "with x as (select g, sum(a) as sa from ev group by g) "
        "select count(*), sum(sa), max(sa) from x", ref_cte, 1),
    "union": (
        "select g as k from ev where id < 40 "
        "union select n from ev where n > 6 order by k", ref_union, 1),
    "percentile_shim": (
        "select g, approx_percentile(f, 0.5) from ev group by g order by g",
        ref_percentile, 1),
}


@pytest.mark.parametrize("producer", sorted(PRODUCERS))
def test_no_file_while_the_statement_runs_nor_after(sess, rows, producer):
    sql, reference, stored = PRODUCERS[producer]
    c0 = sess.stats.counters.snapshot()
    with DropWatch(sess) as watch:
        got = sess.execute(sql).rows()
    c1 = sess.stats.counters.snapshot()
    assert watch.drops == stored
    assert watch.seen == []
    assert intermediate_entries(sess.data_dir) == []
    assert c1["intermediate_resident_total"] \
        - c0["intermediate_resident_total"] == stored
    # all of it freed at the drop: arrays, record, dictionaries
    assert sess.store._resident == {}
    assert not [t for t in sess.store._manifests
                if t.startswith(INTERMEDIATE_PREFIX)]
    assert not [k for k in sess.store._dicts
                if k[0].startswith(INTERMEDIATE_PREFIX)]
    want = reference(rows)
    if producer == "percentile_shim":
        assert [g for g, _ in got] == [g for g, _ in want]
        for (_, q), (_, (lo, hi)) in zip(got, want):
            assert lo <= q <= hi
    else:
        assert [tuple(int(v) for v in row) for row in got] == want


# -- column kinds ------------------------------------------------------------

KINDS = {
    "int64": (
        "select g, sum(a), min(a) from (select id, g, a from ev "
        "where id < 500) as x group by g order by g",
        "select g, sum(a), min(a) from ev where id < 500 "
        "group by g order by g"),
    "float": (
        "select g, sum(f), max(f) from (select id, g, f from ev "
        "where id >= 30) as x group by g order by g",
        "select g, sum(f), max(f) from ev where id >= 30 "
        "group by g order by g"),
    "string": (
        "select s, count(*) from (select id, s from ev where id < 450) "
        "as x group by s order by s",
        "select s, count(*) from ev where id < 450 group by s order by s"),
    "date": (
        "select d, count(*) from (select id, d from ev where id < 300) "
        "as x where d >= date '1995-06-01' group by d order by d",
        "select d, count(*) from ev where id < 300 "
        "and d >= date '1995-06-01' group by d order by d"),
    "nulls": (
        "select n, count(*), count(n) from (select id, n from ev) as x "
        "group by n order by n",
        "select n, count(*), count(n) from ev group by n order by n"),
    "zero_rows": (
        "select count(*), sum(a) from (select id, a, s from ev "
        "where id < 0) as x",
        "select count(*), sum(a) from ev where id < 0"),
}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_column_kinds_answer_as_without_the_derived_table(sess, kind):
    with_derived, plain = KINDS[kind]
    want = sess.execute(plain).rows()
    c0 = sess.stats.counters.snapshot()
    with DropWatch(sess) as watch:
        got = sess.execute(with_derived).rows()
    c1 = sess.stats.counters.snapshot()
    assert watch.drops == 1 and watch.seen == []
    assert c1["intermediate_resident_total"] \
        - c0["intermediate_resident_total"] == 1
    if kind == "float":
        assert [r[0] for r in got] == [r[0] for r in want]
        np.testing.assert_allclose(
            np.array([r[1:] for r in got], dtype=float),
            np.array([r[1:] for r in want], dtype=float), rtol=1e-5)
    else:
        assert got == want
    if kind == "zero_rows":
        assert got == [(0, None)]
        assert c1["intermediate_rows_total"] == c0["intermediate_rows_total"]
    else:
        assert len(got) > 1


def test_resident_record_answers_the_planning_readers(sess, rows):
    """The record the store keeps is a stripe record's: row counts,
    column statistics and `read_shard` answer from it, and a staleness
    check or a manifest reload leaves it where it is."""
    store = sess.store
    name = f"{INTERMEDIATE_PREFIX}probe"
    from citus_tpu.types import ColumnDef, DataType, TableSchema

    sess.catalog.create_reference_table(name, TableSchema((
        ColumnDef("k", DataType.INT64), ColumnDef("v", DataType.INT32))))
    try:
        sid = sess.catalog.table_shards(name)[0].shard_id
        assert sid >= TEMP_ID_BASE
        k = np.array([5, 9, 7], dtype=np.int64)
        v = np.array([1, 0, 3], dtype=np.int32)
        valid = {"k": np.ones(3, dtype=bool),
                 "v": np.array([True, False, True])}
        version = store.data_version(name)
        record = store.hold_resident(name, sid, {"k": k, "v": v}, valid)
        assert record["rows"] == 3
        assert record["bytes"] == 3 * (8 + 4 + 1 + 1)
        assert record["stats"] == {"k": [5, 9, 0], "v": [1, 3, 1]}
        assert store.data_version(name) == version + 1
        assert store.refresh_if_stale(name) is False
        with store._lock:
            store._reload_manifest_locked(name)
        assert store.table_row_count(name) == 3
        assert store.shard_row_count(name, sid) == 3
        assert store.shard_size_bytes(name, sid) == record["bytes"]
        assert store.shard_stripe_records(name, sid) == [record]
        assert store.column_range(name, "k") == (5, 9)
        assert store.column_has_nulls(name, "k") is False
        assert store.column_has_nulls(name, "v") is True
        vals, mask, n = store.read_shard(name, sid, ["v"])
        assert n == 3 and list(vals) == ["v"]
        assert vals["v"] is v and mask["v"] is valid["v"]
        (stripe,) = store.iter_shard_stripes(name, sid)
        assert stripe[2] == 3 and stripe[0]["k"] is k
        store.save_dictionaries(name)
        assert intermediate_entries(sess.data_dir) == []
    finally:
        sess._drop_temp(name)
    assert name not in store._resident and name not in store._manifests
    with pytest.raises(citus_tpu.errors.StorageError):
        store.hold_resident("ev", 1, {}, {})


# -- what a killed process left ----------------------------------------------

LEFTOVER_SQL = ("select cnt, count(*) from (select k, count(*) as cnt from t "
                "group by k) as x group by cnt order by cnt")


def leave_intermediate_2(data_dir: str) -> str:
    """`tables/__intermediate_2/` as the parent's process left it when it
    died between `_store_result` and `_drop_temp` of its second
    statement: the stripe of (k, cnt) = (1, 2), (2, 1), (3, 1), the
    manifest that makes it visible, under the second temp shard id."""
    from citus_tpu.storage.format import write_stripe
    from citus_tpu.storage.table_store import _column_stats
    from citus_tpu.types import DataType
    from citus_tpu.utils import io as dio

    sid = TEMP_ID_BASE + 1
    tdir = os.path.join(data_dir, "tables", f"{INTERMEDIATE_PREFIX}2")
    os.makedirs(os.path.join(tdir, f"shard_{sid}"))
    cols = {"k": np.array([1, 2, 3], dtype=np.int32),
            "cnt": np.array([2, 1, 1], dtype=np.int64)}
    valid = {c: np.ones(3, dtype=bool) for c in cols}
    path = os.path.join(tdir, f"shard_{sid}", "stripe_000001.ctps")
    footer = write_stripe(
        path, [("k", DataType.INT32), ("cnt", DataType.INT64)], cols, valid)
    dio.atomic_write_json_checked(
        os.path.join(tdir, "MANIFEST.json"),
        {"next_stripe": 2, "shards": {str(sid): [{
            "file": "stripe_000001.ctps", "rows": footer["row_count"],
            "bytes": os.path.getsize(path),
            "stats": _column_stats(cols, valid)}]}})
    return tdir


def test_a_leftover_directory_is_neither_read_nor_an_error(tmp_path):
    data_dir = str(tmp_path / "data")
    s = citus_tpu.connect(data_dir=data_dir, n_devices=4,
                          serving_result_cache_bytes=0)
    s.execute("create table t (k int, v int)")
    s.execute("select create_distributed_table('t', 'k')")
    s.execute("insert into t values (1,10),(2,20),(3,30),(1,5)")
    s.close()
    tdir = leave_intermediate_2(data_dir)
    s = citus_tpu.connect(data_dir=data_dir, n_devices=4,
                          serving_result_cache_bytes=0)
    try:
        # the parent's second answer: [(1, 4), (2, 2)]
        answers = [[tuple(int(v) for v in r)
                    for r in s.execute(LEFTOVER_SQL).rows()]
                   for _ in range(3)]
        assert answers == [[(1, 2), (2, 1)]] * 3
        # a result of zero rows under the leftover's name reads no
        # manifest either
        s2 = citus_tpu.connect(data_dir=data_dir, n_devices=4,
                               serving_result_cache_bytes=0)
        try:
            s2.execute(LEFTOVER_SQL)
            assert s2.execute(
                "select count(*) from (select k from t where k > 9) as x"
            ).rows() == [(0,)]
        finally:
            s2.close()
    finally:
        s.close()
    # not this session's to remove: it is not what it wrote
    assert os.path.isdir(tdir)
