"""A statement that materializes subplans (derived table, CTE, set
operation) finds its outer program, its converged capacities and its
executable-cache entry again: the plan fingerprint knows an intermediate
result by its schema (planner/bind.py `BoundRel.identity`) and not by
the counter its temp table is named from (PR 35).  On the parent every
execution of such a statement compiled a program."""

import os
import sys
import threading

import jax.monitoring
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import citus_tpu  # noqa: E402
from benchmark.datasets import tpch_zipf  # noqa: E402
from benchmark.references import tpch_q13  # noqa: E402
from citus_tpu.executor.execcache import exec_cache_for  # noqa: E402
from citus_tpu.ingest.tpch import Q13, Q15  # noqa: E402

# z = 1.5: the planner's first capacities are far off what the hot key
# needs, so capacity feedback sizes a second program — the converged
# sizes have to be found again too
PARAMS = {"scale_factor": 0.02, "shard_count": 8, "zipf_z": 1.5,
          "special_share": 0.01,
          "tables": ["region", "nation", "supplier", "customer", "orders",
                     "lineitem"]}
UNION = """select c_nationkey as k from customer where c_custkey < 200
union select s_nationkey from supplier order by k"""
NESTED = """select cnt, count(*) as n
from (select o_custkey, count(*) as cnt
      from (select o_custkey, o_orderkey from orders
            where o_orderdate < date '1996-01-01') as early
      group by o_custkey) as per_customer
group by cnt order by cnt"""
STATEMENTS = {"q13": Q13, "q15_cte": Q15, "union": UNION, "nested": NESTED}

_XLA_COMPILES = [0]


def _on_event(event: str, duration: float, **kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _XLA_COMPILES[0] += 1


jax.monitoring.register_event_duration_secs_listener(_on_event)


@pytest.fixture(scope="module")
def data():
    return tpch_zipf.generate(PARAMS, 5)


@pytest.fixture(scope="module", params=(1, 4))
def sess(request, data, tmp_path_factory):
    s = citus_tpu.connect(
        data_dir=str(tmp_path_factory.mktemp(f"reuse{request.param}")),
        n_devices=request.param, serving_result_cache_bytes=0)
    tpch_zipf.load(s, data, PARAMS)
    yield s
    s.close()


def program_state(sess) -> tuple:
    return (_XLA_COMPILES[0],
            exec_cache_for(sess.data_dir).snapshot()["compiles_total"],
            sess.stats.counters.snapshot()["capacity_retries"])


@pytest.mark.parametrize("name", sorted(STATEMENTS))
def test_third_execution_on_compiles_nothing(sess, name):
    sql = STATEMENTS[name]
    first = sess.execute(sql).rows()
    sess.execute(sql)
    before = program_state(sess)
    subplans0 = sess.stats.counters.snapshot()["subplans_executed"]
    for _ in range(4):  # executions 3 to 6
        assert sess.execute(sql).rows() == first
    assert program_state(sess) == before
    # … and every one of them ran its subplans again
    assert sess.stats.counters.snapshot()["subplans_executed"] \
        >= subplans0 + 4


def test_q13_answer_under_reuse_is_the_reference(sess, data):
    ref = tpch_q13.build(data)
    for _ in range(3):
        assert tpch_q13.compare(sess.execute(Q13).rows(), ref, 0.0)[0] == []


def test_intermediates_are_counted(sess, data):
    c0 = sess.stats.counters.snapshot()
    sess.execute(Q13)
    c1 = sess.stats.counters.snapshot()
    n_customers = len(data["customer"]["c_custkey"])
    assert c1["intermediate_rows_total"] - c0["intermediate_rows_total"] \
        == n_customers
    # c_custkey and c_count as int64, a validity byte each
    assert c1["intermediate_bytes_total"] - c0["intermediate_bytes_total"] \
        == n_customers * (8 + 8 + 1 + 1)
    # the one result, handed over in memory: as many as subplans ran
    assert c1["intermediate_resident_total"] \
        - c0["intermediate_resident_total"] == 1 \
        == c1["subplans_executed"] - c0["subplans_executed"]
    assert c1["dict_predicate_walks_total"] == c0["dict_predicate_walks_total"]
    if sess.n_devices > 1:
        rows = c1["repartition_rows_total"] - c0["repartition_rows_total"]
        hot = c1["repartition_hot_bucket_rows_total"] \
            - c0["repartition_hot_bucket_rows_total"]
        ref = tpch_q13.build(data)
        kept, groups = int(ref["orders_kept"][0]), len(ref["c_count"])
        # every order the filter keeps crosses the inner program's
        # exchange; the outer program's combine exchanges its groups
        assert kept < rows <= kept + sess.n_devices * groups
        assert kept / 16 < hot <= kept + groups


def test_two_threads_get_their_own_rows(sess, data):
    """Two live intermediates of one shape never share a table, a feed
    or a cached program's rows."""
    custkey = data["orders"]["o_custkey"]
    sql = ("select count(*) from (select o_custkey from orders "
           "where o_custkey < {}) as t")
    bounds = {"a": 40, "b": 900}
    want = {k: int((custkey < b).sum()) for k, b in bounds.items()}
    assert want["a"] != want["b"]
    got: dict[str, list] = {k: [] for k in bounds}
    errors = []

    def work(key: str) -> None:
        try:
            for _ in range(6):
                got[key].append(
                    sess.execute(sql.format(bounds[key])).rows()[0][0])
        except Exception as e:  # surfaced below
            errors.append(e)

    threads = [threading.Thread(target=work, args=(k,)) for k in bounds]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert got == {k: [want[k]] * 6 for k in bounds}


def test_insert_is_seen_by_the_next_execution(sess, data):
    """An intermediate whose rows changed is never answered from a feed
    or a result of the rows it held before."""
    counts = np.bincount(data["orders"]["o_custkey"],
                         minlength=len(data["customer"]["c_custkey"]) + 1)
    lonely = int(np.flatnonzero(counts[1:] == 0)[0]) + 1
    before = dict(sess.execute(Q13).rows())
    sess.execute("insert into orders (o_orderkey, o_custkey, o_comment) "
                 f"values (999999999, {lonely}, 'one more order')")
    after = dict(sess.execute(Q13).rows())
    assert after[0] == before[0] - 1
    assert after[1] == before[1] + 1
    assert {k: v for k, v in after.items() if k > 1} \
        == {k: v for k, v in before.items() if k > 1}
