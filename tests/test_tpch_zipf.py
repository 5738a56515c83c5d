"""Dataset `tpch_zipf` (benchmark/datasets/tpch_zipf.py) and TPC-H Q13
over it, against the benchmark's plain reference
(benchmark/references/tpch_q13.py): what the cell `tpch4z.q13` runs at
SF1 on four chips, here at SF 0.05 on the CPU."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import citus_tpu  # noqa: E402
from benchmark.datasets import tpch, tpch_zipf  # noqa: E402
from benchmark.references import tpch_q13  # noqa: E402

SCALE = 0.05
SEEDS = (3, 2_147_483_777, 11)
Q13 = open(os.path.join(ROOT, "benchmark", "statements",
                        "tpch_q13.sql")).read()


def params(z: float) -> dict:
    return {"scale_factor": SCALE, "shard_count": 8, "zipf_z": z,
            "special_share": 0.01,
            "tables": ["region", "nation", "customer", "orders"]}


@pytest.fixture(scope="module")
def by_seed():
    return {seed: tpch_zipf.generate(params(1.0), seed) for seed in SEEDS}


def test_structure_is_the_same_on_every_seed(by_seed):
    """Counts, the key multiset, the dictionary's length, the special
    text's code and the special orders' count do not move with the
    seed: every seed runs the same programs."""
    first = by_seed[SEEDS[0]]
    n_orders = len(first["orders"]["o_orderkey"])
    assert tpch_zipf.row_counts(first) == {
        "region": 5, "nation": 25, "customer": 7500, "orders": 75000}
    for seed in SEEDS[1:]:
        data = by_seed[seed]
        assert tpch_zipf.row_counts(data) == tpch_zipf.row_counts(first)
        # not only the multiset: the very column
        assert np.array_equal(data["orders"]["o_custkey"],
                              first["orders"]["o_custkey"])
    chosen = set()
    for data in by_seed.values():
        comments = data["orders"]["o_comment"]
        special = comments == tpch_zipf.SPECIAL_COMMENT
        assert special.sum() == round(0.01 * n_orders)
        # first value interned: code 0 on every seed
        assert comments[0] == tpch_zipf.SPECIAL_COMMENT
        assert len(set(comments)) == n_orders - special.sum() + 1
        chosen.add(tuple(np.flatnonzero(special)))
    assert len(chosen) == len(SEEDS)  # WHICH orders: the seed's draw


def test_the_data_set_is_independent_of_the_program():
    """The yardstick's rows may not follow program code: the module
    imports nothing of `citus_tpu`."""
    import ast

    tree = ast.parse(open(tpch_zipf.__file__).read())
    imported = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                for a in n.names}
    imported |= {n.module for n in ast.walk(tree)
                 if isinstance(n, ast.ImportFrom)}
    assert not [m for m in imported if m.startswith("citus_tpu")]


def test_everything_else_is_tpch_row_for_row(by_seed):
    seed = SEEDS[1]
    base = tpch.generate(params(1.0), seed)
    data = by_seed[seed]
    assert set(data) == set(base)
    for table, cols in base.items():
        for col, arr in cols.items():
            if (table, col) in (("orders", "o_custkey"),
                                ("orders", "o_comment")):
                continue
            assert np.array_equal(data[table][col], arr), (table, col)
    plain = data["orders"]["o_comment"] != tpch_zipf.SPECIAL_COMMENT
    assert np.array_equal(data["orders"]["o_comment"][plain],
                          base["orders"]["o_comment"][plain])


def test_every_seed_gives_another_answer(by_seed):
    answers = set()
    for data in by_seed.values():
        ref = tpch_q13.build(data)
        answers.add((tuple(ref["c_count"]), tuple(ref["custdist"])))
    assert len(answers) == len(SEEDS)


@pytest.mark.parametrize("z", (0.0, 1.0, 1.5))
def test_skew_is_the_zipf_expectation(z):
    n_c, n_o = 7500, 75000
    keys = tpch_zipf.zipf_custkeys(n_c, n_o, z)
    assert keys.min() >= 1 and keys.max() <= n_c
    counts = np.bincount(keys, minlength=n_c + 1)[1:]
    p = tpch_zipf.zipf_probabilities(n_c, z)
    want_zero = float(((1.0 - p) ** n_o).sum()) / n_c
    if z == 0.0:
        assert counts.max() < 4 * n_o / n_c
        assert (counts == 0).mean() < 0.001
        return
    assert counts.max() / n_o == pytest.approx(p[0], rel=0.10)
    assert (counts == 0).mean() == pytest.approx(want_zero, rel=0.10)
    # the hot customers are not the low keys
    assert int(np.argmax(counts)) + 1 > 10


@pytest.mark.parametrize("z", (0.0, 1.0, 1.5))
@pytest.mark.parametrize("n_devices", (1, 4))
def test_q13_is_the_reference_exactly(tmp_path, n_devices, z):
    """At z = 1.5 the first capacities are far off the actuals and
    capacity feedback sizes a second program: the answer is exact under
    both."""
    p = params(z)
    data = tpch_zipf.generate(p, SEEDS[1])
    ref = tpch_q13.build(data)
    sess = citus_tpu.connect(data_dir=str(tmp_path / "d"),
                             n_devices=n_devices,
                             serving_result_cache_bytes=0)
    try:
        assert tpch_zipf.load(sess, data, p) == tpch_zipf.row_counts(data)
        for _ in range(2):
            rows = sess.execute(Q13).rows()
            bad, _err = tpch_q13.compare(rows, ref, tpch_q13.tolerance({}))
            assert bad == []
    finally:
        sess.close()
    assert sum(r[1] for r in rows) == 7500 == int(ref["customers"][0])
    kept = data["orders"]["o_comment"] != tpch_zipf.SPECIAL_COMMENT
    assert sum(r[0] * r[1] for r in rows) == int(ref["orders_kept"][0]) \
        == int(kept.sum())
    if z > 0:
        assert max(r[0] for r in rows) > 5000  # the hot account


def test_reference_refuses_a_moved_row():
    ref = {"c_count": np.array([0, 9, 10]), "custdist": np.array([5, 4, 3])}
    good = [(0, 5), (9, 4), (10, 3)]
    assert tpch_q13.compare(good, ref, 0.0)[0] == []
    assert tpch_q13.compare(good[:2], ref, 0.0)[0]
    assert tpch_q13.compare([(0, 5), (10, 3), (9, 4)], ref, 0.0)[0]
    assert tpch_q13.compare([(0, 5), (9, 3), (10, 4)], ref, 0.0)[0]
    assert tpch_q13.compare([(0, 5.5), (9, 4), (10, 3)], ref, 0.0)[0]
