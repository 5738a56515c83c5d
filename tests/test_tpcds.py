"""The benchmark's TPC-DS deployment (`tpcds-sf10-1chip`, cell
`tpcds10.q89`, PR 40) on the CPU at a small scale: the data set module,
the plain reference and its comparison, the engine against the
reference on one device and on four, and the cell through the harness
itself (benchmark/run.py `main`; the platform check and the data
directory are overridden from here, never through an option of the
harness, and the configuration is the cell's own but for its scale
factor)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import citus_tpu  # noqa: E402
from benchmark import run as harness  # noqa: E402
from benchmark.datasets import tpcds  # noqa: E402
from benchmark.references import tpcds_q89 as ref  # noqa: E402

CELL = "tpcds10.q89"
SCALE = 0.01
SEED = 2_147_483_777  # past 32 signed bits, as the driver's are
NEW_READERS = ("stage_window_ms", "window_rows")


def _sql() -> str:
    with open(os.path.join(ROOT, "benchmark", "statements",
                           "tpcds_q89.sql")) as f:
        return f.read()


@pytest.fixture(scope="module")
def data():
    return tpcds.generate({"scale_factor": SCALE}, SEED)


def test_data_set_is_deterministic_and_the_seed_moves_only_measures(data):
    again = tpcds.generate({"scale_factor": SCALE}, SEED)
    other = tpcds.generate({"scale_factor": SCALE}, 5)
    assert tpcds.row_counts(data) == tpcds.row_counts(other) == {
        "date_dim": 73_049, "item": 600, "store": 6, "store_sales": 28_804}

    def same(a, b) -> bool:
        return list(a) == list(b)

    for table, cols in data.items():
        for c, a in cols.items():
            assert same(a, again[table][c]), (table, c)
            if c not in tpcds.MEASURES:
                assert same(a, other[table][c]), (table, c)
    fact, fact2 = data[tpcds.FACT], other[tpcds.FACT]
    for c in tpcds.MEASURES:
        assert np.array_equal(np.sort(fact[c]), np.sort(fact2[c]))
        assert not np.array_equal(fact[c], fact2[c])
    # every answer moves with the seed, no group and no count does
    a, b = ref.build(data), ref.build(other)
    assert len(a["sum_sales"]) == len(b["sum_sales"]) > 100
    for k in ref.KEYS:
        assert np.array_equal(a[k], b[k])
    assert not np.allclose(a["sum_sales"], b["sum_sales"])
    assert len(ref.answer(a)) == ref.LIMIT


def test_sf10_counts_are_the_configuration_rows():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "tpcds-sf10-1chip.json")) as f:
        cfg = json.load(f)
    assert tpcds.table_rows(cfg["dataset_params"]["scale_factor"]) \
        == cfg["rows"]
    widths = tpcds.column_widths()
    assert widths["ss_sales_price"] == 4 and widths["i_brand"] == 4
    assert len(widths) == 28 + 22 + 29 + 23  # every column of the four


def test_data_set_and_reference_import_nothing_of_the_program():
    code = ("import sys; sys.path.insert(0, %r); "
            "import benchmark.datasets.tpcds, "
            "benchmark.references.tpcds_q89; "
            "print([m for m in sys.modules if m.startswith('citus_tpu')])"
            % ROOT)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, cwd=ROOT)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"


@pytest.mark.parametrize("precision", ["bfloat16", "float16"])
def test_lower_precision_sums_fail_the_comparison(data, precision):
    """The readings below the configuration's float32: the same groups
    summed in bfloat16 or in float16 are not correct by the comparison
    that decides `correct` — by the limit on a sum or a mean — while the
    float64 answer is."""
    import ml_dtypes

    want = ref.build(data)
    tol = ref.tolerance(tpcds.row_counts(data))
    bad, err = ref.compare(ref.answer(want), want, tol)
    assert bad == [] and err == 0.0
    dtype = {"bfloat16": ml_dtypes.bfloat16, "float16": np.float16}
    low = ref.groups(data, dtype[precision])
    bad, err = ref.compare(ref.answer(low), want, tol)
    assert any(" vs " in b for b in bad) and err > tol


def test_comparison_refuses_what_it_should(data):
    want = ref.build(data)
    tol = ref.tolerance(tpcds.row_counts(data))
    rows = ref.answer(want)
    assert ref.compare(rows[:-1], want, tol)[0]  # a row short
    assert ref.compare(rows[::-1], want, tol)[0]  # out of order
    wrong = [r[:6] + (r[6] * (1 + 10 * tol), r[7]) for r in rows]
    assert ref.compare(wrong, want, tol)[0]
    # ties within the tolerance compare as a set
    assert ref.compare(rows, want, tol)[0] == []


@pytest.fixture(scope="module", params=[1, 4])
def loaded(request, tmp_path_factory, data):
    d = tmp_path_factory.mktemp(f"tpcds{request.param}")
    s = citus_tpu.connect(data_dir=str(d / "d"), n_devices=request.param,
                          serving_result_cache_bytes=0)
    assert tpcds.load(s, data, {"shard_count": 8}) == tpcds.row_counts(data)
    yield s
    s.close()


def test_q89_published_text_against_the_reference(loaded, data):
    want = ref.build(data)
    tol = ref.tolerance(tpcds.row_counts(data))
    before = loaded.stats.counters.snapshot()
    rows = loaded.execute(_sql()).rows()
    after = loaded.stats.counters.snapshot()
    assert len(rows) == ref.LIMIT
    bad, err = ref.compare(rows, want, tol)
    assert bad == [] and err < tol
    # one derived table, one window over its groups
    assert after["subplans_executed"] - before["subplans_executed"] == 1
    assert after["window_over_group_total"] \
        - before["window_over_group_total"] == 1
    assert after["window_rows_total"] > before["window_rows_total"]


def test_q89_window_runs_in_the_program(loaded):
    """The window over Q89's groups is a stage of the inner statement's
    device program (`ct.window`, its `sort` and `scan` sub-scopes), with
    no float64 prefix sum and on one device no lexsort of its own."""
    import re

    loaded.execute(_sql()).rows()
    texts = [e[0].as_text()
             for e in loaded.executor.plan_cache._entries.values()]
    inner = [t for t in texts if "ct.window" in t]
    assert inner
    for text in inner:
        assert "ct.window/ct.scan" in text
        assert "ct.window/ct.sort" in text
        assert "f64[" not in text
        if loaded.executor.mesh.devices.size == 1:
            sorts = [ln for ln in text.splitlines()
                     if re.search(r"\bsort\(", ln) and "ct.window" in ln]
            assert sorts == []


def _op_widths(text: str, scope: str, op: str) -> list[int]:
    """The output widths of the `op` operations under `scope` in an
    optimized HLO text, one a line."""
    import re

    out = []
    for ln in text.splitlines():
        m = re.search(r"= \w+\[(\d+)(?:,1)?\]\S* ([\w-]+)\(", ln)
        name = re.search(r'op_name="([^"]*)"', ln)
        if m and m.group(2) == op and name and scope in name.group(1):
            out.append(int(m.group(1)))
    return out


def test_q89_group_keys_gather_at_the_group_bys_width(loaded, data):
    """`d_moy` crosses the `date_dim` lookup (a widening take), the date
    compaction and the `item` compaction unread: its chain of three row
    indexes is composed from the top, so in every converged program
    that compacts no gather under `ct.agg_sort/ct.deferred` is wider
    than the group-by's input — the date compaction's wider index is
    never composed at its own width.  A dimension's own column is read
    at its compacted size (the widening take's rule) and may be wider
    at this scale, but no gather there is as wide as a compaction of
    the joined rows that is wider than the group-by's input.  On one
    device the date compaction keeps 768 slots and the group-by reads
    640, so a composition from the bottom fails here; on four both
    compactions keep 640 slots a device at this scale and the check
    guards only against a wider gather.  The answers stay the
    reference's."""
    for _ in range(3):   # capacity feedback converges
        rows = loaded.execute(_sql()).rows()
    bad, err = ref.compare(rows, ref.build(data),
                           ref.tolerance(tpcds.row_counts(data)))
    assert bad == [] and err < ref.tolerance(tpcds.row_counts(data))
    checked = 0
    for entry in loaded.executor.plan_cache._entries.values():
        text = entry[0].as_text()
        compactions = _op_widths(text, "ct.join_out/ct.compact", "slice")
        groupby_in = _op_widths(text, "ct.agg_sort/ct.sort", "sort")
        if "ct.window" not in text or not compactions or not groupby_in:
            continue
        dimension = set(_op_widths(text, "ct.scan_out/ct.compact", "slice"))
        middle = {w for w in compactions if w > max(groupby_in)}
        wide = [w for w in _op_widths(text, "ct.agg_sort/ct.deferred",
                                      "gather")
                if w > max(groupby_in)
                and (w in middle or w not in dimension)]
        assert wide == [], (wide, max(groupby_in), compactions)
        checked += 1
    assert checked >= 1


def test_q89_date_probe_reads_at_the_compacted_width(loaded, data,
                                                     monkeypatch):
    """`d_year in (1999)` keeps one interval of the date keys, and a
    fifth of `store_sales` lies inside it: the converged program
    compacts the fact side to the rows inside before it probes the
    `date_dim` directory, so every gather of the lookups is under a
    third of the fact side's width, and one range compaction is counted
    a statement.  At SF 0.01 the statistics let the `item` lookup build
    on the filtered fact side (its estimated rows fall under `item`'s
    key count), a uniqueness claim the first execution refutes, which
    sends the statement to the general join paths; at SF10 the fact
    side probes `item`, and that plan is kept here by taking no
    uniqueness claim from a join's output.  The answers stay the
    reference's."""
    from citus_tpu.planner.plan import DistributedPlanner, JoinNode

    claim = DistributedPlanner._estimate_expansion_for
    monkeypatch.setattr(
        DistributedPlanner, "_estimate_expansion_for",
        lambda self, node, keys: (None if isinstance(node, JoinNode)
                                  else claim(self, node, keys)))
    known = set(loaded.executor.plan_cache._entries)
    for _ in range(3):
        before = loaded.stats.counters.snapshot()
        rows = loaded.execute(_sql()).rows()
        after = loaded.stats.counters.snapshot()
    tol = ref.tolerance(tpcds.row_counts(data))
    bad, err = ref.compare(rows, ref.build(data), tol)
    assert bad == [] and err < tol
    assert after["lookup_range_compactions_total"] \
        - before["lookup_range_compactions_total"] == 1
    assert after["capacity_retries"] == before["capacity_retries"]
    texts = [e[0].as_text()
             for k, e in loaded.executor.plan_cache._entries.items()
             if k not in known]
    converged = [t for t in texts if "ct.join_range/ct.compact" in t]
    assert converged
    for text in converged:
        fact = max(_op_widths(text, "ct.join_range/ct.compact", "sort"))
        probe = _op_widths(text, "ct.lookup_join", "gather")
        assert probe and max(probe) * 3 < fact, (probe, fact)


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
                                      "store": 6, "store_sales": 28_804}
    window = phases["window"]
    assert window["wrong"] == 0 and window["errors"] == 0
    assert window["compiled_in_window"] == 0
    assert not any(window["programs"].values())
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0
    got = {name: m["value"] for name, m in result["metrics"].items()}
    if trace:
        assert got["window_compiles"] == 0
        assert got["window_rows"] > 0
        # a device metric needs a device trace: none on the CPU
        assert "stage_window_ms" not in got
    else:
        assert set(got) == {"stmts_per_s", "latency_p50_ms", "setup_s"}


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_is_silent_without_its_source(name):
    """The parent runs the new cell's traced run with these readers laid
    over it: on a program without the scope or counter (or a run without
    a device trace) each returns None and does not raise."""
    from types import SimpleNamespace

    cell = SimpleNamespace(config={"n_devices": 1})
    run = SimpleNamespace(window={"counters": {}, "profile": None},
                          records=[{"t1": 0.0, "spans": {"plan": 1.0}}],
                          trace_dir="/nonexistent", cell=cell)
    assert harness.layer_metric(run, name) is None
    run._xspans = {"spanned": True, "stage_ms": {"agg_sort": 1.0},
                   "idle_by_span_ms": {}}
    assert harness.layer_metric(run, name) is None
    run = SimpleNamespace(window={}, records=[], trace_dir="/nonexistent",
                          cell=cell)
    assert harness.layer_metric(run, name) is None


def test_new_readers_read_a_hand_built_run():
    from types import SimpleNamespace

    run = SimpleNamespace(
        window={"counters": {"window_rows_total": 3 * 8192},
                "profile": None},
        records=[{"t1": 0.0}] * 3, trace_dir="/nonexistent",
        cell=SimpleNamespace(config={"n_devices": 1}))
    run._xspans = {"spanned": True, "idle_by_span_ms": {},
                   "stage_ms": {"window": 0.25, "agg_sort": 2.0}}
    assert harness.layer_metric(run, "window_rows") == 8192
    assert harness.layer_metric(run, "stage_window_ms") == 0.25


def test_cell_loads_and_entries_fit():
    cell = harness.Cell(CELL)
    assert cell.chips == 1 and cell.config["n_devices"] == 1
    assert cell.config["dataset"] == "tpcds"
    assert [st["name"] for st in cell.statements] == ["tpcds_q89"]
    assert {m["name"] for m in cell.metrics("end_to_end")} \
        == {"stmts_per_s", "latency_p50_ms", "setup_s"}
    per_layer = {m["name"]: m for m in cell.metrics("per_layer")}
    for name in NEW_READERS:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["layer"] == "device program"
    entry = next(c for c in cell.bench["configs"]
                 if c["name"] == "tpcds-sf10-1chip")
    assert entry["source"] == cell.config["source"]
    assert 1 <= len(entry["source"]) <= 200
    assert set(entry["reduced"]) == set(cell.config["reduced_why"])
    assert len(cell.entry["why"]) <= 200
    assert len(cell.bench["workloads"]) == 8
