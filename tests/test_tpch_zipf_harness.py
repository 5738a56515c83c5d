"""The benchmark's cell `tpch4z.q13` through the harness itself
(benchmark/run.py `main`), at a tiny scale on four virtual CPU devices,
in the shape of tests/test_ssb_harness.py: the platform check and the
data directory are overridden from here, never through an option of the
harness, and the configuration is the cell's own but for its scale
factor and for `group_by_kernel`, forced onto the bucketed grid that the
planner picks by itself only on the chip (and the flat grid's limit is
lowered with the scale, so that 7,501 slots are over it as 150,001 are)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run as harness  # noqa: E402

CELL = "tpch4z.q13"
SEED = 2_147_483_777  # past 32 signed bits, as the driver's are
SCALE = 0.05
NEW_READERS = ("subplans", "subplan_ms", "intermediate_rows",
               "dict_predicate_walks", "repartition_imbalance",
               "window_capacity_retries", "stage_join_expand_ms",
               "agg_bucket_slots")


@pytest.fixture(scope="module")
def data_root(tmp_path_factory):
    return str(tmp_path_factory.mktemp("benchdata"))


@pytest.mark.parametrize("trace", (0, 1))
def test_cell_runs_through_the_harness(monkeypatch, capsys, data_root, trace):
    class TinyCell(harness.Cell):
        def __init__(self, workload):
            super().__init__(workload)
            self.config["dataset_params"]["scale_factor"] = SCALE
            self.config["session_settings"]["group_by_kernel"] = "bucketed"

    from citus_tpu.planner.plan import DistributedPlanner

    monkeypatch.setattr(DistributedPlanner, "DENSE_GROUP_LIMIT", 4096)
    monkeypatch.setattr(harness, "Cell", TinyCell)
    monkeypatch.setattr(harness, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(harness, "DATA_ROOT", data_root)
    rc = harness.main(["--workload", CELL, "--seed", str(SEED),
                       "--seconds", "2", "--trace", str(trace)])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert rc == 0
    phases = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert phases["start"]["chips_used"] == 4
    assert phases["data"]["reused_data"] == bool(trace)  # the second run
    assert phases["data"]["rows"] == {"region": 5, "nation": 25,
                                      "customer": 7500, "orders": 75000}
    # one execution compiles the two programs, two more are quiet: an
    # intermediate result's program is found again (PR 35)
    assert phases["first_statement"]["executions"] <= 4
    window = phases["window"]
    assert window["wrong"] == 0 and window["errors"] == 0
    assert window["compiled_in_window"] == 0
    assert not any(window["programs"].values())
    assert window["rows_per_stmt"] == {"tpch_q13": 7500 + 75000}
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    got = {name: m["value"] for name, m in result["metrics"].items()}
    if trace:
        assert got["window_compiles"] == 0
        assert got["window_capacity_retries"] == 0
        assert got["subplans"] == 1
        assert got["intermediate_rows"] == 7500  # a row a customer
        assert got["dict_predicate_walks"] == 0
        assert got["subplan_ms"] > 0
        # Zipf(1) over 7,500 customers: the hottest holds a tenth of
        # the orders, and the bucket it lands in stands out
        assert 1.05 < got["repartition_imbalance"] < 2.0
        # both programs group on the bucketed grid, as on the chip
        # (7,501 slots of `c_custkey`, the hot account's ≈ 7,500 of
        # `c_count`: 2 tiles each): a pack is its input's slots in
        # whole chunks and a chunk a tile more, on each of four devices
        from citus_tpu.ops.groupby import group_pack_shape

        slots = got["agg_bucket_slots"]
        chunk = group_pack_shape(0, 2)[1]
        assert slots % (4 * chunk) == 0 and slots >= 4 * 2 * 3 * chunk
        # a device metric needs a device trace: none on the CPU
        assert set(NEW_READERS) - set(got) == {"stage_join_expand_ms"}
        # the host path's owners (PR 37): two programs' blocks pulled,
        # the intermediate result's store and drop step by step
        assert got["fetch_bytes"] > 2 * 8 * 7500
        assert got["subplan_store_type_ms"] > 0
        assert got["subplan_store_append_ms"] > 0
        assert got["subplan_drop_ms"] > 0
        assert got["subplan_store_type_ms"] \
            + got["subplan_store_append_ms"] < got["subplan_ms"]
        assert got["gc_pause_ms"] >= 0
        # … and since PR 38 the result goes to the outer feed in memory
        assert got["resident_intermediates"] == 1 == got["subplans"]
        assert got["subplan_feed_ms"] > 0
        assert not {"idle_fetch_wait_ms", "idle_fetch_pull_ms"} & set(got)
    else:
        assert set(got) == {"stmts_per_s", "latency_p50_ms", "setup_s"}


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_is_silent_without_its_source(name):
    """The parent runs the new cell's traced run with these readers laid
    over it: on a program without the span or counter (or a run without
    a device trace) each returns None and does not raise, so the line
    leaves the metric out."""
    from types import SimpleNamespace

    cell = SimpleNamespace(config={"n_devices": 4})
    run = SimpleNamespace(window={"counters": {}, "profile": None},
                          records=[{"t1": 0.0, "spans": {"plan": 1.0}}],
                          trace_dir="/nonexistent", cell=cell)
    assert harness.layer_metric(run, name) is None
    run = SimpleNamespace(window={}, records=[], trace_dir="/nonexistent",
                          cell=cell)
    assert harness.layer_metric(run, name) is None


def test_cell_loads_and_sources_fit():
    cell = harness.Cell(CELL)
    assert cell.chips == 4 and cell.config["n_devices"] == 4
    assert cell.config["dataset"] == "tpch_zipf"
    assert [st["name"] for st in cell.statements] == ["tpch_q13"]
    assert {m["name"] for m in cell.metrics("end_to_end")} \
        == {"stmts_per_s", "latency_p50_ms", "setup_s"}
    assert set(NEW_READERS) <= {m["name"] for m in cell.metrics("per_layer")}
    for cfg in cell.bench["configs"]:
        assert 1 <= len(cfg["source"]) <= 200, cfg["name"]
        assert harness.read_json(ROOT, cfg["file"])["source"] == cfg["source"]
    for w in cell.bench["workloads"]:
        assert len(w["why"]) <= 200, w["name"]
