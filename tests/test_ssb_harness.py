"""The benchmark's SSB cells `ssb1.q4_1` and `ssb10.q4_1` through the
harness itself (benchmark/run.py `main`), at a tiny scale on the CPU:
the first piece of ROADMAP D12 (benchmark/selftest.py is by hand and is
not edited).  The platform check and the data directory are overridden
from here, never through an option of the harness; each configuration
is the cell's own but for its scale factor, and under `ssb10.q4_1` the
planner's pick between the lookup arms is patched to what SF10's
extents give (`customer` and `part` sort), as in tests/test_ssb.py."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run as harness  # noqa: E402

CELLS = ("ssb1.q4_1", "ssb10.q4_1")
SEED = 2_147_483_777  # past 32 signed bits, as the driver's are
SCALE = 0.05
NEW_READERS = ("stage_lookup_sorted_ms", "sorted_lookup_joins",
               "dense_lookup_joins", "resident_feed_bytes", "refed_bytes",
               "governor_events", "lookup_probe_slots")


@pytest.fixture(scope="module")
def data_root(tmp_path_factory):
    return str(tmp_path_factory.mktemp("benchdata"))


@pytest.mark.parametrize("trace", (0, 1))
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_through_the_harness(monkeypatch, capsys, data_root, cell,
                                       trace):
    class TinyCell(harness.Cell):
        def __init__(self, workload):
            super().__init__(workload)
            self.config["dataset_params"]["scale_factor"] = SCALE

    monkeypatch.setattr(harness, "Cell", TinyCell)
    monkeypatch.setattr(harness, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(harness, "DATA_ROOT", data_root)
    if cell == "ssb10.q4_1":
        from benchmark.datasets import ssb
        from citus_tpu.ops import join

        rows = ssb.table_rows(SCALE)
        monkeypatch.setattr(
            join, "sorted_lookup_eligible",
            lambda extent: extent in (rows["customer"], rows["part"]))
    runs, judge = [], harness.judge
    monkeypatch.setattr(harness, "judge", lambda run, head: (
        runs.append(run), judge(run, head))[1])
    rc = harness.main(["--workload", cell, "--seed", str(SEED),
                       "--seconds", "2", "--trace", str(trace)])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert rc == 0
    phases = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert phases["start"]["workload"] == cell
    assert phases["data"]["reused_data"] == bool(trace)  # the second run
    assert phases["data"]["rows"]["dwdate"] == 2556
    window = phases["window"]
    assert window["workload"] == cell and window["seed"] == SEED
    assert window["wrong"] == 0 and window["errors"] == 0
    assert window["compiled_in_window"] == 0
    assert window["rows_per_stmt"] == {
        "ssb_q4_1": sum(phases["data"]["rows"].values())}
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    got = {name: m["value"] for name, m in result["metrics"].items()}
    # what the window's statements carried as a row index and what they
    # gathered for it later (PR 32): both in the window's counters,
    # the same for every statement of one converged program
    counters = runs[0].window["counters"]
    carried = counters["deferred_columns_total"] / result["attempted"]
    gathered = counters["deferred_gathers_total"] / result["attempted"]
    assert carried == int(carried) and gathered == int(gathered)
    assert carried > gathered > 0
    if trace:
        assert got["window_compiles"] == 0
        # the four lookups' probe slots (PR 34): the whole fact feed
        # under the first, what each earlier lookup kept under the rest
        assert got["lookup_probe_slots"] * result["attempted"] \
            == counters["lookup_probe_slots_total"]
        assert 300_160 < got["lookup_probe_slots"] < 2 * 300_160
        # device metrics need a device trace: none on the CPU
        assert not {"device_busy_ms", "stage_deferred_ms",
                    "stage_lookup_dense_ms", "stage_lookup_sorted_ms"} \
            & set(got)
        if cell == "ssb1.q4_1":
            assert got["broadcast_joins"] == 4
            assert got["deferred_columns"] == carried
            assert set(NEW_READERS) & set(got) == {"lookup_probe_slots"}
        else:
            assert counters["broadcast_joins_total"] \
                == 4 * result["attempted"]
            assert got["sorted_lookup_joins"] == 2
            assert got["dense_lookup_joins"] == 2
            # the window found every feed in the cache and built none
            assert got["resident_feed_bytes"] > (6 * 4 + 1) * 300_145
            assert got["refed_bytes"] == 0
            assert got["governor_events"] == 0
    else:
        assert set(got) == {"stmts_per_s", "latency_p50_ms", "setup_s"}


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_is_silent_without_its_source(name):
    """The parent runs the new cell with these readers laid over it: on a
    program without the counter (or a run without a device trace) each
    returns None and does not raise, so the line leaves the metric
    out."""
    from types import SimpleNamespace

    run = SimpleNamespace(window={"counters": {}, "profile": None},
                          records=[{"t1": 0.0}], trace_dir="/nonexistent")
    assert harness.layer_metric(run, name) is None
    run = SimpleNamespace(window={}, records=[], trace_dir="/nonexistent")
    assert harness.layer_metric(run, name) is None
