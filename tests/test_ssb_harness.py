"""The benchmark's new cell `ssb1.q4_1` through the harness itself
(benchmark/run.py `main`), at a tiny scale on the CPU: the first piece
of ROADMAP D12 (benchmark/selftest.py is by hand and is not edited).
The platform check and the data directory are overridden from here,
never through an option of the harness; the configuration is the
cell's own but for its scale factor."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run as harness  # noqa: E402

CELL = "ssb1.q4_1"
SEED = 2_147_483_777  # past 32 signed bits, as the driver's are


@pytest.fixture(scope="module")
def data_root(tmp_path_factory):
    return str(tmp_path_factory.mktemp("benchdata"))


@pytest.mark.parametrize("trace", (0, 1))
def test_cell_runs_through_the_harness(monkeypatch, capsys, data_root, trace):
    class TinyCell(harness.Cell):
        def __init__(self, workload):
            super().__init__(workload)
            self.config["dataset_params"]["scale_factor"] = 0.05

    monkeypatch.setattr(harness, "Cell", TinyCell)
    monkeypatch.setattr(harness, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(harness, "DATA_ROOT", data_root)
    runs, judge = [], harness.judge
    monkeypatch.setattr(harness, "judge", lambda run, head: (
        runs.append(run), judge(run, head))[1])
    rc = harness.main(["--workload", CELL, "--seed", str(SEED),
                       "--seconds", "2", "--trace", str(trace)])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert rc == 0
    phases = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert phases["start"]["workload"] == CELL
    assert phases["data"]["reused_data"] == bool(trace)  # the second run
    assert phases["data"]["rows"]["dwdate"] == 2556
    window = phases["window"]
    assert window["workload"] == CELL and window["seed"] == SEED
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
        assert got["broadcast_joins"] == 4
        assert got["deferred_columns"] == carried
        # stage_deferred_ms needs a device trace, as the stages below
        assert "stage_deferred_ms" not in got
        assert got["window_compiles"] == 0
        # device metrics need a device trace: none on the CPU
        assert "stage_lookup_dense_ms" not in got
        assert "device_busy_ms" not in got
    else:
        assert set(got) == {"stmts_per_s", "latency_p50_ms", "setup_s"}
