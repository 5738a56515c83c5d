"""CPU rehearsal of chip_smoke.py at --sf 0.01.

The script refuses any platform but the chip; the rehearsal overrides
that check from here (never through an option of the script) and points
its data directory at tmp_path.  What it pins: every phase runs, the
comparison with the numpy reference is live, and the last line is the
object the chip check reads.
"""

import json
import os

import pytest

import chip_smoke

ARGS = ["--sf", "0.01", "--seed", "0"]


@pytest.fixture(scope="module")
def data_root(tmp_path_factory):
    return str(tmp_path_factory.mktemp("chip_smoke"))


@pytest.fixture
def rehearsal(monkeypatch, data_root):
    monkeypatch.setattr(chip_smoke, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(chip_smoke, "DATA_ROOT", data_root)


def _lines(capsys) -> list[dict]:
    return [json.loads(line)
            for line in capsys.readouterr().out.splitlines() if line]


def test_refuses_to_run_without_the_chip(monkeypatch, data_root, capsys):
    monkeypatch.setattr(chip_smoke, "DATA_ROOT", data_root)
    assert chip_smoke.main(ARGS) != 0
    assert capsys.readouterr().out == ""  # no result line at all
    assert os.listdir(data_root) == []    # and no data was loaded


@pytest.mark.parametrize("scan_mode", ["host", "device"])
def test_every_phase_runs_and_last_line_is_the_contract(
        rehearsal, monkeypatch, capsys, scan_mode):
    # `scan_pipeline=auto` resolves to host on the CPU and to device on
    # the chip: steer it from here, so that the on-device decode the
    # chip run takes is rehearsed too (each mode in a data root of its
    # own — the executable cache must not carry over)
    import citus_tpu

    real_connect = citus_tpu.connect
    monkeypatch.setattr(
        citus_tpu, "connect",
        lambda **kw: real_connect(scan_pipeline=scan_mode, **kw))
    monkeypatch.setattr(chip_smoke, "DATA_ROOT",
                        chip_smoke.DATA_ROOT + "_" + scan_mode)
    assert chip_smoke.main(ARGS) == 0
    lines = _lines(capsys)
    last = lines[-1]
    assert set(last) == {"ok", "device"}
    assert last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"

    stmts = {ln["stmt"]: ln for ln in lines if "stmt" in ln}
    assert set(stmts) == {"q1", "q3", "dual_repartition", "dml",
                          "point_lookups", "q3_reopened_1dev"}
    assert all(ln["ok"] for ln in stmts.values())
    for name in ("q1", "q3", "dual_repartition"):
        assert stmts[name]["cold_s"] > 0 and stmts[name]["warm_s"] > 0
        assert f"pipelined scan: {scan_mode}" in stmts[name]["tags"]
        scan = stmts[name]["scan"]
        assert scan["feeds_pipelined"] >= 1
        assert (scan["bytes_on_wire"] < scan["bytes_decoded"]) == \
            (scan_mode == "device")
    assert stmts["q1"]["rows"] == 4 and stmts["q3"]["rows"] == 10
    assert stmts["point_lookups"]["n"] == 100
    assert stmts["point_lookups"]["counters"]["queries_fast_path"] == 100
    assert "Fast Path Router" in stmts["point_lookups"]["tags"]
    # the reopened session loaded Q3's executable; it compiled nothing
    cache = stmts["q3_reopened_1dev"]["exec_cache"]
    assert cache["hits_total"] >= 1 and cache["compiles_total"] == 0

    phases = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert {"device", "scale", "reference", "load",
            "environment"} <= set(phases)
    assert phases["load"]["rows"]["lineitem"] > 50_000
    assert len(phases["load"]["rows"]) == 8
    assert phases["environment"]["native_load_error"] is None
    assert phases["environment"]["open_spans"] == 0


def test_a_corrupted_reference_row_fails_the_run(rehearsal, monkeypatch,
                                                 capsys):
    real = chip_smoke.build_reference

    def corrupted(data, seed):
        ref = real(data, seed)
        row = ref["q1"][0]
        ref["q1"][0] = row[:9] + (row[9] + 1,)  # one count off by one
        return ref

    monkeypatch.setattr(chip_smoke, "build_reference", corrupted)
    assert chip_smoke.main(ARGS) == 1
    lines = _lines(capsys)
    assert lines[-1]["ok"] is False and set(lines[-1]) == {"ok", "device"}
    stmts = {ln["stmt"]: ln for ln in lines if "stmt" in ln}
    assert stmts["q1"]["ok"] is False
    assert stmts["q3"]["ok"] is True  # the run went on past a mismatch
    failed = [ln for ln in lines if ln.get("phase") == "q1"]
    assert failed and "count" in failed[0]["mismatches"][0]
