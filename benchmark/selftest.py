#!/usr/bin/env python3
"""benchmark/selftest.py — the harness rehearsed on the CPU, by hand:

    JAX_PLATFORMS=cpu python3 benchmark/selftest.py
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python3 benchmark/selftest.py --mesh

It is not part of tier-1 (`pytest tests/`) and prints no number of a CPU
run under a metric's name: it checks control flow, keys and counts.

It builds a scratch checkout (`.benchdata/benchmark-selftest/root`: this
directory copied, the program linked) and ADDS to it, as a later PR
would and without editing a file that is there: a tiny configuration,
a two-client mixed traffic file, an open-loop traffic file, a span-based
per-layer metric, and the `BENCHMARK.json` entries that name them.  The
command is then run there as the driver runs it.  The harness refuses
any platform but the chip; the rehearsal overrides that check from this
file (`REHEARSE`), never through an option of the harness.

Checked: the trace reduction against `fixtures/`; no result line without
the chip; the last line's keys with --trace 0 and --trace 1; a second
run of a seed reuses data, reference and executable cache; another seed
gives other answers and the same program shapes; a wrong answer injected
into the comparison gives `correct: false` and a count in `failed`; the
added cells run.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
SCRATCH = os.path.join(ROOT, ".benchdata", "benchmark-selftest")
T = os.path.join(SCRATCH, "root")

# run.main() with the platform check pointed at the CPU; `corrupt` makes
# the named reference's comparison find one mismatch more, always or
# only on the window's answers
REHEARSE = """
import sys
import benchmark.run as run
run.REQUIRED_PLATFORM = "cpu"
corrupt, when = {corrupt!r}, {when!r}
if corrupt:
    ref = run.plugin("references", corrupt)
    real_compare, real_drive = ref.compare, run.drive
    state = {{"window": False}}
    def drive(r, clients):
        state["window"] = True
        return real_drive(r, clients)
    def compare(rows, want, tol):
        bad, err = real_compare(rows, want, tol)
        if when == "always" or state["window"]:
            bad = bad + ["injected"]
        return bad, err
    ref.compare, run.drive = compare, drive
sys.exit(run.main({argv!r}))
"""

FAILED: list[str] = []


def check(ok: bool, what: str) -> None:
    print(("ok   " if ok else "FAIL ") + what, flush=True)
    if not ok:
        FAILED.append(what)


def write_json(path: str, obj) -> None:
    assert not os.path.exists(path), f"{path}: the self-test only adds"
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def build_root(mesh: bool) -> None:
    """The scratch checkout, and what a later PR would add to it."""
    shutil.rmtree(SCRATCH, ignore_errors=True)
    os.makedirs(T)
    shutil.copytree(BENCH_DIR, os.path.join(T, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "citus_tpu"), os.path.join(T, "citus_tpu"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    b = os.path.join(T, "benchmark")
    real = "tpch-sf1-4chip" if mesh else "tpch-sf1-1chip"
    with open(os.path.join(b, "configs", real + ".json")) as f:
        cfg = json.load(f)
    cfg["name"] = "selftest-tiny"
    cfg["dataset_params"]["scale_factor"] = 0.01
    write_json(os.path.join(b, "configs", "selftest-tiny.json"), cfg)
    write_json(os.path.join(b, "traffic", "selftest_mix.json"), {
        "loop": "closed", "clients": 2, "profile_seconds": 1,
        "statements": [{"statement": "tpch_q1", "weight": 3},
                       {"statement": "tpch_q3", "weight": 1}]})
    write_json(os.path.join(b, "traffic", "selftest_open.json"), {
        "loop": "open", "clients": 2, "rate_per_s": 20, "arrival_seed": 7,
        "statements": [{"statement": "tpch_q1", "weight": 1}]})
    write_json(os.path.join(b, "layer_metrics", "selftest_dispatch_ms.json"),
               {"from": "window_spans", "spans": ["mesh.dispatch"],
                "reduce": "p95"})
    bench["configs"].append({
        "name": "selftest-tiny", "source": "selftest", "reduced": [],
        "file": "benchmark/configs/selftest-tiny.json", "why": "selftest"})
    chips = 4 if mesh else 1
    for name, traffic in (("tiny.q1", "q1"), ("tiny.q3", "q3"),
                          ("tiny.mix", "selftest_mix"),
                          ("tiny.open", "selftest_open")):
        bench["workloads"].append({
            "name": name, "config": "selftest-tiny", "traffic": traffic,
            "chips": chips, "why": "selftest"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and not (mesh and "first_answer" in
                                     m.get("moves", m["name"])):
            m["workloads"] = m["workloads"] + ["tiny.q1", "tiny.q3"]
    bench["per_layer"].append({
        "name": "selftest_dispatch_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "device program",
        "moves": "latency_p50_ms", "workloads": ["tiny.mix"]})
    with open(os.path.join(T, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)


def run(workload: str, seed: int, trace: int = 0, seconds: float = 2,
        rehearse: bool = True, corrupt: str | None = None,
        when: str = "window"):
    argv = ["--workload", workload, "--seed", str(seed),
            "--seconds", str(seconds), "--trace", str(trace)]
    with open(os.path.join(T, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    cmd = [sys.executable, "-c", REHEARSE.format(
        argv=argv, corrupt=corrupt, when=when)] if rehearse \
        else command + argv
    env = {**os.environ, "BENCH_RUN": "selftest"}
    p = subprocess.run(cmd, cwd=T, env=env, capture_output=True, text=True,
                       timeout=900)
    lines = [json.loads(ln) for ln in p.stdout.splitlines() if ln.strip()]
    if p.returncode not in (0, 2) and not corrupt:
        sys.stderr.write(p.stderr[-4000:])
    return p.returncode, lines


def phases(lines) -> dict:
    return {ln["phase"]: ln for ln in lines if "phase" in ln}


def metric_names(bench: dict, group: str, cell: str) -> set[str]:
    return {m["name"] for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]}


def trace_reduction() -> None:
    """xtrace.py against a hand-built plane (two devices, nested
    operations, a collective, two statements) and against the trimmed
    trace of a chip run that `fixtures/recorded.json` names."""
    sys.path.insert(0, ROOT)
    from jax.profiler import ProfileData

    from benchmark import xtrace

    hand = os.path.join(BENCH_DIR, "fixtures", "hand_built.xspace.txt")
    with open(hand) as f:
        data = ProfileData.from_text_proto(f.read())
    r = xtrace.reduce_trace(data, window_s=100e-6, n_statements=2,
                            n_devices_used=2)
    # device 0: ops [0,10] (while, holding fusion [2,5] and all-to-all
    # [6,8]) and [30,40] us; device 1: [0,5] us
    check(abs(r["busy_s_by_device"]["0"] - 20e-6) < 1e-12
          and abs(r["busy_s_by_device"]["1"] - 5e-6) < 1e-12,
          "hand-built plane: busy is the union of operation intervals")
    check(r["busiest_device"] == 0
          and abs(r["device_busy_ms_per_stmt"] - 0.010) < 1e-9
          and abs(r["busy_s"] - 12.5e-6) < 1e-12,
          "hand-built plane: busiest device per statement, mean over chips")
    ops = dict(r["device_ops"])
    check(abs(ops["while.1"] - 5e-6) < 1e-12
          and abs(ops["fusion.7"] - 13e-6) < 1e-12,
          "hand-built plane: self time takes nested operations out")
    check(abs(r["collective_ms_per_stmt"] - 0.001) < 1e-9,
          "hand-built plane: collective time is the all-to-all's")
    gaps = dict(r["idle_gaps"])
    check(r["clock_aligned"]
          and abs(gaps["between statements: total of 1 gaps"] - 20e-6)
          < 1e-12, "hand-built plane: the gap is labelled by the "
                   "statement boundary in it")
    back = ProfileData.from_text_proto(xtrace.to_text_proto(data))
    r2 = xtrace.reduce_trace(back, 100e-6, 2, 2)
    check(r2["busy_s_by_device"] == r["busy_s_by_device"]
          and r2["device_ops"] == r["device_ops"],
          "a trimmed copy of a trace reduces to the same numbers")
    check(xtrace.reduce_trace(ProfileData.from_text_proto(
        'planes { id: 1 name: "/host:CPU" }'), 1.0, 1) is None,
        "a trace without device operations reduces to nothing")
    recorded = os.path.join(BENCH_DIR, "fixtures", "recorded.json")
    with open(recorded) as f:
        want = json.load(f)
    with open(os.path.join(BENCH_DIR, "fixtures", want["trace"])) as f:
        data = ProfileData.from_text_proto(f.read())
    got = xtrace.reduce_trace(data, want["window_s"], want["n_statements"],
                              want["n_devices"])
    for k, v in want["expect"].items():
        if isinstance(v, float):
            same = abs(got[k] - v) <= 1e-9 * max(abs(v), 1.0)
        elif isinstance(v, list):  # the first entries, names and seconds
            same = [[n, round(x, 12)] for n, x in got[k][:len(v)]] \
                == [[n, round(x, 12)] for n, x in v]
        else:
            same = got[k] == v
        check(same, f"recorded chip trace, {want['trace']}: {k}")


def contract(bench: dict) -> None:
    """The limits the driver refuses a BENCHMARK.json over, as far as a
    file can be held to them without the driver."""
    import re

    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    check(set(bench) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"},
          "BENCHMARK.json: exactly the contract's keys")
    cells = {w["name"]: w for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    ok = all(set(w) == {"name", "config", "traffic", "chips", "why"}
             and w["chips"] in (1, 4) and name.match(w["name"])
             and name.match(w["traffic"]) and 0 < len(w["why"]) <= 200
             for w in bench["workloads"])
    ok &= len({(w["config"], w["traffic"]) for w in bench["workloads"]}) \
        == len(cells) == len(bench["workloads"])
    ok &= sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(cells) // 2)
    check(ok, "BENCHMARK.json: cells")
    ok = True
    for c in bench["configs"]:
        ok &= set(c) == {"name", "source", "file", "reduced", "why"}
        ok &= any(w["config"] == c["name"] for w in cells.values())
        ok &= 0 < len(c["source"]) <= 200 and 0 < len(c["why"]) <= 200
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        ok &= cfg["name"] == c["name"] and cfg["source"] == c["source"] \
            and cfg["reduced"] == c["reduced"] \
            and c["file"].startswith(bench["paths"][0] + "/")
    check(ok, "BENCHMARK.json: configurations match their files")
    ok = "setup_s" in e2e and len(e2e) <= 16
    for m in bench["end_to_end"] + bench["per_layer"]:
        ok &= bool(name.match(m["name"]) and unit.match(m["unit"])) \
            and m["better"] in ("lower", "higher") \
            and all(w in cells for w in m.get("workloads", []))
    for m in bench["end_to_end"]:
        ok &= set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                         "source"} \
            and 0.01 <= m["bound"] <= 0.25 \
            and m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        ok &= set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                         "layer", "moves"}
        ok &= m["source"] in ("device_trace", "program_span",
                              "program_counter", "host_clock")
        ok &= os.path.exists(os.path.join(
            BENCH_DIR, "layer_metrics", m["name"] + ".json")) or \
            os.path.exists(os.path.join(
                BENCH_DIR, "layer_metrics", m["name"] + ".py"))
        moved = e2e.get(m["moves"], {"workloads": []})
        ok &= set(m.get("workloads", cells)) <= set(
            moved.get("workloads", cells))
    check(ok, "BENCHMARK.json: metrics, units, bounds, moves")
    n_cells = 24
    check((2 + 14 * n_cells) * (bench["run_seconds"] + 60) + n_cells * 180
          + 1200 <= 43200, "run_seconds fits a full check of 24 cells")


def main() -> int:
    mesh = "--mesh" in sys.argv[1:]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        contract(json.load(f))
    trace_reduction()
    build_root(mesh)
    with open(os.path.join(T, "BENCHMARK.json")) as f:
        bench = json.load(f)

    rc, lines = run("tiny.q1", 0, rehearse=False)
    check(rc != 0 and lines == [], "no chip: non-zero exit, no result line")
    check(not os.path.exists(os.path.join(T, ".benchdata")),
          "no chip: nothing was loaded")

    big = 2**31 + 11
    rc, lines = run("tiny.q3", big)
    last, ph = lines[-1], phases(lines)
    check(rc == 0 and set(last) == {"correct", "attempted", "failed",
                                    "metrics", "device"},
          "--trace 0: exit 0 and the last line's keys")
    check(last["correct"] is True and last["failed"] == 0
          and last["attempted"] > 0, "--trace 0: correct, none failed")
    check(set(last["metrics"]) == metric_names(bench, "end_to_end",
                                               "tiny.q3"),
          f"--trace 0: the cell's end-to-end metrics {sorted(last['metrics'])}")
    check(set(last["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"},
          "--trace 0: the device block's keys")
    check(all(set(v) == {"value", "unit"} for v in last["metrics"].values()),
          "every metric is {value, unit}")
    check(ph["data"]["reused_data"] is False
          and ph["first_statement"]["reused_exec_cache"] is False
          and ph["window"]["programs"] == {
              "xla_compiles": 0, "exec_cache_compiles": 0,
              "plan_cache_misses": 0, "capacity_retries": 0},
          "first run of a seed loads and compiles; nothing in the window")
    check(all(k in ln for ln in lines[:-1]
              for k in ("platform", "kind", "count", "seed")),
          "every line before the last names the device and the seed")
    programs_a = sorted(os.listdir(os.path.join(
        T, ph["data"]["dir"], "data", "exec_cache")))

    rc, lines = run("tiny.q3", big, trace=1)
    last, ph = lines[-1], phases(lines)
    check(rc == 0 and last["correct"] is True
          and ph["data"]["reused_data"] is True
          and ph["data"]["reused_reference"] == ["tpch_q3"]
          and ph["first_statement"]["reused_exec_cache"] is True,
          "second run of the seed reuses data, reference, executable cache")
    want = metric_names(bench, "per_layer", "tiny.q3")
    device_only = {m["name"] for m in bench["per_layer"]
                   if m["source"] == "device_trace"}
    check(set(last["metrics"]) == want - device_only,
          "--trace 1 on the CPU: the cell's per-layer metrics, without "
          f"those of a device trace {sorted(last['metrics'])}")
    check(last["metrics"]["window_compiles"]["value"] == 0,
          "--trace 1: window_compiles reads 0")
    check(ph["profile"]["statements_profiled"] > 0
          and ph["profile"]["statements_with_spans"] == last["attempted"],
          "--trace 1: a stretch was profiled; every statement has spans")

    rc, lines = run("tiny.q1", 5)
    ph5 = phases(lines)
    rc2, lines2 = run("tiny.q1", 6)
    ph6 = phases(lines2)
    check(rc == 0 and rc2 == 0 and lines[-1]["correct"]
          and lines2[-1]["correct"], "two other seeds run and are correct")

    def ref(ph, name):
        import numpy as np

        with np.load(os.path.join(T, ph["data"]["dir"], "reference",
                                  name + ".npz")) as z:
            return {k: z[k] for k in z.files}

    a, b = ref(ph5, "tpch_q1"), ref(ph6, "tpch_q1")
    check((a["counts"] == b["counts"]).all()
          and not (a["floats"] == b["floats"]).all(),
          "another seed: other sums, the same counts")
    progs = [sorted(os.listdir(os.path.join(T, p["data"]["dir"], "data",
                                            "exec_cache")))
             for p in (ph5, ph6)]
    check(progs[0] == progs[1] and len(progs[0]) > 0,
          "another seed: the same program shapes (executable-cache keys)")

    rc, lines = run("tiny.q3", 5)
    ph = phases(lines)
    check(rc == 0 and ph["data"]["reused_data"] is True
          and ph["data"]["reused_reference"] == [],
          "a second cell on the configuration reuses the seed's data")
    check(set(programs_a) <= set(os.listdir(os.path.join(
        T, ph["data"]["dir"], "data", "exec_cache"))),
        "Q3's programs have the same keys under another seed")

    rc, lines = run("tiny.q1", 5, corrupt="tpch_q1", when="always")
    check(rc != 0 and not any("correct" in ln for ln in lines),
          "a wrong answer before the window ends the run, no result line")
    rc, lines = run("tiny.q1", 5, corrupt="tpch_q1", when="window")
    last = lines[-1] if lines else {}
    check(rc != 0 and last.get("correct") is False
          and last.get("failed", 0) == last.get("attempted", -1) > 0,
          "a wrong answer in the window: correct false, counted in failed")

    rc, lines = run("tiny.mix", 5, trace=1, seconds=3)
    last = lines[-1]
    check(rc == 0 and last["correct"] and "selftest_dispatch_ms"
          in last["metrics"] and "plan_ms" in last["metrics"],
          "an added cell, traffic mix (2 clients, 2 statements) and span "
          "metric run from new files and entries alone")
    rc, lines = run("tiny.open", 5, seconds=2)
    last, ph = lines[-1], phases(lines)
    check(rc == 0 and last["correct"] and last["attempted"] == 40
          and "generator_late_s_max" in ph["window"],
          "an added open-loop mix sends its fixed schedule (40 at 20/s)")

    print(f"\n{len(FAILED)} check(s) failed" if FAILED else "\nall passed")
    return 1 if FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
