#!/usr/bin/env python3
"""benchmark/selftest_spans.py — xspans.py and the ten metrics that read
it, held to traces whose answers are known:

    JAX_PLATFORMS=cpu python3 benchmark/selftest_spans.py

No chip, no session, no number of a CPU run: it reduces recorded and
hand-built traces.  `tests/test_benchmark_spans.py` runs every group
below in tier-1.

  hand_built   fixtures/spans_hand_built.xspace.txt: two devices, three
               statements, a nested operation, a stage inside another, a
               gap that straddles a dozen spans, a producer thread's
               spans; every metric's value as the file's head gives it
  wire_format  an `.xplane.pb` built here byte by byte whose op_name
               paths sit on the event METADATA, as the chip writes them
  no_names     PR 25's trace of a program without scopes or spans: the
               idle metrics read None and all device time is unscoped;
               a trace with no device plane: every metric reads None
  recorded     fixtures/tpch1_q3_v5e_spans.xspace.txt, cut from a traced
               chip run of PR 27, against fixtures/spans_recorded.json
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import types

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

METRICS = ("stage_join_ms", "stage_groupby_ms", "stage_scan_ms",
           "stage_repartition_ms", "stage_unscoped_ms", "idle_plan_ms",
           "idle_dispatch_ms", "idle_fetch_ms", "idle_combine_ms",
           "idle_unspanned_ms")


def fixture(name: str):
    from jax.profiler import ProfileData

    with open(os.path.join(BENCH_DIR, "fixtures", name)) as f:
        return ProfileData.from_text_proto(f.read())


def metrics_of(reduction) -> dict:
    """Every new metric through its own `layer_metrics/<name>.py`, on a
    run that holds `reduction` as xspans.of_run() would leave it."""
    import importlib

    run = types.SimpleNamespace(_xspans=reduction)
    return {m: importlib.import_module(
        f"benchmark.layer_metrics.{m}").read(run) for m in METRICS}


def close(a, b, rel=1e-9) -> bool:
    return a is not None and abs(a - b) <= rel * max(abs(b), 1e-12)


def sums(red: dict) -> list[str]:
    """What has to add up in any reduction."""
    bad = []
    if not close(sum(red["stage_ms"].values()), red["ops_ms"], 1e-9):
        bad.append("stage self times do not add up to the operations'")
    if not close(red["ops_ms"], red["busy_ms"], 1e-6):
        bad.append("self times do not add up to the busy time")
    parts = sum(red["idle_metric_ms"].values()) \
        + red["idle_other_spans_ms"] + red["idle_outside_ms"]
    if red["idle_ms"] and not close(parts, red["idle_ms"], 1e-9):
        bad.append("idle parts do not add up to the idle time")
    if not close(sum(red["idle_by_span_ms"].values()), red["idle_ms"]) \
            and red["idle_ms"]:
        bad.append("idle by span does not add up to the idle time")
    return bad


def hand_built() -> list[str]:
    from benchmark import xspans

    red = xspans.reduce_spans(fixture("spans_hand_built.xspace.txt"), 3)
    bad = sums(red)
    us = 1e-3 / 3  # us over the trace → ms a statement
    want = {"stage_join_ms": 28, "stage_groupby_ms": 42,
            "stage_scan_ms": 20, "stage_repartition_ms": 8,
            "stage_unscoped_ms": 5, "idle_plan_ms": 14,
            "idle_dispatch_ms": 24, "idle_fetch_ms": 17,
            "idle_combine_ms": 37, "idle_unspanned_ms": 30}
    got = metrics_of(red)
    bad += [f"{m}: {got[m]} is not {v} us over 3 statements"
            for m, v in want.items() if not close(got[m], v * us)]
    sub = red["stage_sub_ms"]
    if not (close(sub.get("agg_sort/reduce"), 22 * us)
            and close(sub.get("repartition/exchange"), 8 * us)
            and close(sub.get("bucket_probe/pack"), 8 * us)):
        bad.append(f"sub-scopes, or the innermost stage: {sub}")
    if red["busiest_device"] != 0 or not close(red["busy_ms"], 103 * us):
        bad.append("the busiest device is device 0, 103 us busy")
    if not (close(red["idle_ms"], 137 * us)
            and close(red["idle_outside_ms"], 15 * us)
            and red["gaps"] == 3):
        bad.append("three gaps, 137 us idle, 15 outside every statement")
    longest = red["longest_gaps"][0]
    if not (close(longest[0], 0.070) and len(longest[1]) == 9 and close(
            longest[1]["statement/execute/mesh.dispatch"], 0.015)):
        bad.append(f"the longest gap is cut at span boundaries: {longest}")
    if not close(red["idle_by_span_ms"].get("scan.transfer"), 4 * us):
        bad.append("a span inside feed is the innermost where it is open")
    if (red["statements_seen"], red["orphan_spans"],
            red["producer_lines"]) != (3, 1, ["python3"]):
        bad.append("three trees; the producer's spans join statement 8, "
                   "the one of statement 5 is an orphan")
    if not close(red["spans_per_statement"], (9 + 12 + 8) / 3):
        bad.append(f"spans a statement: {red['spans_per_statement']}")
    if red["top_ops"][0][0] != "scan_out · fusion.1 fusion:kLoop f32[1024]":
        bad.append(f"operations are listed as stage · label: "
                   f"{red['top_ops'][0]}")
    return bad


# -- a binary XSpace, byte by byte (xplane.proto's field numbers) ----------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _f(field: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(field << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(field << 3 | 2) + _varint(len(value)) + value


def _entry(key: int, value: bytes) -> bytes:
    return _f(1, key) + _f(2, value)


def wire_format() -> list[str]:
    from benchmark import xspans

    scan = "jit(packed_fn)/jit(main)/ct.scan_out/mul:"
    probe = "jit(packed_fn)/jit(main)/ct.bucket_probe/ct.probe/gather:"
    plane = (
        _f(2, "/device:TPU:0")
        + _f(5, _entry(1, _f(1, 1) + _f(2, "tf_op")))
        + _f(5, _entry(2, _f(1, 2) + _f(2, probe)))
        + _f(5, _entry(3, _f(1, 3) + _f(2, "flops")))
        # metadata 1: the path as a string; 2: as a reference; 3: none
        + _f(4, _entry(1, _f(1, 1) + _f(2, "%fusion.1 = f32[8]{0} fusion()")
                       + _f(5, _f(1, 3) + _f(3, 7))
                       + _f(5, _f(1, 1) + _f(5, scan))))
        + _f(4, _entry(2, _f(1, 2) + _f(2, "%fusion.2 = s32[8]{0} fusion()")
                       + _f(5, _f(1, 1) + _f(7, 2))))
        + _f(4, _entry(3, _f(1, 3) + _f(2, "%copy.3 = s32[8]{0} copy()")))
        + _f(3, _f(1, 1) + _f(2, "XLA Ops")
             + _f(4, _f(1, 1) + _f(2, 1_000_000) + _f(3, 4_000_000))
             + _f(4, _f(1, 2) + _f(2, 6_000_000) + _f(3, 2_000_000))
             + _f(4, _f(1, 3) + _f(2, 9_000_000) + _f(3, 1_000_000))))
    host = (_f(2, "/host:CPU")
            + _f(4, _entry(1, _f(1, 1) + _f(2, "ct:statement")))
            + _f(3, _f(1, 1) + _f(2, "python3")
                 + _f(4, _f(1, 1) + _f(2, 0) + _f(3, 12_000_000))))
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "t.xplane.pb")
        with open(path, "wb") as f:
            f.write(_f(1, plane) + _f(1, host))
        paths = xspans.metadata_paths(path)
        red = xspans.reduce_spans(path, 1)
    bad = sums(red)
    if paths != {"/device:TPU:0": {
            "%fusion.1 = f32[8]{0} fusion()": scan,
            "%fusion.2 = s32[8]{0} fusion()": probe}}:
        bad.append(f"metadata paths: {paths}")
    if not (close(red["stage_ms"].get("scan_out"), 0.004)
            and close(red["stage_sub_ms"].get("bucket_probe/probe"), 0.002)
            and close(red["stage_ms"].get(xspans.UNSCOPED), 0.001)):
        bad.append(f"events did not join their metadata's path: "
                   f"{red['stage_ms']}")
    return bad


def no_names() -> list[str]:
    from jax.profiler import ProfileData

    from benchmark import xspans

    bad = []
    red = xspans.reduce_spans(fixture("tpch1_q1_v5e.xspace.txt"), 6)
    bad += sums(red)
    if red["scoped"] or red["spanned"]:
        bad.append("PR 25's trace holds no ct. scope and no ct: span")
    got = metrics_of(red)
    bad += [f"{m} reads {v} from a program that names nothing"
            for m, v in got.items()
            if v != (None if m.startswith("idle_") else red["ops_ms"]
                     if m == "stage_unscoped_ms" else 0.0)]
    if not close(red["idle_outside_ms"], red["idle_ms"]):
        bad.append("without spans every gap is outside")
    if xspans.reduce_spans(ProfileData.from_text_proto(
            'planes { id: 1 name: "/host:CPU" }'), 1) is not None:
        bad.append("a trace without a device plane reduces to nothing")
    if any(v is not None for v in metrics_of(None).values()):
        bad.append("a run without a reduction gives no metric")
    run = types.SimpleNamespace(window={"profile": None}, trace_dir="",
                                records=[])
    if xspans.of_run(run) is not None:
        bad.append("a run that profiled nothing gives no reduction")
    return bad


def recorded() -> list[str]:
    from benchmark import xspans

    with open(os.path.join(BENCH_DIR, "fixtures",
                           "spans_recorded.json")) as f:
        want = json.load(f)
    data = fixture(want["trace"])
    red = xspans.reduce_spans(data, want["n_statements"])
    bad = sums(red)
    got = metrics_of(red)
    for m, v in want["metrics"].items():
        if not close(got[m], v, 1e-9):
            bad.append(f"{want['trace']}: {m} is {got[m]}, recorded {v}")
    for k, v in want["expect"].items():
        have = red[k]
        if isinstance(v, float):
            same = close(have, v, 1e-9)
        elif isinstance(v, dict):
            same = set(have) == set(v) and all(
                close(have[x], v[x], 1e-9) for x in v)
        elif isinstance(v, list):  # the first entries, names and ms
            same = [[n, round(x, 9)] for n, x in have[:len(v)]] \
                == [[n, round(x, 9)] for n, x in v]
        else:
            same = have == v
        if not same:
            bad.append(f"{want['trace']}: {k} is {have}, recorded {v}")
    again = xspans.reduce_spans(
        type(data).from_text_proto(xspans.to_text_proto(data)),
        want["n_statements"])
    if again["stage_ms"] != red["stage_ms"] \
            or again["idle_metric_ms"] != red["idle_metric_ms"]:
        bad.append("a cut of the cut reduces to other numbers")
    return bad


GROUPS = {"hand_built": hand_built, "wire_format": wire_format,
          "no_names": no_names, "recorded": recorded}


def main() -> int:
    failed = 0
    for name, group in GROUPS.items():
        bad = group()
        print(("ok   " if not bad else "FAIL ") + name, flush=True)
        for what in bad:
            print("     " + what, flush=True)
        failed += bool(bad)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
