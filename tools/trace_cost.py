#!/usr/bin/env python3
"""tools/trace_cost.py — what the recorder and the collector cost a
statement of a benchmark cell, from the harness's own run.

    cd <checkout> && python3 <this file> --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs `benchmark/run.py` of the checkout it is started in (so a parent's
`git archive` is measured by the same tool), unchanged but for a watch
around its window, and prints one more line after the result line:

* `gc_per_stmt`: collections a statement of the window by generation
  (`gc.get_stats()` before and after the window), what `_gc_hook`
  (stats/tracing.py) is called for: generation 0 costs it a call and
  one comparison, generations 1 and 2 two counter increments and, on a
  traced statement, a span;
* `p50_inside_ms`, `p50_outside_ms` (--trace 1): the median latency of
  the statements that ended inside the profiled stretch and of the
  window's others: the recorder's on-cost, annotations written into a
  live profiler session and all;
* `counters_per_stmt`: the window's deltas of the counters the host
  path added in PR 37, where the checkout's program has them.

The numbers are the chip's host only when the run is (`chiprun`).
"""

from __future__ import annotations

import gc
import json
import os
import sys

COUNTERS = ("fetch_bytes_total", "gc_pauses_total", "gc_pause_us_total")


def main(argv: list[str]) -> int:
    sys.path.insert(0, os.getcwd())
    import numpy as np

    from benchmark import run as harness

    seen: dict = {}
    drive = harness.drive

    def watched(run, clients):
        before = gc.get_stats()
        drive(run, clients)
        seen["gc"] = [a["collections"] - b["collections"]
                      for a, b in zip(gc.get_stats(), before)]
        seen["run"] = run

    harness.drive = watched
    rc = harness.main(argv)
    run = seen.get("run")
    if run is None:
        return rc
    n = max(len(run.records), 1)
    out = {"phase": "trace_cost", "workload": run.cell.name,
           "seed": run.seed, "trace": int(run.trace),
           "statements": len(run.records),
           "gc_per_stmt": [c / n for c in seen["gc"]],
           "gc_thresholds": list(gc.get_threshold()),
           "counters_per_stmt": {
               k: v / n for k, v in run.window.get("counters", {}).items()
               if k in COUNTERS}}
    prof = run.window.get("profile")
    if prof is not None:
        lat = {True: [], False: []}
        for r in run.records:
            lat[prof["t0"] <= r["t1"] <= prof["t1"]].append(
                (r["t1"] - r["t0"]) * 1e3)
        out["statements_inside"] = len(lat[True])
        for key, where in (("p50_inside_ms", True),
                           ("p50_outside_ms", False)):
            out[key] = float(np.median(lat[where])) if lat[where] else None
    print(json.dumps(out), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
