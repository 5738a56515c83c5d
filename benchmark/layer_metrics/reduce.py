"""The one reducer behind every `layer_metrics/<name>.json`: a per-layer
metric that is a reduction of spans, counters, scan statistics or the
device trace names its source in a data file and needs no code.

    {"from": "window_spans", "spans": [...], "reduce": "median"}
        per statement of the window, the sum of the named spans' ms
        (stats/tracing.py span tree); median | mean | p95 | sum
    {"from": "first_answer_spans", "spans": [...]}
        the same for the statement that gave first_answer_s
    {"from": "first_answer_scan", "field": "bytes_on_wire"}
        a ScanPhaseStats field of that statement
    {"from": "window_programs", "fields": [...]}
        programs compiled / loaded / retried inside the window, summed
    {"from": "window_counter", "counter": "...", "per": "statement"}
        a session counter's delta over the window (all clients)
    {"from": "device_trace", "field": "..."}
        a field of xtrace.reduce_trace()

`scale` multiplies the value (0.001: ms → s).  A source that is not
there gives None, and the harness leaves the metric out of the line.
"""

from __future__ import annotations

import numpy as np

_REDUCE = {"median": np.median, "mean": np.mean, "sum": np.sum,
           "p95": lambda v: np.percentile(v, 95)}


def _span_sum(spans: dict, names: list[str]) -> float:
    return sum(spans.get(n, 0.0) for n in names)


def read(run, spec: dict):
    src, scale = spec["from"], float(spec.get("scale", 1.0))
    if src == "window_spans":
        per_stmt = [_span_sum(r["spans"], spec["spans"])
                    for r in run.records if r.get("spans")]
        if not per_stmt:
            return None
        return float(_REDUCE[spec.get("reduce", "median")](per_stmt)) * scale
    if src in ("first_answer_spans", "first_answer_scan"):
        fa = run.first_answer
        if fa is None:
            return None
        if src == "first_answer_scan":
            return fa["scan"][spec["field"]] * scale
        if fa["spans_ms"] is None:
            return None
        return _span_sum(fa["spans_ms"], spec["spans"]) * scale
    if src == "window_programs":
        return sum(run.window["programs"][f] for f in spec["fields"]) * scale
    if src == "window_counter":
        v = run.window["counters"][spec["counter"]]
        if spec.get("per") == "statement":
            v = v / max(len(run.records), 1)
        return v * scale
    if src == "device_trace":
        if run.device_trace is None:
            return None
        return run.device_trace[spec["field"]] * scale
    raise ValueError(f"layer metric source {src!r} is not known")
