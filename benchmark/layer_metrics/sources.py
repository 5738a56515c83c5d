"""What the per-layer readers added in PR 37 share: a reading of the
run that is None, and does not raise, where the program under test
wrote no such span or counter (the parent of the PR that adds one runs
the traced run with these files laid over it)."""

from __future__ import annotations

import numpy as np

from benchmark import xspans


def idle_under(run, span: str) -> float | None:
    """ms a statement the busiest device idled while `span` was the
    innermost span open on the statement's thread
    (`xspans.of_run(run)["idle_by_span_ms"]`).  None without a device
    trace, and where no statement's span tree holds the name; 0.0 where
    the span is there and the device never idled under it."""
    red = xspans.of_run(run)
    if red is None or not any(span in (r.get("spans") or ())
                              for r in run.records):
        return None
    return red["idle_by_span_ms"].get(span, 0.0)


def counter_per_statement(run, counter: str, scale: float = 1.0
                          ) -> float | None:
    """A session counter's delta over the window, all clients, over the
    window's statements; None where the counters lack the name."""
    counters = run.window.get("counters", {})
    if counter not in counters:
        return None
    return counters[counter] * scale / max(len(run.records), 1)


def span_median(run, span: str, *with_spans: str) -> float | None:
    """ms a statement under `span` (every span of that name in the
    statement's tree, summed, plus those of `with_spans` where
    present), median over the window's statements whose tree holds
    `span`; None where none does."""
    per_stmt = [r["spans"][span]
                + sum(r["spans"].get(n, 0.0) for n in with_spans)
                for r in run.records
                if r.get("spans") and span in r["spans"]]
    return float(np.median(per_stmt)) if per_stmt else None
