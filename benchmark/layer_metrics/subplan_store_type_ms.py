"""ms a statement under the span `subplan.store.type`, median
(`session.py` `_store_result`): an intermediate result's columns walked
as Python objects for their type, their NULL masks and typed copies."""

from benchmark.layer_metrics import sources


def read(run):
    return sources.span_median(run, "subplan.store.type")
