"""ms a statement under the span `subplan.store.append`, median
(`session.py` `_store_result`): the temp reference table created and
its one stripe written — with `subplan.store.intern`, a string column's
dictionary, where the result has one (Q13's has none)."""

from benchmark.layer_metrics import sources


def read(run):
    return sources.span_median(run, "subplan.store.append",
                               "subplan.store.intern")
