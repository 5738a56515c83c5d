"""ms a statement under the spans `subplan.drop`, summed, median
(`session.py` `_drop_temp`, from the statement's `finally`): each temp
table's catalog entry, its stripe's files and its resident feed
freed."""

from benchmark.layer_metrics import sources


def read(run):
    return sources.span_median(run, "subplan.drop")
