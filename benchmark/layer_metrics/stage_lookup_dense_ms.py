"""ms a statement of self time under `ct.lookup_join/ct.dense`, the
dense directory's build and probe, on the busiest device.  None where
the program writes no such sub-scope (any commit before PR 29): the
line then leaves the metric out."""

from benchmark import xspans


def read(run):
    red = xspans.of_run(run)
    if red is None:
        return None
    return red["stage_sub_ms"].get("lookup_join/dense")
