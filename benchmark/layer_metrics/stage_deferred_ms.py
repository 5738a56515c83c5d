"""ms a statement of self time under the `ct.deferred` sub-scope of any
stage (`join_out/deferred`, `agg_grid/deferred`, …): the gathers of
columns that crossed a compaction or a lookup as a row index
(`Block.take`) and the compositions of two such indexes, each filed
under the stage that first read the column, on the busiest device.
None where the program writes no such sub-scope (any commit before
PR 32): the line then leaves the metric out."""

from benchmark import xspans


def read(run):
    red = xspans.of_run(run)
    if red is None:
        return None
    subs = [ms for key, ms in red["stage_sub_ms"].items()
            if key.endswith("/deferred")]
    return sum(subs) if subs else None
