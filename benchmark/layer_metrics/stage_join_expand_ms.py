"""ms a statement of self time under `ct.join_out/ct.expand`, the
general join path's pair emission (`ops.join.expand_join_pairs`: match
ranges on the build side, a prefix sum, one output slot a pair), on the
busiest device.  None where the program writes no such sub-scope (a
plan whose joins are all fused lookups, any commit before PR 35, a CPU
run): the line then leaves the metric out."""

from benchmark import xspans


def read(run):
    red = xspans.of_run(run)
    if red is None:
        return None
    return red["stage_sub_ms"].get("join_out/expand")
