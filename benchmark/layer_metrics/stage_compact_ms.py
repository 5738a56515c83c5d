"""ms a statement of self time under the `ct.compact` sub-scope of any
stage (`join_out/compact`, `scan_out/compact`, `agg_out/compact`):
`PlanCompiler._compact`'s search for the survivors' positions and its
gathers at the compacted size, on the busiest device.  None where the
program writes no such sub-scope (any commit before PR 30): the line
then leaves the metric out."""

from benchmark import xspans


def read(run):
    red = xspans.of_run(run)
    if red is None:
        return None
    subs = [ms for key, ms in red["stage_sub_ms"].items()
            if key.endswith("/compact")]
    return sum(subs) if subs else None
