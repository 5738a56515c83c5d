"""ms a statement of self time under `ct.lookup_join/ct.sort` and
`ct.lookup_join/ct.carry`, the sort-and-scan lookup's three sorts and
what it carries between them (`ops.join.sorted_unique_lookup`), on the
busiest device.  Its two scans are not in it: the chip's compiler turns
them into `reduce-window` trees that carry no scope, so they read as
`stage_unscoped_ms`.  None where the program writes neither sub-scope
(a plan with no sorted lookup, any commit before PR 28, a CPU run): the
line then leaves the metric out."""

from benchmark import xspans


def read(run):
    red = xspans.of_run(run)
    if red is None:
        return None
    subs = [red["stage_sub_ms"][key]
            for key in ("lookup_join/sort", "lookup_join/carry")
            if key in red["stage_sub_ms"]]
    return sum(subs) if subs else None
