"""Fused lookup joins a statement of the window ran on the sort-and-scan
arm (`lookup_sorted_joins_total` over the window's statements).  None
where the program has no such counter (any commit before PR 33):
`reduce.py`'s `window_counter` would raise there, so this reader asks
first."""


def read(run):
    counters = run.window.get("counters", {})
    if "lookup_sorted_joins_total" not in counters:
        return None
    return counters["lookup_sorted_joins_total"] / max(len(run.records), 1)
