"""Columns a statement of the window carried across a compaction or a
lookup as a row index, without a gather at that step
(`deferred_columns_total` over the window's statements; a null mask
counts as a column).  None where the program has no such counter (any
commit before PR 32): `reduce.py`'s `window_counter` would raise there,
so this reader asks first."""


def read(run):
    counters = run.window.get("counters", {})
    if "deferred_columns_total" not in counters:
        return None
    return counters["deferred_columns_total"] / max(len(run.records), 1)
