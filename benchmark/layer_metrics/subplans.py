"""Subplans a statement of the window executed ahead of its own plan
(`subplans_executed` over the window's statements): derived tables,
CTEs, set-operation sides and expression subqueries that recursive
planning ran and, but for the last, stored as intermediate results.
Q13 reads 1, its one derived table.  None where the program has no
such counter."""


def read(run):
    counters = run.window.get("counters", {})
    if "subplans_executed" not in counters:
        return None
    return counters["subplans_executed"] / max(len(run.records), 1)
