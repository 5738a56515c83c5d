"""Rows a statement of the window stored as intermediate results
(`intermediate_rows_total` over the window's statements): what came
back from the device, was typed and interned on the host, written as a
stripe and placed on every device again for the outer program.  Q13
stores one row a customer.  None where the program has no such counter
(any commit before PR 35)."""


def read(run):
    counters = run.window.get("counters", {})
    if "intermediate_rows_total" not in counters:
        return None
    return counters["intermediate_rows_total"] / max(len(run.records), 1)
