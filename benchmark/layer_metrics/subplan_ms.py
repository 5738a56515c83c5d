"""ms a statement under the span `subplan`, median over the window's
statements: a subplan planned, run on the device, fetched, combined and
(`subplan.store`, its child) written as a temp reference table — all of
it before the outer statement's own program is dispatched.  None where
no statement's span tree holds the name (any commit before PR 35, or a
statement without a subplan): `reduce.py`'s `window_spans` would read
0.0 there."""

import numpy as np


def read(run):
    per_stmt = [r["spans"]["subplan"] for r in run.records
                if r.get("spans") and "subplan" in r["spans"]]
    return float(np.median(per_stmt)) if per_stmt else None
