"""Intermediate results a statement of the window handed to its outer
statement's feed in memory (`intermediate_resident_total` over the
window's statements; `session.py` `_store_result`, `storage/
table_store.py` `hold_resident`): typed arrays held by the store, no
stripe written, read back and deleted in between.  Q13 reads 1, equal
to `subplans`.  None where the program has no such counter (any commit
before PR 38, which wrote every one of them as a stripe)."""

from benchmark.layer_metrics import sources


def read(run):
    return sources.counter_per_statement(run, "intermediate_resident_total")
