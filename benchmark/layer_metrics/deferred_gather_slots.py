"""Index slots a statement's deferred gathers read
(`deferred_gather_slots_total` over the window's statements, all
clients): the sum, over every gather the converged programs issued for a
column carried across a compaction or a lookup as a row index — values,
null masks and index compositions — of its index's static size over the
mesh, counted by the compiler at trace time.  What the order of an index
chain's composition decides: from the top, every gather of a chain runs
at its narrowest index's size.  None where the program has no such
counter, and does not raise."""

from benchmark.layer_metrics.sources import counter_per_statement


def read(run):
    return counter_per_statement(run, "deferred_gather_slots_total")
