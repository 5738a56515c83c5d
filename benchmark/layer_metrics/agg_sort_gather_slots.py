"""Index slots a statement's sort-path group-bys gather
(`agg_sort_gather_slots_total` over the window's statements, all
clients): the sum, over every gather the converged programs'
`segment_aggregate` made — the value columns and their null masks in
sorted order at the input's width, each group's first row, key values
and reductions at the group capacity — of its index's static size, over
both levels of a repartition and over the mesh, counted by the compiler
at trace time.  What the width the group-by's outputs are made at
decides.  None where the program has no such counter, and does not
raise."""

from benchmark.layer_metrics.sources import counter_per_statement


def read(run):
    return counter_per_statement(run, "agg_sort_gather_slots_total")
