"""String values a statement typed and interned into its intermediate
results' dictionaries on the host (`intermediate_string_values_total`
over the window's statements, all clients): rows times string columns
of every result recursive planning held as a temp table
(`Session._store_result`, under `subplan.store.type` and
`subplan.store.intern`).  TPC-DS Q57 reads 3 x (rows of v1 + rows of
v2).  None where the program has no such counter (one older than the
counter)."""

from benchmark.layer_metrics.sources import counter_per_statement


def read(run):
    return counter_per_statement(run, "intermediate_string_values_total")
