"""Operands of the build-side sorts of a statement's multi-key joins
(`join_sort_operands_total` over the window's statements, all clients):
one a join whose keys the program packed into one int64 over the
ranges the planner knew (`ops.join.pack_keys`; a (key, position)
composite), else the keys and the validity of a lexsort, counted by
the compiler at trace time.  TPC-DS Q57 reads 2, its CTE's two
self-joins on (category, brand, call center, rank +/- 1); 10 without
the packing.  None where the program has no such counter (one older than
the counter)."""

from benchmark.layer_metrics.sources import counter_per_statement


def read(run):
    return counter_per_statement(run, "join_sort_operands_total")
