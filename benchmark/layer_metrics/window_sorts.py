"""Lexsorts a statement's window operators ran (`window_sorts_total`
over the window's statements, all clients): one a window ORDER BY spec
whose rows the program sorted by validity, partition keys and order
keys (`PlanCompiler._window_sort`), counted by the compiler at trace
time.  A window over groups that reach it in its partition and ORDER BY
order sorts nothing (`WindowNode.presorted`; `window_presorted_total`
beside it in the counters and in no line): TPC-DS Q57 reads 0, its two
windows over the five-key group-by's groups.  None where the program
has no such counter (one older than the counter)."""

from benchmark.layer_metrics.sources import counter_per_statement


def read(run):
    return counter_per_statement(run, "window_sorts_total")
