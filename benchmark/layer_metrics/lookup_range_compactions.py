"""Fused inner lookups a statement's converged programs compact to the
rows inside the build keys' range before the probe
(`lookup_range_compactions_total` over the window's statements, all
clients), counted by the compiler at trace time.  Capacity feedback
installs such a compaction only where the range keeps few enough of the
probe side's rows for it to pay, so it reads 1 where a date filter
keeps one interval of a fact table's date keys and 0 where the range
covers nearly every key.  None where the program has no such counter,
and does not raise."""

from benchmark.layer_metrics.sources import counter_per_statement


def read(run):
    return counter_per_statement(run, "lookup_range_compactions_total")
