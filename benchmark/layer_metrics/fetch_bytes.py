"""Bytes a statement of the window pulled back from the mesh
(`fetch_bytes_total`: the packed block, `[2 x columns + 1, devices x
capacity]` 64-bit words, and the overflow block of every execution,
`executor/runner.py` beside `settle`): what `idle_fetch_pull_ms` is the
price of."""

from benchmark.layer_metrics import sources


def read(run):
    return sources.counter_per_statement(run, "fetch_bytes_total")
