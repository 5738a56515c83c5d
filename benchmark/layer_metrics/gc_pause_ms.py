"""ms a statement of the window spent in collections of generation 1
or 2 that ran on the statement's thread while it was open
(`gc_pause_us_total`, `stats/tracing.py` `_gc_hook`; `gc_pauses_total`
counts them, and a traced statement holds each as a `gc.pause` span)."""

from benchmark.layer_metrics import sources


def read(run):
    return sources.counter_per_statement(run, "gc_pause_us_total", 1e-3)
