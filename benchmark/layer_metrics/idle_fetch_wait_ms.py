"""ms a statement the device idles under `mesh.fetch.wait`
(`executor/runner.py` `_dispatch`: `jax.block_until_ready(out)`): the
dispatch has returned and the program has not ended, so the idle time
here is the launch head before its first operation, and the host's
wake-up after its last.  With `idle_fetch_pull_ms` and `settle` it adds
up to `idle_fetch_ms`."""

from benchmark.layer_metrics import sources


def read(run):
    return sources.idle_under(run, "mesh.fetch.wait")
