"""ms a statement the device idles under `mesh.fetch.pull`
(`executor/runner.py` `_dispatch`: the one `jax.device_get` of the
packed block and the overflow block): the program has ended and its
`fetch_bytes` travel to the host and are copied there."""

from benchmark.layer_metrics import sources


def read(run):
    return sources.idle_under(run, "mesh.fetch.pull")
