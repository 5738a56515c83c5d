"""How far the fullest bucket of the window's exchanges stood above the
mean bucket: `repartition_hot_bucket_rows_total` × buckets /
`repartition_rows_total`, where an exchange has devices × devices
(source, target) buckets that share one static capacity.  1.0 is a
balanced shuffle; at 1.19 every bucket is sized for 19 % more rows than
the mean one holds.  None where the program has no such counters (any
commit before PR 35) or no recorded exchange ran."""


def read(run):
    counters = run.window.get("counters", {})
    rows = counters.get("repartition_rows_total")
    hot = counters.get("repartition_hot_bucket_rows_total")
    if not rows or hot is None:
        return None
    return hot * int(run.cell.config["n_devices"]) ** 2 / rows
