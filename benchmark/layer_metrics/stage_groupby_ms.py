from benchmark import xspans


def read(run):
    return xspans.stage_ms(run, "agg_grid", "agg_bucket", "agg_sort", "agg_out",
                           "agg_global")
