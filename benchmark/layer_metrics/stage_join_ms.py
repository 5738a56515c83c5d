from benchmark import xspans


def read(run):
    return xspans.stage_ms(run, "bucket_probe", "lookup_join", "join_out")
