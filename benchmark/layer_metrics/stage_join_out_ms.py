from benchmark import xspans


def read(run):
    return xspans.stage_ms(run, "join_out")
