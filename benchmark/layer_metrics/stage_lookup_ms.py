from benchmark import xspans


def read(run):
    return xspans.stage_ms(run, "lookup_join")
