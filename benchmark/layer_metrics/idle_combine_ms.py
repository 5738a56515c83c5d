from benchmark import xspans


def read(run):
    return xspans.idle_ms(run, "combine")
