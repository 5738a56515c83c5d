from benchmark import xspans


def read(run):
    return xspans.stage_ms(run, "feed_unpack", "decode", "scan_out")
