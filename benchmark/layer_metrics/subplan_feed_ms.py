"""ms a statement under the spans `subplan.feed`, summed, median
(`executor/feed.py` `_feed_scan`, inside the outer statement's `feed`):
an intermediate result's held arrays padded to the feed's capacity and
placed on every device.  What a result that stays on the device
(ROADMAP M11) would still remove, beside the pull and the combine.
None where no statement's tree holds the span (any commit before PR
38, whose feed read the result's stripe back)."""

from benchmark.layer_metrics import sources


def read(run):
    return sources.span_median(run, "subplan.feed")
