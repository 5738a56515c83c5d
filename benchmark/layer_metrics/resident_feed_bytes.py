"""Device bytes of scan feeds a statement of the window was served from
the feed cache: what stayed resident between statements
(`feed_cache_hit_bytes_total` over the window's statements).  None
where the program has no such counter (any commit before PR 33):
`reduce.py`'s `window_counter` would raise there, so this reader asks
first."""


def read(run):
    counters = run.window.get("counters", {})
    if "feed_cache_hit_bytes_total" not in counters:
        return None
    return counters["feed_cache_hit_bytes_total"] / max(len(run.records), 1)
