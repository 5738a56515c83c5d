"""Device bytes of scan feeds a statement of the window had to build
anew — read, decode, place — because the feed cache held none
(`feed_cache_miss_bytes_total` over the window's statements); 0 where
the deployment is resident.  None where the program has no such counter
(any commit before PR 33): `reduce.py`'s `window_counter` would raise
there, so this reader asks first."""


def read(run):
    counters = run.window.get("counters", {})
    if "feed_cache_miss_bytes_total" not in counters:
        return None
    return counters["feed_cache_miss_bytes_total"] / max(len(run.records), 1)
