"""Times the memory governor acted inside the window, all clients:
statements streamed in batches, multi-pass spill passes, allocator OOMs,
feed-cache entries evicted for memory and stream-batch shrinks, summed
(`executor/hbm.py`, `executor/runner.py` `degrade_for_oom`,
`executor/stream.py`); 0 where the deployment runs resident.  None where
the window's counters lack any of the five."""

COUNTERS = ("queries_streamed", "spill_passes_total", "oom_events_total",
            "cache_evictions_total", "stream_batch_shrinks_total")


def read(run):
    counters = run.window.get("counters", {})
    if any(name not in counters for name in COUNTERS):
        return None
    return sum(counters[name] for name in COUNTERS)
