"""Probe slots a statement of the window ran its fused lookup joins
over (`lookup_probe_slots_total` over the window's statements): the sum,
over the converged program's fused lookups, of the probe side's static
size over the mesh — the quantity the join order decides, since every
later probe, gather and sort of a star join is sized by what the
earlier lookups kept.  None where the program has no such counter (any
commit before PR 34): `reduce.py`'s `window_counter` would raise there,
so this reader asks first."""


def read(run):
    counters = run.window.get("counters", {})
    if "lookup_probe_slots_total" not in counters:
        return None
    return counters["lookup_probe_slots_total"] / max(len(run.records), 1)
