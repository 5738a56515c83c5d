"""Packed slots a statement of the window ran its bucketed group-bys
over (`agg_bucket_slots_total` over the window's statements): the sum,
over the converged programs' bucketed group-bys, of the pack's static
size over the mesh — the quantity the pack's layout decides, since the
sort, the cuts, the one-hot products and the scatter reductions all run
over every packed slot, live or garbage.  None where the program has no
such counter (any commit before PR 36): `reduce.py`'s `window_counter`
would raise there, so this reader asks first."""


def read(run):
    counters = run.window.get("counters", {})
    if "agg_bucket_slots_total" not in counters:
        return None
    return counters["agg_bucket_slots_total"] / max(len(run.records), 1)
