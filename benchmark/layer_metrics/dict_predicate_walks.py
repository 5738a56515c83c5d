"""Dictionary walks a statement of the window made to turn a string
predicate (LIKE, IN, BETWEEN, <) into codes at bind time
(`dict_predicate_walks_total` over the window's statements).  A
dictionary keeps each predicate's codes and walks only what it has
grown by, so a repeated statement reads 0; 1 means every bind visits
every distinct value again, on the host, with the device idle.  None
where the program has no such counter (any commit before PR 35)."""


def read(run):
    counters = run.window.get("counters", {})
    if "dict_predicate_walks_total" not in counters:
        return None
    return counters["dict_predicate_walks_total"] / max(len(run.records), 1)
