"""benchmark/xspans.py, the reader of the `ct.` stage scopes and `ct:`
spans in a profiler trace, and the ten per-layer metrics over it: every
group of benchmark/selftest_spans.py, on the CPU (it reduces recorded
and hand-built traces; no session, no chip)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark import selftest_spans  # noqa: E402


@pytest.mark.parametrize("group", sorted(selftest_spans.GROUPS))
def test_spans_reader(group):
    assert selftest_spans.GROUPS[group]() == []


def test_new_metrics_are_entries_of_the_benchmark():
    """Every metric the self-test holds to a known answer is a
    `per_layer` entry with its reader beside the others, and the stage
    names the yardstick sums are stages the program registers."""
    import json

    from benchmark import xspans
    from citus_tpu.stats.tracing import SPAN_NAMES, STAGE_NAMES

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in selftest_spans.METRICS:
        assert entries[name]["unit"] == "ms/stmt"
        assert entries[name]["source"] == "device_trace"
        assert os.path.exists(os.path.join(
            root, "benchmark", "layer_metrics", name + ".py"))
    # exact: the yardstick still sums the retired bucketed probe's scope,
    # which reads 0; the next `benchmark` PR drops it, this set becomes
    # empty, and any other drift fails here as well
    assert xspans.STAGES - set(STAGE_NAMES) == {"bucket_probe"}
    for names in xspans.IDLE_METRICS.values():
        assert set(names) <= set(SPAN_NAMES)


# ---------------------------------------------------------------------------
# the seven readers of PR 37 and the two of PR 38: silent without their
# source, the value on a run built by hand

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ALL_CELLS = ["tpch1.q1", "tpch1.q3", "tpch4.q3", "ssb1.q4_1",
             "ssb10.q4_1", "tpch4z.q13"]
# name: (unit, source, layer, cells, the value on `_built_run`)
HOST_PATH_READERS = {
    "idle_fetch_wait_ms": ("ms/stmt", "device_trace", "dispatch and fetch",
                           ALL_CELLS, 0.75),
    "idle_fetch_pull_ms": ("ms/stmt", "device_trace", "dispatch and fetch",
                           ALL_CELLS, 0.0),
    "fetch_bytes": ("bytes/stmt", "program_counter", "dispatch and fetch",
                    ALL_CELLS, 2048.0),
    "subplan_store_type_ms": (
        "ms", "program_span", "recursive planning and intermediate results",
        ["tpch4z.q13"], 4.0),
    "subplan_store_append_ms": (
        "ms", "program_span", "recursive planning and intermediate results",
        ["tpch4z.q13"], 3.5),
    "subplan_drop_ms": (
        "ms", "program_span", "recursive planning and intermediate results",
        ["tpch4z.q13"], 0.5),
    "gc_pause_ms": ("ms/stmt", "program_counter",
                    "session front end and planner", ALL_CELLS, 0.25),
    "resident_intermediates": (
        "count/stmt", "program_counter",
        "recursive planning and intermediate results", ["tpch4z.q13"], 0.75),
    "subplan_feed_ms": (
        "ms", "program_span", "recursive planning and intermediate results",
        ["tpch4z.q13"], 2.5),
    # the index slots of the deferred gathers, over the cells whose
    # programs carry a column as a row index
    "deferred_gather_slots": (
        "count/stmt", "program_counter", "device program",
        ALL_CELLS[1:] + ["tpcds10.q89"], 900.0),
    # the index slots of the sort-path group-bys' gathers, over the
    # cells whose group-by takes the sort path
    "agg_sort_gather_slots": (
        "count/stmt", "program_counter", "device program",
        ["tpch1.q3", "tpch4.q3", "tpcds10.q89", "tpcds10cs.q57"], 1200.0),
    # the fused lookups compacted to their build keys' range, over the
    # cells whose programs fuse an inner lookup
    "lookup_range_compactions": (
        "count/stmt", "program_counter", "device program",
        ["tpch1.q3", "tpch4.q3", "ssb1.q4_1", "ssb10.q4_1", "tpcds10.q89",
         "tpcds10cs.q57"], 1.0),
}
BETTER_HIGHER = {"resident_intermediates", "lookup_range_compactions"}


def _built_run():
    """Three statements: two with a derived table (one of them with a
    string column, one dropping two temp tables), one without; the
    device never idled under `mesh.fetch.pull`."""
    from types import SimpleNamespace

    fetch = {"mesh.fetch": 3.0, "mesh.fetch.wait": 2.0,
             "mesh.fetch.pull": 1.0}
    records = [
        {"t1": 0.0, "spans": {**fetch, "subplan.store.type": 3.0,
                              "subplan.store.append": 2.0,
                              "subplan.feed": 2.0,
                              "subplan.drop": 0.25}},
        {"t1": 0.0, "spans": {**fetch, "subplan.store.type": 5.0,
                              "subplan.store.append": 4.0,
                              "subplan.store.intern": 1.0,
                              "subplan.feed": 3.0,
                              "subplan.drop": 0.75}},
        {"t1": 0.0, "spans": dict(fetch)},
        {"t1": 0.0, "spans": None},
    ]
    run = SimpleNamespace(
        window={"counters": {"fetch_bytes_total": 8192,
                             "gc_pause_us_total": 1000,
                             "gc_pauses_total": 2,
                             "intermediate_resident_total": 3,
                             "deferred_gather_slots_total": 4 * 900,
                             "agg_sort_gather_slots_total": 4 * 1200,
                             "lookup_range_compactions_total": 4},
                "profile": None},
        records=records, trace_dir="/nonexistent",
        cell=SimpleNamespace(config={"n_devices": 4}))
    # what xspans.of_run keeps on the run once it has reduced a trace
    run._xspans = {"spanned": True,
                   "idle_by_span_ms": {"mesh.fetch.wait": 0.75,
                                       "plan": 1.0}}
    return run


@pytest.mark.parametrize("name", sorted(HOST_PATH_READERS))
def test_host_path_reader_is_silent_without_its_source(name):
    """On the parent (no such span, no such counter), in an untraced
    shape and without a device trace each reader returns None and does
    not raise, so the line leaves the metric out."""
    from types import SimpleNamespace

    from benchmark import run as harness

    cell = SimpleNamespace(config={"n_devices": 4})
    parent = SimpleNamespace(
        window={"counters": {"shuffle_bytes_total": 1}, "profile": None},
        records=[{"t1": 0.0, "spans": {"plan": 1.0, "mesh.fetch": 2.0,
                                       "subplan.store": 3.0}}],
        trace_dir="/nonexistent", cell=cell)
    assert harness.layer_metric(parent, name) is None
    # … and with a device trace reduced: the idle readers still find
    # no statement that holds their span
    parent._xspans = {"spanned": True,
                      "idle_by_span_ms": {"mesh.fetch": 2.0}}
    assert harness.layer_metric(parent, name) is None
    empty = SimpleNamespace(window={}, records=[],
                            trace_dir="/nonexistent", cell=cell)
    assert harness.layer_metric(empty, name) is None


@pytest.mark.parametrize("name", sorted(HOST_PATH_READERS))
def test_host_path_reader_reads_a_hand_built_run(name):
    import json

    from benchmark import run as harness

    unit, source, layer, cells, want = HOST_PATH_READERS[name]
    assert harness.layer_metric(_built_run(), name) == pytest.approx(want)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert entries[name] == {
        "name": name, "unit": unit,
        "better": "higher" if name in BETTER_HIGHER else "lower",
        "source": source,
        "layer": layer, "moves": "latency_p50_ms", "workloads": cells}


@pytest.mark.parametrize("sub", ["sort", "scan"])
def test_window_sub_scopes_are_filed_under_the_window_stage(sub):
    """PR 40's `window/sort` and `window/scan`: an operation under either
    belongs to the `window` stage (what `stage_window_ms` reads) and to
    its sub-scope; neither sub-scope is a stage of the yardstick."""
    from benchmark import xspans
    from citus_tpu.stats.tracing import STAGE_NAMES

    assert sub in STAGE_NAMES and "window" in STAGE_NAMES[sub]
    assert sub not in xspans.STAGES
    path = f"jit(packed_fn)/ct.window/ct.{sub}/while/body/add"
    assert xspans.stage_of(path) == ("window", sub)
