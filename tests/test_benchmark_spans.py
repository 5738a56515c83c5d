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
