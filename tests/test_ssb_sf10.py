"""tests/test_ssb.py's cases once more, under the lookup arms SSB picks
at SF10: `customer` (300,000 keys) and `part` (800,000) are over
ops.join.SORTED_LOOKUP_MIN_EXTENT there, `supplier` (20,000) and
`dwdate` (61,130 slots at any scale) under it, so a star join runs both
arms in one fragment — the benchmark cell `ssb10.q4_1`.  At this file's
scale (SF0.05) the predicate the planner asks is patched to sort exactly
the `customer` and `part` joins; the program is not changed (plans,
programs and capacity memos key on the pick).  All thirteen statements
on one device and on four, two seeds, exact; Q4.1's plan, EXPLAIN tags
and counters; the feed cache's two byte counters.

A file of its own: the driver gives each file to one worker process,
and XLA's CPU backend does not survive both files' compilations in one
(pytest.ini)."""

import pytest
from test_ssb import (  # noqa: F401 — fixtures, and the cases themselves
    PARAMS,
    loaded,
    rows_of,
    test_q4_1_feeds_stay_resident,
    test_q4_1_plan_and_counters,
    test_statement_matches_reference,
)

from benchmark.datasets import ssb
from citus_tpu.ops import join


def test_sf10_extents_flip_the_pick():
    """From the paper's row counts alone: at SF10 `customer` (300,000
    keys) and `part` (800,000) are over the sorted lookup's extent,
    `supplier` (20,000) and `dwdate` (61,130 slots at any scale) under
    it; at SF1 all four are under."""
    date_extent = 19981230 - 19920101 + 1
    assert date_extent == 61_130
    at10, at1 = ssb.table_rows(10.0), ssb.table_rows(1.0)
    assert (at10["customer"], at10["part"], at10["supplier"]) == (
        300_000, 800_000, 20_000)
    assert join.sorted_lookup_eligible(at10["customer"])
    assert join.sorted_lookup_eligible(at10["part"])
    assert not join.sorted_lookup_eligible(at10["supplier"])
    assert not join.sorted_lookup_eligible(date_extent)
    assert not any(join.sorted_lookup_eligible(at1[t])
                   for t in ("customer", "part", "supplier"))


@pytest.fixture
def arms(monkeypatch):
    """Overrides test_ssb.py's: two of Q4.1's four lookups sort."""
    rows = ssb.table_rows(PARAMS["scale_factor"])
    monkeypatch.setattr(
        join, "sorted_lookup_eligible",
        lambda extent: extent in (rows["customer"], rows["part"]))
    return 2
