"""bench_kernels.py harness smoke tests: one tiny shape per
subcommand, so the A/B harnesses can't silently rot while the full
runs stay reserved for real hardware.  slow-marked like the probe
smoke in test_ops — microbench compiles have no place in the tier-1
budget (the full runs are what the driver captures on a chip)."""

import pathlib
import sys

import pytest

pytestmark = pytest.mark.slow

root = pathlib.Path(__file__).resolve().parent.parent
if str(root) not in sys.path:
    sys.path.insert(0, str(root))


def test_groupby_harness_smoke(tmp_path):
    """`python bench_kernels.py groupby` at a toy shape, uniform and
    skewed: every form of the chunked grid (each chunk size, and the
    element-gather pack kept in the tool) agrees with the sort path,
    and the table is written where asked."""
    import bench_kernels

    rows = bench_kernels.bench_groupby(
        regimes=[(1 << 13, 3 << 12, "uniform"),
                 (1 << 13, 3 << 12, "skewed")],
        repeats=1, reps=2, out=str(tmp_path))
    assert len(rows) == 10 and all(r["agrees"] for r in rows)
    assert {r["form"] for r in rows} == {
        "sort", "chunked_1024", "chunked_2816", "chunked_4096",
        "chunked_4096_by_gather"}
    assert (tmp_path / "bench_groupby.json").exists()


def test_dense_aggregate_harness_smoke():
    """The default (dense segment-aggregation) A/B at a toy shape:
    both formulations produce a timing row and agree with the numpy
    reference."""
    import bench_kernels

    rows = bench_kernels.main(regimes=[(1 << 12, 64)])
    assert len(rows) == 1
    n, k, t_seg, t_oh, ok = rows[0]
    assert t_seg > 0 and t_oh > 0 and ok


def test_compact_harness_smoke(tmp_path):
    """`python bench_kernels.py compact small`: the scatter form kept
    in the tool and `survivor_positions` agree at every shape, and the
    table is written where asked."""
    import bench_kernels

    rows = bench_kernels.bench_compact(small=True, out=str(tmp_path))
    forms = [r for r in rows if "form" in r]
    assert len(forms) == 20 and all(r["agrees"] for r in forms)
    assert (tmp_path / "bench_compact.json").exists()


def test_carry_harness_smoke(tmp_path):
    """`python bench_kernels.py carry small`: the eager form kept in
    the tool and `Block.take` agree for every column count, and the
    table is written where asked."""
    import bench_kernels

    rows = bench_kernels.bench_carry(small=True, out=str(tmp_path))
    assert len(rows) == 12 and all(r["agrees"] for r in rows)
    by_form = {(r["form"], r["columns"], r["read_after_first"]): r
               for r in rows}
    # five columns, none read in between: ten gathers against six
    assert by_form["deferred", 5, 0]["slots_gathered"] < \
        by_form["eager", 5, 0]["slots_gathered"]
    assert (tmp_path / "bench_carry.json").exists()
