"""`PlanCompiler._compact`: survivors packed into k slots, by one sort
of their positions (PR 30).  Held to a numpy reference
(`np.flatnonzero(valid)[:k]`) on the function alone, eager, jitted and
under `shard_map` at four virtual devices, and through a statement
whose `join_out` compacts (Q3 at SF0.002 against the sqlite oracle),
the capacity overflow and its retry included."""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import citus_tpu
from citus_tpu.executor.batch import Block
from citus_tpu.executor.compiler import PlanCompiler

N = 1000


def _block(valid: np.ndarray, with_nulls: bool = True) -> Block:
    """Columns of the dtypes a plan carries (a key, a measure, a flag, a
    narrowed key), no value of a row equal to row 0's, so a slot that
    read row 0 shows."""
    n = valid.shape[0]
    pos = np.arange(n)
    cols = {"i64": jnp.asarray(pos.astype(np.int64) * 7 + (1 << 40)),
            "f32": jnp.asarray(pos.astype(np.float32) + 0.5),
            "flag": jnp.asarray(pos % 3 == 1),
            "i32": jnp.asarray((n - pos).astype(np.int32))}
    nulls = {"f32": jnp.asarray(pos % 5 == 2)} if with_nulls else {}
    return Block(cols, jnp.asarray(valid), nulls)


def _compact(blk: Block, k: int):
    """→ (compacted block, overflow counted), on the function alone: of
    a PlanCompiler it touches the overflow accumulator and nothing
    else."""
    this = SimpleNamespace(_overflow=jnp.zeros((), jnp.int64))
    out = PlanCompiler._compact(this, blk, k)
    return out, this._overflow


def _check(blk: Block, out: Block, overflow, k: int) -> None:
    valid = np.asarray(blk.valid)
    want = np.flatnonzero(valid)[:k]      # survivors, in row order
    n_valid = int(valid.sum())
    assert int(overflow) == max(n_valid - k, 0)
    got_valid = np.asarray(out.valid)
    assert got_valid.shape == (k,)
    assert got_valid.tolist() == [True] * len(want) + \
        [False] * (k - len(want))
    for src_map, out_map in ((blk.columns, out.columns),
                             (blk.nulls, out.nulls)):
        assert set(out_map) == set(src_map)
        for cid, arr in src_map.items():
            src, got = np.asarray(arr), np.asarray(out_map[cid])
            assert got.dtype == src.dtype
            np.testing.assert_array_equal(got[:len(want)], src[want])
            # a padding slot reads row 0 — never the sentinel n, which
            # a gather would clamp to the last row
            np.testing.assert_array_equal(
                got[len(want):], np.full(k - len(want), src[0]))


def _sorts_and_does_not_scatter(compiled_text: str) -> bool:
    """The mechanism, read from a compiled program: a sort under
    `ct.compact` and no scatter on that path."""
    ops = [ln for ln in compiled_text.splitlines() if "ct.compact" in ln]
    return any(" sort(" in ln for ln in ops) and \
        not any("scatter" in ln for ln in ops)


def _valid(case: str, n: int, k: int) -> np.ndarray:
    rng = np.random.default_rng(11)
    valid = np.zeros(n, bool)
    count = {"fewer": k // 2, "exactly": k, "more": k + 37,
             "one_more": k + 1, "none": 0, "all": n, "one": 1}[case]
    valid[rng.permutation(n)[:count]] = True
    return valid


@pytest.mark.parametrize("case", ["fewer", "exactly", "more", "one_more",
                                  "none", "all", "one"])
@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
def test_compact_matches_numpy(case, jitted):
    k = 128
    blk = _block(_valid(case, N, k))
    fn = jax.jit(_compact, static_argnums=1) if jitted else _compact
    out, overflow = fn(blk, k)
    _check(blk, out, overflow, k)


@pytest.mark.parametrize("case", ["fewer", "all", "none"])
def test_compact_to_one_slot_fewer(case):
    """k = n − 1: the smallest shrink a caller can ask for."""
    n = 257
    blk = _block(_valid(case, n, n - 1))
    out, overflow = _compact(blk, n - 1)
    _check(blk, out, overflow, n - 1)


def test_compact_first_and_last_rows_survive():
    """The two ends of the position range: row 0 (which padding slots
    also read) and row n − 1 (one under the sentinel)."""
    valid = np.zeros(N, bool)
    valid[[0, N - 1]] = True
    blk = _block(valid, with_nulls=False)
    out, overflow = _compact(blk, 8)
    _check(blk, out, overflow, 8)
    assert np.asarray(out.columns["i32"])[:2].tolist() == [N, 1]


def test_compact_overflow_accumulates():
    """Overflow adds to what the program has counted so far."""
    blk = _block(_valid("more", N, 128))
    this = SimpleNamespace(_overflow=jnp.asarray(5, jnp.int64))
    PlanCompiler._compact(this, blk, 128)
    assert int(this._overflow) == 5 + 37
    assert this._overflow.dtype == jnp.int64


@pytest.mark.parametrize("n_devices", [1, 4])
def test_compact_per_device_under_shard_map(n_devices):
    """Inside `shard_map` each device sorts its own block: no
    collective, each shard's survivors in its own row order, each
    shard's overflow its own."""
    from citus_tpu.executor.compiler import shard_map

    k, per = 64, 500
    counts = [10, 64, 101, 0][:n_devices]
    rng = np.random.default_rng(3)
    valid = np.zeros((n_devices, per), bool)
    for d, c in enumerate(counts):
        valid[d, rng.permutation(per)[:c]] = True
    blk = _block(valid.reshape(-1))
    mesh = Mesh(np.array(jax.devices()[:n_devices]), ("s",))

    def local(b):
        out, overflow = _compact(b, k)
        return out, overflow[None]

    out, overflow = jax.jit(shard_map(
        local, mesh=mesh, in_specs=P("s"), out_specs=P("s")))(blk)
    assert np.asarray(overflow).tolist() == [max(c - k, 0) for c in counts]
    for d in range(n_devices):
        rows = slice(d * per, (d + 1) * per)
        shard = Block({c: a[rows] for c, a in blk.columns.items()},
                      blk.valid[rows],
                      {c: a[rows] for c, a in blk.nulls.items()})
        got = Block({c: a[d * k:(d + 1) * k] for c, a in out.columns.items()},
                    out.valid[d * k:(d + 1) * k],
                    {c: a[d * k:(d + 1) * k] for c, a in out.nulls.items()})
        _check(shard, got, max(counts[d] - k, 0), k)


def test_compact_program_sorts_and_does_not_scatter():
    """The mechanism, read from the lowered program: one sort under
    `ct.compact`, no scatter and no scan (the `cumsum` went with the
    scatter it fed)."""
    blk = _block(_valid("fewer", N, 128))
    lowered = jax.jit(_compact, static_argnums=1).lower(blk, 128)
    traced = lowered.as_text()
    assert "stablehlo.sort" in traced
    for gone in ("scatter", "reduce_window", "cumsum"):
        assert gone not in traced
    assert _sorts_and_does_not_scatter(lowered.compile().as_text())


# -- through a statement ----------------------------------------------------

def _q3_session(tmp_path, n_devices):
    from citus_tpu.ingest import tpch
    from oracle import make_oracle

    sess = citus_tpu.connect(data_dir=str(tmp_path / "q3"),
                             n_devices=n_devices, compute_dtype="float64",
                             serving_result_cache_bytes=0)
    tpch.load_into_session(sess, sf=0.002, seed=7)
    conn = make_oracle(tpch.generate_tables(0.002, seed=7),
                       {"orders": ["o_orderdate"],
                        "lineitem": ["l_shipdate", "l_commitdate",
                                     "l_receiptdate"]})
    return sess, conn, tpch.QUERIES["Q3"]


def _compacting_programs(sess) -> list[str]:
    return [text for text in (
        entry[0].as_text() for entry in
        sess.executor.plan_cache._entries.values())
        if "ct.join_out/ct.compact" in text]


@pytest.mark.parametrize("n_devices", [1, 4])
def test_q3_join_out_compacts_by_sort(tmp_path, n_devices):
    """Q3's `join_out` compaction at one device and on a four-device
    mesh: the answer is the oracle's, with no retry, and the compiled
    program carries the sub-scope with a sort and no scatter in it."""
    from oracle import compare_results, run_oracle

    sess, conn, sql = _q3_session(tmp_path, n_devices)
    try:
        result = sess.execute(sql)
        assert result.retries == 0
        compare_results(result.rows(), run_oracle(conn, sql), True, 1e-6)
        programs = _compacting_programs(sess)
        assert programs
        assert all(_sorts_and_does_not_scatter(text) for text in programs)
    finally:
        sess.close()


@pytest.mark.parametrize("n_devices", [1, 4])
def test_q3_join_out_overflow_then_retry(tmp_path, n_devices):
    """More survivors than the planned slots: the compaction counts the
    overflow, the host retries with slots that fit,
    and the answer is the oracle's.  The fused join's compacted
    capacity follows the planner's row estimate (not
    `join_output_capacity_factor`, which sizes pair emission and is
    set small here all the same), so the estimate is cut to one row —
    640 slots — and Q3's ship-date filter widened to every line, so
    that the first join keeps some 5,800 rows of 12,000."""
    from citus_tpu.executor.feed import walk_plan
    from citus_tpu.planner.plan import JoinNode
    from citus_tpu.sql.parser import parse_one
    from oracle import compare_results, run_oracle

    sess, conn, sql = _q3_session(tmp_path, n_devices)
    sql = sql.replace("l_shipdate > date '1995-03-15'",
                      "l_shipdate > date '1992-01-01'")
    try:
        with sess.settings.override(join_output_capacity_factor=0.1):
            plan, _cleanup = sess._plan_select(parse_one(sql))
            fused = [node for node in walk_plan(plan.root)
                     if isinstance(node, JoinNode)
                     and getattr(node, "fuse_lookup", False)]
            assert fused
            for node in fused:
                node.est_rows = 1
            result = sess.executor.execute_plan(plan)
        assert result.retries >= 1
        compare_results(result.rows(), run_oracle(conn, sql), True, 1e-6)
        assert _compacting_programs(sess)
    finally:
        sess.close()


# -- the benchmark's reader over the sub-scope --------------------------------

@pytest.mark.parametrize("reduction,want", [
    ({"stage_sub_ms": {"join_out/compact": 3.0, "scan_out/compact": 0.5,
                       "join_out": 10.0, "lookup_join/dense": 7.0}}, 3.5),
    # a program of before PR 30: the line leaves the metric out
    ({"stage_sub_ms": {"join_out": 49.7, "lookup_join/sort": 24.2}}, None),
    (None, None),                                  # no device trace
], ids=["two_stages", "no_sub_scope", "no_trace"])
def test_stage_compact_ms_reader(reduction, want):
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.layer_metrics import stage_compact_ms

    assert stage_compact_ms.read(SimpleNamespace(_xspans=reduction)) == want
