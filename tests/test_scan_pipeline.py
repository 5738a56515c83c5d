"""Pipelined columnar scan (executor/scanpipe.py): wire-codec units,
decode goldens against numpy references, eager-vs-pipelined parity (directed + fuzz slice
with interleaved DML — the serving cache-on ≡ cache-off fuzzer mode is
the template), fault-point drains with a zero-leak prefetch ledger, and
the OOM shed-to-eager path."""

import random

import numpy as np
import pytest

import citus_tpu
from citus_tpu.errors import CitusTpuError
from citus_tpu.executor.hbm import accountant_for, oom_budget
from citus_tpu.executor.scanpipe import encode_column
from citus_tpu.stats import counters as sc
from citus_tpu.utils import faultinjection as fi
from citus_tpu.utils.faultinjection import inject


@pytest.fixture(autouse=True)
def _clean_faults():
    fi.reset()
    yield
    fi.reset()


def _prefetch_bytes(data_dir) -> int:
    """Live prefetch-category bytes, gc'ing first when nonzero: an
    exception traceback (a just-absorbed injected fault) can pin the
    failed attempt's queue payloads until collection — Python exception
    semantics, not an accountant leak (the PR-10 torture harness
    documents the same caveat)."""
    import gc

    acc = accountant_for(data_dir)
    if acc.live_bytes("prefetch"):
        gc.collect()
    return acc.live_bytes("prefetch")


def _mk(data_dir, mode, **kw):
    # result cache off: every read must actually reach the scan path —
    # a repeated statement served from the serving cache would make the
    # parity and fault assertions vacuous
    return citus_tpu.connect(data_dir=data_dir, n_devices=2,
                             scan_pipeline=mode,
                             serving_result_cache_bytes=0, **kw)


def _seed_kv(sess, n=2000):
    sess.execute("CREATE TABLE kv (id INT, v INT, name TEXT)")
    sess.execute("SELECT create_distributed_table('kv', 'id', 4)")
    vals = ", ".join(
        f"({i}, {i * 10}, " + ("NULL" if i % 3 == 0 else f"'n{i % 7}'")
        + ")" for i in range(n))
    sess.execute("INSERT INTO kv VALUES " + vals)


# ---------------------------------------------------------------------------
# wire codec units

class TestWireCodec:
    def test_for_packs_narrow_ints(self):
        buf = np.arange(1000, 1500, dtype=np.int64).reshape(2, 250)
        kind, wire, base = encode_column(buf)
        assert kind == "for" and wire.dtype == np.uint16
        assert wire.nbytes < buf.nbytes
        np.testing.assert_array_equal(
            wire.astype(np.int64) + int(base), buf)

    def test_for_skips_wide_span(self):
        buf = np.array([0, 1 << 40], dtype=np.int64)
        kind, wire, _ = encode_column(buf)
        assert kind == "plain" and wire is buf

    def test_dict_packs_low_ndv_floats(self):
        rng = np.random.default_rng(0)
        lutv = np.array([0.02, 0.05, 1.5, 900.0], dtype=np.float32)
        buf = lutv[rng.integers(0, 4, size=(2, 4096))]
        kind, codes, lut = encode_column(buf)
        assert kind == "dict" and codes.dtype == np.uint8
        np.testing.assert_array_equal(lut[codes.astype(np.int64)], buf)

    def test_dict_skips_nan_and_distinct(self):
        buf = np.array([1.0, np.nan], dtype=np.float32)
        assert encode_column(buf)[0] == "plain"
        distinct = np.arange(70000, dtype=np.float32) * 1.5
        assert encode_column(distinct)[0] == "plain"


class TestDecodeKernels:
    """The on-mesh decode against the numpy oracles."""

    def test_xla_decode_of_mesh_sharded_wire_buffers(self):
        """The formulations every device-mode scan runs, on buffers
        sharded over the mesh the way `_place` leaves them.  The
        dictionary gather (replicated LUT, sharded codes) is the one an
        explicit-axis mesh will not resolve by itself: the first chip
        run died there."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from citus_tpu.distributed.mesh import SHARD_AXIS, make_mesh
        from citus_tpu.executor import scanpipe
        from citus_tpu.ops.reference import (
            bit_unpack_reference,
            dict_decode_reference,
        )

        mesh = make_mesh(4)
        rows = NamedSharding(mesh, P(SHARD_AXIS))
        whole = NamedSharding(mesh, P())
        rng = np.random.default_rng(3)
        lut = np.linspace(0, 1, 11, dtype=np.float32)
        codes = rng.integers(0, 11, size=(4, 640)).astype(np.uint8)
        got = scanpipe._dict_expand(jax.device_put(codes, rows),
                                    jax.device_put(lut, whole))
        np.testing.assert_array_equal(
            np.asarray(got), dict_decode_reference(codes, lut))
        assert got.sharding.is_equivalent_to(rows, 2)

        bits = rng.integers(0, 2, size=(4, 640)).astype(bool)
        packed = np.packbits(bits, axis=-1)
        got = scanpipe._bits_expand(jax.device_put(packed, rows), 640)
        np.testing.assert_array_equal(
            np.asarray(got), bit_unpack_reference(packed, 640))

        wire = rng.integers(0, 200, size=(4, 640)).astype(np.uint8)
        got = scanpipe._for_expand(jax.device_put(wire, rows),
                                   np.asarray(-7, dtype=np.int64))
        np.testing.assert_array_equal(
            np.asarray(got), wire.astype(np.int64) - 7)

        n = np.array([[5], [0], [640], [17]], dtype=np.int32)
        got = np.asarray(
            scanpipe._valid_expand(jax.device_put(n, rows), 640))
        assert got.sum(axis=1).tolist() == [5, 0, 640, 17]


# ---------------------------------------------------------------------------
# parity

class TestPipelineParity:
    @pytest.mark.parametrize("mode", ["host", "device"])
    def test_directed_parity(self, tmp_path, mode):
        """NULLs, deletes, renames, post-ALTER columns, chunk-skippable
        filters and group-bys answer identically to the eager path."""
        d = str(tmp_path / "par")
        off = _mk(d, "off")
        _seed_kv(off)
        off.execute("DELETE FROM kv WHERE id < 300")
        off.execute("UPDATE kv SET v = v + 1 WHERE id >= 1500")
        off.execute("ALTER TABLE kv RENAME COLUMN v TO val")
        off.execute("ALTER TABLE kv ADD COLUMN extra INT")
        off.execute("INSERT INTO kv VALUES (9001, 7, 'zz', 42)")
        pipe = _mk(d, mode)
        for q in [
            "SELECT count(*), sum(val) FROM kv",
            "SELECT name, count(*), min(val) FROM kv GROUP BY name",
            "SELECT count(*) FROM kv WHERE val >= 15000",
            "SELECT count(*) FROM kv WHERE extra IS NULL",
            "SELECT sum(extra) FROM kv",
            "SELECT count(*) FROM kv WHERE id = 9001",
        ]:
            want = sorted(off.execute(q).rows(), key=repr)
            got = sorted(pipe.execute(q).rows(), key=repr)
            assert got == want, (q, got, want)
        assert _prefetch_bytes(d) == 0
        off.close()
        pipe.close()

    def test_device_mode_shrinks_wire_bytes(self, tmp_path):
        """Packed-int/dictionary columns cross the wire compressed:
        bytes_on_wire < bytes_decoded, and the decode counter moves."""
        d = str(tmp_path / "wire")
        sess = _mk(d, "device")
        _seed_kv(sess, n=3000)
        sess.executor.scan_stats.reset()
        sess.execute("SELECT sum(v), count(name) FROM kv")
        snap = sess.executor.scan_stats.snapshot()
        assert snap["feeds_pipelined"] >= 1
        assert 0 < snap["bytes_on_wire"] < snap["bytes_decoded"]
        counters = sess.stats.counters.snapshot()
        assert counters[sc.DEVICE_DECODED_BYTES_TOTAL] > 0
        assert counters[sc.CHUNKS_PREFETCHED_TOTAL] > 0
        sess.close()

    def test_feed_cache_hits_pipelined_feeds(self, tmp_path):
        d = str(tmp_path / "cache")
        sess = _mk(d, "device")
        _seed_kv(sess)
        sess.execute("SELECT sum(v) FROM kv")
        h0 = sess.executor.feed_cache.hits
        sess.execute("SELECT sum(v) FROM kv WHERE v >= 0")
        sess.execute("SELECT sum(v) FROM kv WHERE v >= 0")
        assert sess.executor.feed_cache.hits > h0
        sess.close()

    def test_explain_renders_pipeline_tag(self, tmp_path):
        d = str(tmp_path / "exp")
        sess = _mk(d, "host")
        _seed_kv(sess, n=50)
        plan = "\n".join(r[0] for r in sess.execute(
            "EXPLAIN SELECT count(*) FROM kv").rows())
        assert "pipelined scan: host" in plan
        off = _mk(d, "off")
        plan = "\n".join(r[0] for r in off.execute(
            "EXPLAIN SELECT count(*) FROM kv").rows())
        assert "pipelined scan" not in plan
        sess.close()
        off.close()


# ---------------------------------------------------------------------------
# fuzz slice: pipelined ≡ eager under interleaved DML from a second
# session (the serving cache-on ≡ cache-off fuzzer mode is the template)

def _run_scan_fuzz(tmp_path, n_ops: int, seed: int):
    from fuzzer import generate_serving

    data_dir = str(tmp_path / "scanfuzz")
    writer = _mk(data_dir, "off")
    writer.execute("CREATE TABLE kv (id INT, v INT)")
    writer.execute("SELECT create_distributed_table('kv', 'id', 4)")
    writer.execute("INSERT INTO kv VALUES " + ", ".join(
        f"({i}, {i * 3})" for i in range(60)))
    readers = {"off": writer, "host": _mk(data_dir, "host"),
               "device": _mk(data_dir, "device")}
    rng = random.Random(seed)
    state = {"next_id": 60}
    stats = {"reads": 0, "writes": 0}
    try:
        for op in range(n_ops):
            kind, sql, rows = generate_serving(rng, state)
            if kind == "copy":
                csv = str(tmp_path / f"scan_{op}.csv")
                with open(csv, "w") as f:
                    for i, v in rows:
                        f.write(f"{i},{v}\n")
                sql = f"COPY kv FROM '{csv}' WITH (FORMAT csv)"
                kind = "write"
            if kind == "txn_write":
                writer.execute("BEGIN")
                writer.execute(sql)
                writer.execute("COMMIT")
                stats["writes"] += 1
                continue
            if kind == "write":
                writer.execute(sql)
                stats["writes"] += 1
                continue
            stats["reads"] += 1
            want = sorted(readers["off"].execute(sql).rows())
            for mode in ("host", "device"):
                got = sorted(readers[mode].execute(sql).rows())
                assert got == want, (
                    f"scan_pipeline={mode} diverged from eager on "
                    f"{sql!r} (step {op}): {got} != {want}")
        assert _prefetch_bytes(data_dir) == 0
        return stats
    finally:
        for s in set(readers.values()):
            s.close()


def test_scan_fuzz_smoke_slice(tmp_path):
    """Deterministic tier-1 slice: scan_pipeline=host and =device read
    identically to =off under interleaved DML/COPY/txn writes."""
    stats = _run_scan_fuzz(tmp_path, n_ops=45, seed=627)
    assert stats["reads"] >= 20 and stats["writes"] >= 5


@pytest.mark.slow
def test_scan_fuzz_full(tmp_path):
    stats = _run_scan_fuzz(tmp_path, n_ops=300, seed=20260804)
    assert stats["reads"] >= 150 and stats["writes"] >= 40


# ---------------------------------------------------------------------------
# fault points + OOM governance

class TestPipelineFaults:
    def test_prefetch_fault_retried_and_drained(self, tmp_path):
        d = str(tmp_path / "pf")
        sess = _mk(d, "host", retry_backoff_base_ms=1,
                   retry_backoff_max_ms=5)
        _seed_kv(sess, n=500)
        want = sess.execute("SELECT count(*), sum(v) FROM kv").rows()
        sess.executor.feed_cache.clear()
        with inject("executor.scan_prefetch", require_fired=True):
            got = sess.execute("SELECT count(*), sum(v) FROM kv").rows()
        assert got == want
        assert _prefetch_bytes(d) == 0
        sess.close()

    def test_sticky_prefetch_fault_errors_cleanly_no_leak(self,
                                                          tmp_path):
        """A mid-prefetch death the retries cannot outlast drains the
        pipeline into a clean error — answered XOR errored, and the
        zero-leak ledger holds for the prefetch category."""
        d = str(tmp_path / "pfs")
        sess = _mk(d, "device", retry_backoff_base_ms=1,
                   retry_backoff_max_ms=5, max_statement_retries=1)
        _seed_kv(sess, n=500)
        sess.execute("SELECT sum(v) FROM kv")
        sess.executor.feed_cache.clear()
        with inject("executor.scan_prefetch", times=10):
            with pytest.raises(CitusTpuError):
                sess.execute("SELECT sum(v) FROM kv")
        assert _prefetch_bytes(d) == 0
        assert accountant_for(d).transient_bytes() == 0
        sess.close()

    def test_device_decode_fault_retried(self, tmp_path):
        d = str(tmp_path / "dd")
        sess = _mk(d, "device", retry_backoff_base_ms=1,
                   retry_backoff_max_ms=5)
        _seed_kv(sess, n=500)
        want = sess.execute("SELECT count(*), sum(v) FROM kv").rows()
        sess.executor.feed_cache.clear()
        with inject("executor.device_decode", require_fired=True):
            got = sess.execute("SELECT count(*), sum(v) FROM kv").rows()
        assert got == want
        assert _prefetch_bytes(d) == 0
        sess.close()

    def test_pipelined_read_fails_over_to_replica(self, tmp_path):
        """A storage-kind read failure on a pipelined scan must carry
        (table, shard_id) so the retry loop marks the placement suspect
        and answers from the surviving replica — the eager read_shard
        failover contract, which the pipeline's direct verified_read
        calls would otherwise silently drop."""
        d = str(tmp_path / "fo")
        sess = _mk(d, "host", shard_replication_factor=2,
                   retry_backoff_base_ms=1, retry_backoff_max_ms=5)
        _seed_kv(sess, n=600)
        want = sess.execute("SELECT count(*), sum(v) FROM kv").rows()
        sess.executor.feed_cache.clear()
        from citus_tpu.stats import counters as scnt

        f0 = sess.stats.counters.snapshot()[scnt.FAILOVERS_TOTAL]
        with inject("store.read_shard", error="storage",
                    require_fired=True):
            got = sess.execute("SELECT count(*), sum(v) FROM kv").rows()
        assert got == want
        assert sess.stats.counters.snapshot()[
            scnt.FAILOVERS_TOTAL] > f0
        sess.close()

    def test_prefetch_oom_sheds_to_eager(self, tmp_path):
        """An allocator OOM while prefetching sheds the pipeline (all
        prefetch charges release) and the feed retries eagerly inside
        the same statement — the ladder never even engages."""
        d = str(tmp_path / "shed")
        sess = _mk(d, "host", retry_backoff_base_ms=1,
                   retry_backoff_max_ms=5)
        _seed_kv(sess, n=500)
        want = sess.execute("SELECT count(*), sum(v) FROM kv").rows()
        sess.executor.feed_cache.clear()
        acc = accountant_for(d)
        sess.executor.scan_stats.reset()
        with oom_budget(acc, fail_at=1):
            got = sess.execute("SELECT count(*), sum(v) FROM kv").rows()
        assert got == want
        assert sess.executor.scan_stats.snapshot()[
            "feeds_pipelined"] == 0
        assert _prefetch_bytes(d) == 0
        sess.close()
