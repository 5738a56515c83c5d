"""Restart survival: persistent compiled-executable cache, single-flight
compile dedup, and warm-before-admit serving (executor/execcache.py).

The contract under test, end to end:

* a fresh process *loads* serialized executables instead of recompiling
  (cold-load answers are oracle-identical to compiled answers);
* corrupt, torn, truncated, or version/backend-skewed entries are
  DETECTED (CRC + environment stamp) and fall back to a clean
  recompile — never a crash, never a stale executable;
* CrashSim power cuts at every durable write of the cache leave a
  state the next session recovers from with a correct answer;
* N sessions hitting a cold shape produce ONE compile (leader/follower
  single-flight; leader death self-promotes a follower — answered XOR
  errored XOR promoted, no stranded waiters);
* warm-before-admit pre-adopts the hottest persisted shapes under a
  bounded budget and degrades gracefully to lazy loading.
"""

import glob
import json
import os
import threading
import time

import numpy as np
import pytest

import citus_tpu
from citus_tpu.executor.execcache import (
    CompileGate,
    EXEC_CACHE_DIR,
    exec_cache_for,
)
from citus_tpu.stats import counters as sc
from citus_tpu.utils import faultinjection as fi
from citus_tpu.utils import io as dio
from citus_tpu.utils.crashsim import PowerCut, power_cut_at

SQL = ("SELECT b, count(*), sum(a) FROM t GROUP BY b ORDER BY b")
# 200 rows, a = 0..199, b = a % 7: the host-side oracle for SQL
EXPECTED = [(b,
             len([a for a in range(200) if a % 7 == b]),
             sum(a for a in range(200) if a % 7 == b))
            for b in range(7)]


@pytest.fixture(autouse=True)
def _clean_faults():
    fi.reset()
    yield
    fi.reset()


def _connect(data_dir, **kw):
    # result cache OFF: repeated identical SQL must reach the executor
    # (the serving cache would answer without executing — the classic
    # directed-fault mask), capacity feedback OFF so one statement is
    # exactly one plan-cache key (no tighten-recompile second key)
    return citus_tpu.connect(
        data_dir=data_dir, n_devices=4, serving_result_cache_bytes=0,
        enable_capacity_feedback=False, **kw)


def _seed(data_dir, **kw):
    s = _connect(data_dir, **kw)
    s.execute("CREATE TABLE t (a INT, b INT)")
    s.execute("SELECT create_distributed_table('t', 'a', 4)")
    s.execute("INSERT INTO t VALUES " + ", ".join(
        f"({i}, {i % 7})" for i in range(200)))
    return s


def _rows(r):
    return [tuple(int(x) for x in row) for row in r.rows()]


def _cache_files(data_dir, suffix):
    return sorted(glob.glob(os.path.join(
        data_dir, EXEC_CACHE_DIR, f"*{suffix}")))


class TestColdLoad:
    def test_cold_load_answers_match_oracle_and_skip_compile(
            self, tmp_path):
        data_dir = str(tmp_path / "d")
        s1 = _seed(data_dir)
        assert _rows(s1.execute(SQL)) == EXPECTED  # compiled answer
        s1.close()
        assert _cache_files(data_dir, ".meta.json"), \
            "compile did not persist an executable"
        ec = exec_cache_for(data_dir)
        base_compiles = ec.compiles_total
        s2 = _connect(data_dir)
        assert _rows(s2.execute(SQL)) == EXPECTED  # loaded answer
        snap = s2.stats.counters.snapshot()
        assert snap[sc.EXEC_CACHE_HITS_TOTAL] >= 1
        assert ec.compiles_total == base_compiles, \
            "restart recompiled a shape the disk cache held"
        s2.close()

    def test_reload_targets_the_mesh_not_the_whole_backend(
            self, tmp_path):
        """The execution_devices repair, by name: an executable stored
        from a 2-device mesh on the 8-device backend, reloaded by a
        fresh ExecutableCache, must load onto those two devices.
        Loaded onto every device of the backend it dispatches with
        `Expected args to execute_sharded_on_local_devices to have 8
        shards, got: [2, 2]`."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from citus_tpu.distributed.mesh import SHARD_AXIS, make_mesh
        from citus_tpu.executor.execcache import ExecutableCache

        assert len(jax.devices()) == 8
        mesh = make_mesh(2)
        sharding = NamedSharding(mesh, P(SHARD_AXIS))
        x = jax.device_put(np.arange(8, dtype=np.int64), sharding)
        compiled = jax.jit(lambda v: v * 2 + 1).lower(x).compile()
        key = ("two-of-eight", 2)
        assert ExecutableCache(str(tmp_path)).store(
            key, mesh, compiled, [], [], 0)

        fresh = ExecutableCache(str(tmp_path))
        entry, status = fresh.load(key, mesh)
        assert status == "hit"
        out = entry[0](x)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.arange(8) * 2 + 1)
        assert {d.id for d in out.sharding.device_set} == \
            {d.id for d in mesh.devices.flat}

    def test_exec_cache_disabled_compiles(self, tmp_path):
        data_dir = str(tmp_path / "d")
        s1 = _seed(data_dir)
        s1.execute(SQL)
        s1.close()
        s2 = _connect(data_dir, exec_cache_enabled=False)
        assert _rows(s2.execute(SQL)) == EXPECTED
        snap = s2.stats.counters.snapshot()
        assert snap[sc.EXEC_CACHE_HITS_TOTAL] == 0
        assert snap[sc.EXEC_CACHE_MISSES_TOTAL] == 0
        s2.close()


class TestRotDetection:
    """Every persisted-entry failure mode downgrades to a counted
    reject + clean recompile — never a crash, never a stale answer."""

    def _seeded_dir(self, tmp_path):
        data_dir = str(tmp_path / "d")
        s = _seed(data_dir)
        s.execute(SQL)
        s.close()
        return data_dir

    def _assert_recompiles(self, data_dir):
        s = _connect(data_dir)
        assert _rows(s.execute(SQL)) == EXPECTED
        snap = s.stats.counters.snapshot()
        assert snap[sc.EXEC_CACHE_REJECTS_TOTAL] >= 1
        assert snap[sc.EXEC_CACHE_HITS_TOTAL] == 0
        s.close()

    def test_bitflipped_payload_recompiles(self, tmp_path):
        data_dir = self._seeded_dir(tmp_path)
        path = _cache_files(data_dir, ".bin")[0]
        with open(path, "rb") as f:
            data = bytearray(f.read())
        data[len(data) // 2] ^= 0x40  # silent rot mid-payload
        with open(path, "wb") as f:
            f.write(bytes(data))
        self._assert_recompiles(data_dir)

    def test_truncated_payload_recompiles(self, tmp_path):
        data_dir = self._seeded_dir(tmp_path)
        path = _cache_files(data_dir, ".bin")[0]
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            f.truncate(size // 2)  # torn write survivor
        self._assert_recompiles(data_dir)

    def test_corrupt_meta_recompiles(self, tmp_path):
        data_dir = self._seeded_dir(tmp_path)
        path = _cache_files(data_dir, ".meta.json")[0]
        with open(path, "rb") as f:
            data = bytearray(f.read())
        data[len(data) // 2] ^= 0x01  # CRC-checked JSON catches this
        with open(path, "wb") as f:
            f.write(bytes(data))
        self._assert_recompiles(data_dir)

    def test_version_skew_recompiles(self, tmp_path):
        data_dir = self._seeded_dir(tmp_path)
        path = _cache_files(data_dir, ".meta.json")[0]
        meta = dio.read_json_checked(path)
        meta["version"] = 0  # an old cache format must never be served
        dio.atomic_write_json_checked(path, meta)
        self._assert_recompiles(data_dir)

    def test_environment_skew_recompiles(self, tmp_path):
        # jax-version / backend / mesh-shape stamp mismatch: the entry
        # is intact but was compiled by a different environment — a
        # deploy must never serve a stale executable across an upgrade
        data_dir = self._seeded_dir(tmp_path)
        path = _cache_files(data_dir, ".meta.json")[0]
        meta = dio.read_json_checked(path)
        meta["stamp"] = dict(meta["stamp"], jax="0.0.0-skewed")
        dio.atomic_write_json_checked(path, meta)
        self._assert_recompiles(data_dir)

    def test_load_fault_recompiles(self, tmp_path):
        # injected rot at the named seam (the chaos soak arms this):
        # the load downgrades to a reject and the compile path answers
        data_dir = self._seeded_dir(tmp_path)
        s = _connect(data_dir)
        with fi.inject("executor.exec_cache_load", require_fired=True):
            assert _rows(s.execute(SQL)) == EXPECTED
        assert s.stats.counters.snapshot()[
            sc.EXEC_CACHE_REJECTS_TOTAL] >= 1
        s.close()

    def test_store_fault_errors_cleanly_then_retry_answers(
            self, tmp_path):
        # a fault while persisting fires BEFORE the best-effort catch:
        # the statement errors cleanly, the session retry envelope
        # recompiles, and the answer is still correct
        data_dir = str(tmp_path / "d")
        s = _seed(data_dir)
        with fi.inject("executor.exec_cache_store", require_fired=True):
            assert _rows(s.execute(SQL)) == EXPECTED
        assert s.stats.counters.snapshot()[sc.RETRIES_TOTAL] >= 1
        s.close()


class TestCrashSim:
    def test_power_cut_sweep_over_cache_writes(self, tmp_path):
        """Cut power at EVERY durable write op of a compiling statement
        (exec-cache payload, exec-cache meta, caps memo, index) in
        every tear mode: the next session must answer correctly —
        adopting the entry when it committed, recompiling otherwise."""
        data_dir = str(tmp_path / "d")
        s = _seed(data_dir)
        s.close()

        def wipe():
            for p in _cache_files(data_dir, ""):
                os.unlink(p)

        # rehearsal: count the statement's durable ops with a cold
        # cache (n=None never cuts)
        wipe()
        s = _connect(data_dir)
        with power_cut_at(None) as sim:
            assert _rows(s.execute(SQL)) == EXPECTED
        s.close()
        n_ops = sim.ops
        assert n_ops >= 2, \
            f"expected >= 2 durable cache writes, saw {sim.journal}"
        for crash_at in range(1, n_ops + 1):
            for mode in ("lost", "torn", "complete"):
                wipe()
                dying = _connect(data_dir)
                try:
                    with power_cut_at(crash_at, mode):
                        try:
                            r = dying.execute(SQL)
                            assert _rows(r) == EXPECTED
                        except PowerCut:
                            pass  # the process died mid-write
                finally:
                    # the "dead process" is abandoned without close()
                    # (its handlers may not write); only its service
                    # threads stop so the sweep doesn't leak them
                    dying.maintenance.stop()
                    dying.jobs.shutdown()
                fresh = _connect(data_dir)
                assert _rows(fresh.execute(SQL)) == EXPECTED, \
                    f"wrong answer after cut at op {crash_at} ({mode})"
                fresh.close()


class TestSingleFlight:
    def test_8_session_cold_fan_in_one_compile_per_shape(self, tmp_path):
        data_dir = str(tmp_path / "d")
        seeder = _seed(data_dir)
        seeder.close()
        ec = exec_cache_for(data_dir)
        base = ec.snapshot()
        base_hits = ec.hits_total
        sessions = [_connect(data_dir) for _ in range(8)]
        barrier = threading.Barrier(8)
        results, errors = [None] * 8, [None] * 8

        def worker(i):
            try:
                barrier.wait(timeout=30)
                results[i] = _rows(sessions[i].execute(SQL))
            except BaseException as e:  # noqa: BLE001 — surfaced below
                errors[i] = e

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert errors == [None] * 8, errors
        assert all(r == EXPECTED for r in results)
        snap = ec.snapshot()
        compiles = snap["compiles_total"] - base["compiles_total"]
        saved = (snap["gate_deduped_total"]
                 - base["gate_deduped_total"]) + \
            (ec.hits_total - base_hits)
        # THE acceptance assert: 8 cold sessions, ONE distinct shape,
        # exactly one compile — everyone else followed the in-flight
        # resolve or adopted the freshly persisted executable
        assert compiles == 1, snap
        assert saved == 7, snap
        for s in sessions:
            s.close()

    def test_leader_death_self_promotes_follower(self):
        gate = CompileGate()
        order = []

        class Death(BaseException):
            pass

        def dying_leader():
            order.append("lead")
            time.sleep(0.1)  # let the follower start waiting
            raise Death()

        def clean_compile():
            order.append("compile")
            return ("entry",)

        follower_out = []

        def leader():
            with pytest.raises(Death):
                gate.run("k", dying_leader)

        def follower():
            time.sleep(0.02)  # enqueue behind the dying leader
            follower_out.append(gate.run("k", clean_compile))

        t1 = threading.Thread(target=leader)
        t2 = threading.Thread(target=follower)
        t1.start(); t2.start()
        t1.join(timeout=30); t2.join(timeout=30)
        # ledger: the follower promoted (never stranded, never errored
        # by a death it didn't cause) and compiled itself
        assert follower_out == [(("entry",), False)]
        snap = gate.snapshot()
        assert snap["promoted_total"] == 1
        assert snap["flights_led_total"] == 1
        assert snap["in_flight"] == 0

    def test_leader_compile_error_clones_to_followers(self):
        gate = CompileGate()

        class CompileBoom(Exception):
            pass

        boom = CompileBoom("trace failed")
        boom.injected_fault = True

        def failing_leader():
            time.sleep(0.1)
            raise boom

        caught = []

        def follower():
            time.sleep(0.02)
            try:
                gate.run("k", lambda: None)
            except CompileBoom as e:
                caught.append(e)

        t1 = threading.Thread(
            target=lambda: pytest.raises(CompileBoom,
                                         gate.run, "k", failing_leader))
        t2 = threading.Thread(target=follower)
        t1.start(); t2.start()
        t1.join(timeout=30); t2.join(timeout=30)
        assert len(caught) == 1
        assert caught[0] is not boom  # per-waiter clone, markers intact
        assert getattr(caught[0], "injected_fault", False)
        assert gate.snapshot()["errored_followers_total"] == 1
        assert gate.snapshot()["in_flight"] == 0


class TestWarmup:
    def test_warmup_preloads_plan_cache_before_admission(self, tmp_path):
        data_dir = str(tmp_path / "d")
        s1 = _seed(data_dir)
        s1.execute(SQL)
        s1.close()
        ec = exec_cache_for(data_dir)
        base_compiles = ec.compiles_total
        s2 = _connect(data_dir, warmup_budget_ms=30_000,
                      warmup_top_shapes=8)
        assert s2._warmup_thread is not None
        s2._warmup_thread.join(timeout=60)
        snap = s2.stats.counters.snapshot()
        assert snap[sc.WARMUP_COMPILES_TOTAL] >= 1
        assert len(s2.executor.plan_cache) >= 1
        assert not s2.wlm.warming()  # the hold released
        hits0 = s2.executor.plan_cache.hits
        assert _rows(s2.execute(SQL)) == EXPECTED
        # the warmed statement ran on the pre-adopted executable:
        # plan-cache hit, zero compiles anywhere
        assert s2.executor.plan_cache.hits > hits0
        assert ec.compiles_total == base_compiles
        s2.close()

    def test_warmup_budget_exceeded_degrades_to_lazy(self, tmp_path):
        data_dir = str(tmp_path / "d")
        s1 = _seed(data_dir)
        s1.execute(SQL)
        s1.close()
        # a 1 ms budget expires before the first adoption: admission
        # must open anyway (the hold auto-expires) and the statement
        # loads lazily — correctness never depends on warmup finishing
        s2 = _connect(data_dir, warmup_budget_ms=1, warmup_top_shapes=8)
        if s2._warmup_thread is not None:
            s2._warmup_thread.join(timeout=60)
        t0 = time.monotonic()
        assert _rows(s2.execute(SQL)) == EXPECTED
        assert time.monotonic() - t0 < 60
        assert not s2.wlm.warming()
        s2.close()

    def test_warmup_fault_degrades_to_lazy(self, tmp_path):
        data_dir = str(tmp_path / "d")
        s1 = _seed(data_dir)
        s1.execute(SQL)
        s1.close()
        with fi.inject("wlm.warmup", require_fired=True):
            s2 = _connect(data_dir, warmup_budget_ms=30_000,
                          warmup_top_shapes=8)
            assert s2._warmup_thread is not None
            s2._warmup_thread.join(timeout=60)
        # the fault stopped warmup; the hold released and lazy
        # loading still answers correctly
        assert not s2.wlm.warming()
        assert _rows(s2.execute(SQL)) == EXPECTED
        s2.close()

    def test_close_mid_warmup_releases_admission_hold(self, tmp_path):
        # the hold lives on the SHARED per-data_dir manager: a session
        # closed 1 s into a 60 s budget must not leave other sessions
        # blocked until the deadline — close signals the stop event
        data_dir = str(tmp_path / "d")
        s1 = _seed(data_dir)
        s1.execute(SQL)
        s1.close()
        s2 = _connect(data_dir, warmup_budget_ms=60_000,
                      warmup_top_shapes=8)
        s2.close()  # may land mid-warmup; must stop + release
        other = _connect(data_dir)
        t0 = time.monotonic()
        assert _rows(other.execute(SQL)) == EXPECTED
        assert time.monotonic() - t0 < 30, \
            "an orphaned warmup hold blocked admission"
        assert not other.wlm.warming()
        other.close()

    def test_warmup_skips_when_cache_empty(self, tmp_path):
        s = _connect(str(tmp_path / "d"), warmup_budget_ms=30_000)
        assert s._warmup_thread is None  # nothing to warm, no hold
        s.close()


class TestCapsMemoRegressions:
    """PR-15 satellite: the 512-entry overflow used to clear() the
    whole memo (every converged shape forgotten at once) and every
    memoization rewrote the whole file (O(N²) bytes under a storm)."""

    _VAL = ({}, {}, {}, False, {}, None, {})

    def test_overflow_evicts_oldest_half_not_everything(self, tmp_path):
        s = _connect(str(tmp_path / "d"))
        ex = s.executor
        ex.CAPS_MEMO_MAX = 8
        for i in range(8):
            ex._caps_memo_insert(("fp", i), self._VAL)
        assert len(ex._caps_memo) == 8
        ex._caps_memo_insert(("fp", 8), self._VAL)  # overflow
        memo = dict(ex._caps_memo)
        assert len(memo) == 5  # 8 - oldest half (4) + the new one
        for i in range(4):
            assert ("fp", i) not in memo, "oldest half must evict"
        for i in range(4, 9):
            assert ("fp", i) in memo, "newest shapes must survive"
        # the surviving memo round-trips through the persisted file
        ex.flush_persistent()
        fresh = ex._load_caps_memo()
        assert set(fresh) == set(memo)
        s.close()

    def test_rewrite_debounced_and_flushed_on_close(self, tmp_path):
        s = _connect(str(tmp_path / "d"))
        ex = s.executor
        # suppress the idle-window flush so only the count threshold
        # can trigger a write inside this burst
        ex._memo_last_write = time.monotonic() + 3600
        writes0 = ex._memo_writes
        for i in range(ex.CAPS_MEMO_FLUSH_EVERY - 1):
            ex._caps_memo_insert(("storm", i), self._VAL)
        assert ex._memo_writes == writes0, \
            "a compile storm must coalesce memo rewrites"
        ex._caps_memo_insert(("storm", 99), self._VAL)
        assert ex._memo_writes == writes0 + 1  # threshold flush
        # dirty remainder drains at close so restarts start warm
        ex._memo_last_write = time.monotonic() + 3600
        ex._caps_memo_insert(("tail", 0), self._VAL)
        assert ex._memo_writes == writes0 + 1
        s.close()
        assert ex._memo_writes == writes0 + 2
        assert ("tail", 0) in ex._load_caps_memo()

    def test_lone_memoization_still_persists_promptly(self, tmp_path):
        s = _connect(str(tmp_path / "d"))
        ex = s.executor
        writes0 = ex._memo_writes
        ex._caps_memo_insert(("lone", 0), self._VAL)  # idle window open
        assert ex._memo_writes == writes0 + 1
        assert ("lone", 0) in ex._load_caps_memo()
        s.close()


    def test_parent_version_memo_is_ignored_not_misread(self, tmp_path):
        """A memo persisted at CAPS_MEMO_VERSION 8 holds six slots a
        statement, without the range capacities of the fused lookups
        that the seventh holds now: the seven-slot reader must drop the
        file by its version — never unpack it — and the statement
        converges its capacities anew."""
        data_dir = str(tmp_path / "d")
        sql = "SELECT x.a, y.a FROM t x JOIN t y ON x.b = y.b"

        def connect():
            # capacity feedback on (the default): the join's output
            # tightens at its first execution, which memoizes
            return citus_tpu.connect(data_dir=data_dir, n_devices=4,
                                     serving_result_cache_bytes=0)

        s = _seed(data_dir)
        s.close()
        s = connect()
        want = sorted(_rows(s.execute(sql)))
        assert len(want) == sum(n * n for _b, n, _s in EXPECTED)
        s.close()
        path = os.path.join(data_dir, "caps_memo.json")
        with open(path) as f:
            obj = json.load(f)
        assert obj["version"] == 9 and len(obj["memo"]) == 1
        # rewrite it as the parent wrote it
        slots = obj["memo"][0][1]["t"]
        assert len(slots) == 7
        slots.pop()
        obj["version"] = 8
        with open(path, "w") as f:
            json.dump(obj, f)
        s = connect()
        assert s.executor._caps_memo == {}
        assert sorted(_rows(s.execute(sql))) == want
        assert len(s.executor._caps_memo) == 1
        s.close()
        with open(path) as f:
            obj = json.load(f)
        assert obj["version"] == 9
        assert [len(v["t"]) for _k, v in obj["memo"]] == [7]


class TestHygiene:
    def test_prune_bounds_on_disk_entries(self, tmp_path):
        from citus_tpu.executor import execcache as xc

        data_dir = str(tmp_path / "d")
        s = _seed(data_dir)
        s.execute(SQL)
        xc_old = xc.EXEC_CACHE_MAX_ENTRIES
        try:
            xc.EXEC_CACHE_MAX_ENTRIES = 1
            # a second distinct shape overflows the 1-entry bound
            s.execute("SELECT count(*) FROM t WHERE b < 3")
            assert len(_cache_files(data_dir, ".meta.json")) <= 1
        finally:
            xc.EXEC_CACHE_MAX_ENTRIES = xc_old
        assert _rows(s.execute(SQL)) == EXPECTED  # pruning never breaks
        s.close()

    def test_index_survives_corruption(self, tmp_path):
        # the hotness index is advisory: corrupt it and warmup ordering
        # rebuilds from entry mtimes, entries still load verified
        data_dir = str(tmp_path / "d")
        s1 = _seed(data_dir)
        s1.execute(SQL)
        s1.close()
        ec = exec_cache_for(data_dir)
        ec.flush_index()
        idx = os.path.join(data_dir, EXEC_CACHE_DIR, "index.json")
        with open(idx, "w") as f:
            f.write("{not json")
        with ec._mu:
            ec._index_loaded = False  # force a re-read from disk
            ec._index = {}
        assert ec.top_hashes(8), "mtime rebuild found no entries"
        s2 = _connect(data_dir)
        assert _rows(s2.execute(SQL)) == EXPECTED
        assert s2.stats.counters.snapshot()[
            sc.EXEC_CACHE_HITS_TOTAL] >= 1
        s2.close()
