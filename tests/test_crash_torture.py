"""Power-cut torture harness (SQLite crash-test style).

A deterministic DML workload runs against a Session while the crash
shim (citus_tpu/utils/crashsim.py) counts every durable write op going
through the utils/io seam.  Then, for each write-op index N, the
workload replays from the same base state and the "power" is cut at op
N — the op is torn/lost/completed per the physically possible
post-crash states, the disk freezes, and the dying session is
abandoned.  A COLD restart (fresh Catalog + TableStore +
recover_transactions) must then satisfy THE invariant:

    every unit committed before the crash is fully visible, the
    in-flight unit is fully visible XOR fully invisible, every stripe
    checksum verifies, and (after the scrub pass) no orphan temp file
    remains.

Tier-1 runs a deterministic >=25-crashpoint slice; the full every-N
sweep is `slow`.  Crash-during-shard-move and crash-during-split
regressions ride the same shim.
"""

import os
import shutil

import pytest

import citus_tpu
from citus_tpu.catalog import Catalog
from citus_tpu.operations.cleanup import CleanupRegistry
from citus_tpu.operations.scrubber import ScrubReport, scrub_store
from citus_tpu.storage import TableStore
from citus_tpu.transaction.manager import recover_transactions
from citus_tpu.utils.crashsim import CrashSim, PowerCut, power_cut_at
from citus_tpu.utils import io as dio

SEED_ROWS = {i: 100 + i for i in range(40)}

# The torture workload: (statements, apply(model)) per ATOMIC unit —
# autocommit statements and whole transactions.  A crash mid-unit may
# leave the unit fully applied or fully absent, never half.
def _u_insert(model):
    model.update({100: 1, 101: 2, 102: 3})


def _u_update(model):
    model[100] = 50


def _u_delete(model):
    model.pop(3, None)


def _u_txn(model):
    model[101] = 9
    model[200] = 7
    model.pop(4, None)


def _u_insert2(model):
    model[300] = 5


def _u_point_update(model):
    model[7] = 777


def _u_rollback(_model):
    pass  # ROLLBACK: no effect by definition


def _u_insert3(model):
    model.update({400: 1, 401: 2})


UNITS = [
    (["INSERT INTO kv VALUES (100, 1), (101, 2), (102, 3)"], _u_insert),
    (["UPDATE kv SET v = 50 WHERE id = 100"], _u_update),
    (["DELETE FROM kv WHERE id = 3"], _u_delete),
    (["BEGIN",
      "UPDATE kv SET v = 9 WHERE id = 101",
      "INSERT INTO kv VALUES (200, 7)",
      "DELETE FROM kv WHERE id = 4",
      "COMMIT"], _u_txn),
    (["INSERT INTO kv VALUES (300, 5)"], _u_insert2),
    (["UPDATE kv SET v = 777 WHERE id = 7"], _u_point_update),
    (["BEGIN",
      "DELETE FROM kv WHERE id = 300",
      "ROLLBACK"], _u_rollback),
    (["INSERT INTO kv VALUES (400, 1), (401, 2)"], _u_insert3),
]


def _states():
    """states[j] = expected model after the first j units."""
    out = [dict(SEED_ROWS)]
    for _stmts, apply_fn in UNITS:
        m = dict(out[-1])
        apply_fn(m)
        out.append(m)
    return out


STATES = _states()

_QUIET = dict(n_devices=2, recover_2pc_interval_ms=-1,
              defer_shard_delete_interval_ms=-1,
              health_check_interval_ms=-1, retry_backoff_base_ms=1)


def _connect(path, **kw):
    merged = dict(_QUIET)
    merged.update(kw)
    return citus_tpu.connect(data_dir=str(path), **merged)


def _abandon(sess):
    """Simulated process death: stop the threads, save NOTHING."""
    sess.maintenance.stop()
    sess.jobs.shutdown()


@pytest.fixture(scope="module")
def base_dir(tmp_path_factory):
    base = tmp_path_factory.mktemp("torture") / "base"
    sess = _connect(base)
    sess.execute("CREATE TABLE kv (id INT, v INT)")
    sess.execute("SELECT create_distributed_table('kv', 'id', 4)")
    sess.execute("INSERT INTO kv VALUES " + ", ".join(
        f"({i}, {v})" for i, v in SEED_ROWS.items()))
    sess.close()
    return base


def _cold_restart(work) -> tuple[Catalog, TableStore, dict]:
    """Fresh Catalog + TableStore + 2PC recovery — a cold process on
    the crashed directory (no Session: keeps the sweep cheap)."""
    cat = Catalog.load(os.path.join(work, "catalog.json"))
    store = TableStore(str(work), cat)
    recover_transactions(store, os.path.join(work, "txnlog"))
    # a second recovery pass must be a no-op (idempotence)
    assert recover_transactions(
        store, os.path.join(work, "txnlog")) == (0, 0)
    return cat, store, _read_state(cat, store)


def _read_state(cat, store, table="kv") -> dict:
    out = {}
    for shard in cat.table_shards(table):
        vals, _mask, n = store.read_shard(table, shard.shard_id,
                                          ["id", "v"])
        for i in range(n):
            out[int(vals["id"][i])] = int(vals["v"][i])
    return out


def _no_orphan_temps(work) -> list[str]:
    leftovers = []
    for dpath, _dirs, files in os.walk(work):
        if "restore_points" in dpath:
            continue
        for f in files:
            if f.startswith(".aw.") or ".tmp" in f:
                leftovers.append(os.path.join(dpath, f))
    return leftovers


def _run_workload(sess):
    for i, (stmts, _apply) in enumerate(UNITS):
        for sql in stmts:
            sess.execute(sql)
    return i


class _OwnDiskSim(CrashSim):
    """A CrashSim that sees only writes under `root`, the simulated
    process's data directory.  CrashSim counts every durable write of
    the PROCESS; under xdist the maintenance threads of sessions that
    earlier test files left open write `cleanup.json` meanwhile, and
    one more op in the rehearsal than in a replay reads as "op N never
    reached"."""

    def __init__(self, crash_at, mode, root):
        super().__init__(crash_at, mode)
        self.root = os.path.abspath(root) + os.sep

    def op(self, kind, path, payload=None, tmp=None):
        if os.path.abspath(path).startswith(self.root):
            super().op(kind, path, payload, tmp)


def _own_disk_power_cut(crash_at, mode, root) -> power_cut_at:
    cut = power_cut_at(crash_at, mode)
    cut.sim = _OwnDiskSim(crash_at, mode, str(root))
    return cut


def _rehearse(base_dir, tmp_path) -> int:
    """Count the workload's durable write ops (no crash) and pin the
    final state against the model."""
    work = tmp_path / "rehearsal"
    shutil.copytree(base_dir, work)
    sess = _connect(work)
    with _own_disk_power_cut(None, None, work) as sim:
        _run_workload(sess)
    sess.close()
    cat, store, state = _cold_restart(str(work))
    assert state == STATES[-1], "rehearsal end state diverged from model"
    assert sim.ops >= 25, (
        f"workload too small for a 25-crashpoint slice ({sim.ops} ops)")
    return sim.ops


def _torture_one(base_dir, tmp_path, n: int,
                 mode: str | None = None) -> str:
    """Replay the workload, cut power at op `n` (tear mode forced or
    cycled), cold-restart, assert the invariant.  Returns the tear
    mode applied (telemetry)."""
    work = tmp_path / f"crash_{mode or 'cyc'}_{n:03d}"
    shutil.copytree(base_dir, work)
    sess = _connect(work)
    crashed_unit = None
    completed_units = 0
    with _own_disk_power_cut(n, mode, work) as sim:
        try:
            for i, (stmts, _apply) in enumerate(UNITS):
                for sql in stmts:
                    sess.execute(sql)
                completed_units = i + 1
        except PowerCut:
            crashed_unit = completed_units  # the unit in flight
        finally:
            _abandon(sess)
    assert crashed_unit is not None, f"op {n} never reached"
    cat, store, state = _cold_restart(str(work))
    allowed = (STATES[crashed_unit], STATES[crashed_unit + 1])
    assert state in allowed, (
        f"crash at op {n} (tear={sim.tear_applied}, unit "
        f"{crashed_unit}): recovered state is neither pre- nor "
        f"post-unit.\n got: {state}\n pre: {allowed[0]}\n post: "
        f"{allowed[1]}")
    # every committed stripe checksums clean; crash debris is swept
    rep = scrub_store(cat, store, ScrubReport(), temp_max_age_s=0.0)
    assert rep.corrupt_copies == 0 and rep.unrepairable == 0, (
        f"crash at op {n}: corruption after recovery: {rep.details}")
    leftovers = _no_orphan_temps(str(work))
    assert not leftovers, (
        f"crash at op {n}: orphan temp files survived the scrub: "
        f"{leftovers}")
    shutil.rmtree(work, ignore_errors=True)
    return sim.tear_applied or "none"


class TestPowerCutTorture:
    def test_tier1_crashpoint_slice(self, base_dir, tmp_path):
        """Deterministic >=25-crashpoint slice spread over the whole
        workload, all three tear modes exercised."""
        total = _rehearse(base_dir, tmp_path)
        n_points = min(total, 27)
        points = sorted({1 + (k * (total - 1)) // (n_points - 1)
                         for k in range(n_points)})
        assert len(points) >= 25
        modes = set()
        for n in points:
            modes.add(_torture_one(base_dir, tmp_path, n))
        assert modes >= {"lost", "torn", "complete"}

    @pytest.mark.slow
    def test_full_crashpoint_sweep(self, base_dir, tmp_path):
        """Acceptance: EVERY write-op index in the workload, under
        EVERY tear mode (lost / torn / complete)."""
        total = _rehearse(base_dir, tmp_path)
        for mode in (None, "lost", "torn", "complete"):
            for n in range(1, total + 1):
                _torture_one(base_dir, tmp_path, n, mode=mode)


class TestCrashSimPrimitives:
    def test_torn_atomic_write_leaves_orphan_not_target(self, tmp_path):
        p = str(tmp_path / "x.json")
        dio.atomic_write_bytes(p, b"first")
        sim = CrashSim(crash_at=1, mode="torn")
        dio.install_sim(sim)
        try:
            with pytest.raises(PowerCut):
                dio.atomic_write_bytes(p, b"second-version")
        finally:
            dio.install_sim(None)
        assert open(p, "rb").read() == b"first"  # target untouched
        torn = [f for f in os.listdir(tmp_path) if f.startswith(".aw.")]
        assert len(torn) == 1

    def test_complete_mode_makes_op_durable(self, tmp_path):
        p = str(tmp_path / "x.json")
        sim = CrashSim(crash_at=1, mode="complete")
        dio.install_sim(sim)
        try:
            with pytest.raises(PowerCut):
                dio.atomic_write_bytes(p, b"payload")
        finally:
            dio.install_sim(None)
        assert open(p, "rb").read() == b"payload"

    def test_disk_freezes_after_the_cut(self, tmp_path):
        sim = CrashSim(crash_at=1, mode="lost")
        dio.install_sim(sim)
        try:
            with pytest.raises(PowerCut):
                dio.atomic_write_bytes(str(tmp_path / "a"), b"x")
            with pytest.raises(PowerCut):
                dio.atomic_write_bytes(str(tmp_path / "b"), b"y")
        finally:
            dio.install_sim(None)
        assert not os.path.exists(tmp_path / "a")
        assert not os.path.exists(tmp_path / "b")

    def test_torn_stream_truncates_tmp(self, tmp_path):
        p = str(tmp_path / "s.bin")
        sim = CrashSim(crash_at=1, mode="torn")
        dio.install_sim(sim)
        try:
            with pytest.raises(PowerCut):
                with dio.atomic_stream_writer(p) as f:
                    f.write(b"A" * 1000)
        finally:
            dio.install_sim(None)
        assert not os.path.exists(p)
        tmps = [f for f in os.listdir(tmp_path) if ".tmp" in f]
        assert len(tmps) == 1
        assert os.path.getsize(tmp_path / tmps[0]) == 500


class TestCrashDuringShardOps:
    """Satellite: a power cut mid-move / mid-split leaves the source
    placement authoritative and no half-copied placement visible."""

    def _fresh(self, tmp_path, name):
        d = tmp_path / name
        sess = _connect(d)
        sess.execute("CREATE TABLE kv (id INT, v INT)")
        sess.execute("SELECT create_distributed_table('kv', 'id', 2)")
        sess.execute("INSERT INTO kv VALUES " + ", ".join(
            f"({i}, {v})" for i, v in SEED_ROWS.items()))
        return d, sess

    @pytest.mark.parametrize("mode", ["lost", "torn", "complete"])
    def test_crash_during_move(self, tmp_path, mode):
        from citus_tpu.operations.shard_transfer import (
            move_shard_placement,
        )

        d, sess = self._fresh(tmp_path, f"move_{mode}")
        shard = sess.catalog.table_shards("kv")[0]
        src_node = sess.catalog.active_placement(
            shard.shard_id, probe=False).node_id
        target = next(nd.name for nd in sess.catalog.nodes.values()
                      if nd.node_id != src_node)
        with power_cut_at(1, mode=mode):
            moved = False
            try:
                move_shard_placement(sess.catalog, sess.store,
                                     shard.shard_id, target)
                sess._save_catalog()
                moved = True
            except PowerCut:
                pass
            finally:
                _abandon(sess)
        assert not moved  # the save is op 1: the cut always hits it
        cat, store, state = _cold_restart(str(d))
        assert state == SEED_ROWS  # data intact either way
        p = cat.active_placement(shard.shard_id, probe=False)
        if mode == "complete":  # the flip was durable: move committed
            assert p.node_id != src_node
        else:  # source placement stays authoritative
            assert p.node_id == src_node
            assert all(q.shard_state == "active"
                       for q in cat.shard_placements(shard.shard_id))

    def test_injected_fault_before_split_commit(self, tmp_path):
        """The `operations.shard_split` seam: a kill after the children
        are written but before the catalog commit rolls the whole split
        back — parent authoritative, children swept."""
        from citus_tpu.operations.shard_split import (
            split_shard_by_split_points,
        )
        from citus_tpu.utils import faultinjection as fi
        from citus_tpu.utils.faultinjection import InjectedFault

        d, sess = self._fresh(tmp_path, "split_fault")
        shard = sess.catalog.table_shards("kv")[0]
        mid = (shard.min_value + shard.max_value) // 2
        original = {s.shard_id for s in sess.catalog.table_shards("kv")}
        with fi.inject("operations.shard_split"):
            with pytest.raises(InjectedFault):
                split_shard_by_split_points(sess, shard.shard_id, [mid])
        assert {s.shard_id
                for s in sess.catalog.table_shards("kv")} == original
        got = {int(i): int(v) for i, v in
               sess.execute("SELECT id, v FROM kv").rows()}
        assert got == SEED_ROWS
        # the split is retryable after the clean failure
        children = split_shard_by_split_points(sess, shard.shard_id,
                                               [mid])
        assert len(children) == 2
        got = {int(i): int(v) for i, v in
               sess.execute("SELECT id, v FROM kv").rows()}
        assert got == SEED_ROWS
        sess.close()

    def test_crash_sweep_during_split(self, tmp_path):
        """Cut power at EVERY write op of a shard split: after a cold
        restart + cleanup sweep the catalog either shows the committed
        split (children own all rows) or the untouched parent — never
        a half-copied placement."""
        from citus_tpu.operations.shard_split import (
            split_shard_by_split_points,
        )

        # rehearsal: count the split's ops
        d, sess = self._fresh(tmp_path, "split_rehearsal")
        shard = sess.catalog.table_shards("kv")[0]
        mid = (shard.min_value + shard.max_value) // 2
        with power_cut_at(None) as sim:
            split_shard_by_split_points(sess, shard.shard_id, [mid])
        sess.close()
        total = sim.ops
        assert total >= 3
        for n in range(1, total + 1):
            dn, sess = self._fresh(tmp_path, f"split_{n:02d}")
            shard = sess.catalog.table_shards("kv")[0]
            parent_id = shard.shard_id
            mid = (shard.min_value + shard.max_value) // 2
            original_shards = {s.shard_id
                               for s in sess.catalog.table_shards("kv")}
            with power_cut_at(n):
                try:
                    split_shard_by_split_points(sess, parent_id, [mid])
                except PowerCut:
                    pass
                finally:
                    _abandon(sess)
            cat = Catalog.load(os.path.join(dn, "catalog.json"))
            store = TableStore(str(dn), cat)
            recover_transactions(store, os.path.join(dn, "txnlog"))
            # cold-process cleanup sweep (fresh registry: the crashed
            # process's in-memory active-op guard died with it)
            CleanupRegistry(str(dn)).sweep(store, cat)
            shards = {s.shard_id for s in cat.table_shards("kv")}
            if parent_id in shards:  # split did not commit
                assert shards == original_shards
            else:  # committed: parent fully replaced by children
                assert parent_id not in shards
                assert len(shards) == len(original_shards) + 1
            # placements never dangle on unknown shards
            for p in cat.placements.values():
                assert p.shard_id in cat.shards
            # every row still readable exactly once, checksums clean
            assert _read_state(cat, store) == SEED_ROWS
            rep = scrub_store(cat, store, ScrubReport(),
                              temp_max_age_s=0.0)
            assert rep.corrupt_copies == 0
            shutil.rmtree(dn, ignore_errors=True)
