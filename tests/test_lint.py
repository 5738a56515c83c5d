"""graftlint: tier-1 tree gate, fixture-corpus goldens, baseline
hygiene, CLI, and the runtime lock-order sanitizer self-tests.

The tree gate is THE acceptance check: the whole `citus_tpu/` +
`tools/` tree must lint clean against `lint_baseline.json` (every
baseline entry individually justified) in under 15 s.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from citus_tpu.analysis import load_baseline, run_lint, unbaselined
from citus_tpu.analysis.core import BASELINE_NAME

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "lint_fixtures")


# ---------------------------------------------------------------------------
# tree gate (ONE timed whole-tree scan, shared by the wrapper tests so
# the file stays cheap in the tier-1 wall-clock budget)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tree_scan():
    t0 = time.monotonic()
    findings = run_lint(ROOT)
    return findings, time.monotonic() - t0


def test_tree_lints_clean_within_budget(tree_scan):
    findings, elapsed = tree_scan
    baseline = load_baseline(os.path.join(ROOT, BASELINE_NAME))
    fresh, stale = unbaselined(findings, baseline)
    assert not fresh, ("unbaselined graftlint findings:\n"
                       + "\n".join(str(f) for f in fresh))
    assert not stale, ("stale baseline entries (fixed — remove them):\n"
                       + "\n".join(stale))
    # tier-1 duration budget (tools/t1_times.py ranks this file): the
    # whole-tree AST pass must stay cheap enough to gate every PR
    assert elapsed < 15.0, f"tree lint took {elapsed:.1f}s (budget 15s)"


def test_baseline_entries_all_justified():
    with open(os.path.join(ROOT, BASELINE_NAME)) as f:
        data = json.load(f)
    for e in data["findings"]:
        why = e.get("why", "")
        assert why and "TODO" not in why, (
            f"baseline entry without a justification: {e}")


def test_cli_exits_zero_on_clean_tree():
    """Acceptance: `python -m citus_tpu.analysis` exits 0."""
    proc = subprocess.run(
        [sys.executable, "-m", "citus_tpu.analysis", "--json"],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["findings"] == []
    assert payload["baselined"] >= 0


# ---------------------------------------------------------------------------
# fixture corpus: every rule family fires on its fixture, clean
# fixtures stay silent
# ---------------------------------------------------------------------------
GOLDEN = {
    ("lock-order-cycle", "citus_tpu/cycle_ab.py", 17),
    ("unlocked-shared-write", "citus_tpu/guarded.py", 19),
    ("unlocked-shared-write", "citus_tpu/guarded.py", 22),
    ("raw-lock-acquire", "citus_tpu/guarded.py", 25),
    ("bare-except", "citus_tpu/discipline_bad.py", 14),
    ("swallowed-base-exception", "citus_tpu/discipline_bad.py", 21),
    ("swallowed-fault-seam", "citus_tpu/discipline_bad.py", 29),
    ("silent-exception", "citus_tpu/discipline_bad.py", 36),
    ("unowned-thread", "citus_tpu/discipline_bad.py", 41),
    ("raw-durable-write", "citus_tpu/rawwrite.py", 7),
    ("raw-durable-write", "citus_tpu/rawwrite.py", 11),
    ("raw-durable-write", "citus_tpu/rawwrite.py", 15),
    ("raw-device-placement", "citus_tpu/rawplace.py", 9),
    ("raw-device-placement", "citus_tpu/rawplace.py", 13),
    ("raw-device-placement", "citus_tpu/rawplace.py", 17),
    # a device-TARGETED put outside distributed/mesh.py trips BOTH
    # placement rules: it bypasses the accounted seam AND the mesh
    # fault/DeviceLostError seam
    ("mesh-seam", "citus_tpu/rawplace.py", 9),
    ("mesh-seam", "citus_tpu/meshseam.py", 9),
    ("mesh-seam", "citus_tpu/meshseam.py", 13),
    ("raw-device-placement", "citus_tpu/meshseam.py", 9),
    ("raw-device-placement", "citus_tpu/meshseam.py", 13),
    ("raw-device-placement", "citus_tpu/meshseam.py", 19),
    ("host-sync-in-traced", "citus_tpu/executor/hot.py", 12),
    ("host-sync-in-traced", "citus_tpu/executor/hot.py", 13),
    ("host-sync-in-traced", "citus_tpu/executor/hot.py", 14),
    ("traced-python-branch", "citus_tpu/executor/hot.py", 15),
    ("host-sync-in-traced", "citus_tpu/executor/hot.py", 22),
    ("jit-in-loop", "citus_tpu/executor/hot.py", 34),
    ("traced-python-branch", "citus_tpu/executor/hot.py", 47),
    ("device-sync-in-loop", "citus_tpu/executor/stream.py", 10),
    ("device-sync-in-loop", "citus_tpu/executor/stream.py", 11),
    ("fault-point-registry", "citus_tpu/uses.py", 23),
    ("fault-point-registry", "citus_tpu/utils/faultinjection.py", 5),
    ("counter-registry", "citus_tpu/uses.py", 25),
    ("counter-registry", "citus_tpu/stats/counters.py", 1),
    ("counter-registry", "citus_tpu/stats/counters.py", 7),
    ("config-registry", "citus_tpu/uses.py", 27),
    ("config-registry", "citus_tpu/config.py", 17),
    ("explain-tag-registry", "citus_tpu/uses.py", 29),
    ("explain-tag-registry", "citus_tpu/planner/explain.py", 5),
    ("span-registry", "citus_tpu/uses.py", 31),
    ("span-registry", "citus_tpu/uses.py", 34),
    ("span-registry", "citus_tpu/uses.py", 35),
    ("span-registry", "citus_tpu/stats/tracing.py", 5),
    ("span-registry", "citus_tpu/stats/tracing.py", 11),
}


@pytest.fixture(scope="module")
def fixture_findings():
    return run_lint(FIXTURES)


def test_fixture_corpus_matches_golden(fixture_findings):
    got = {(f.rule, f.path, f.line) for f in fixture_findings}
    missing = GOLDEN - got
    extra = got - GOLDEN
    assert not missing, f"rules stopped firing on fixtures: {missing}"
    assert not extra, f"unexpected fixture findings: {extra}"


def test_each_rule_family_has_a_firing_fixture():
    """Acceptance: ≥1 fixture proves each of the 4 families fires."""
    rules = {r for r, _p, _l in GOLDEN}
    families = {
        "locks": {"lock-order-cycle", "unlocked-shared-write",
                  "raw-lock-acquire"},
        "hotpath": {"host-sync-in-traced", "traced-python-branch",
                    "device-sync-in-loop", "jit-in-loop"},
        "registries": {"fault-point-registry", "counter-registry",
                       "config-registry", "explain-tag-registry",
                       "span-registry"},
        "discipline": {"bare-except", "swallowed-base-exception",
                       "swallowed-fault-seam", "silent-exception",
                       "unowned-thread", "raw-durable-write",
                       "raw-device-placement", "mesh-seam"},
    }
    for family, expected in families.items():
        assert expected <= rules, f"family {family} missing fixtures"


def test_clean_fixtures_stay_silent(fixture_findings):
    assert not [f for f in fixture_findings
                if f.path == "citus_tpu/clean.py"]
    # the io seam itself is the sanctioned home of raw primitives
    assert not [f for f in fixture_findings
                if f.path == "citus_tpu/utils/io.py"]
    # the sanctioned per-batch sync carries an inline ignore
    assert not [f for f in fixture_findings
                if f.path == "citus_tpu/executor/stream.py"
                and f.context == "sanctioned"]


def test_inline_ignore_suppresses(tmp_path):
    sub = tmp_path / "citus_tpu"
    sub.mkdir()
    (sub / "mod.py").write_text(
        "def f():\n"
        "    try:\n"
        "        return 1\n"
        "    except:  # graftlint: ignore[bare-except] — test\n"
        "        return 2\n")
    assert run_lint(str(tmp_path)) == []
    (sub / "mod.py").write_text(
        "def f():\n"
        "    try:\n"
        "        return 1\n"
        "    except:\n"
        "        return 2\n")
    assert [f.rule for f in run_lint(str(tmp_path))] == ["bare-except"]


# ---------------------------------------------------------------------------
# registry-sync wrappers (the migrated ad-hoc consistency tests; the
# fault-point wrapper lives with its siblings in test_fault_injection)
# ---------------------------------------------------------------------------
def test_subset_scan_skips_unused_direction():
    """A subset run (explicit path) must not report registry entries
    as unused merely because their use sites weren't scanned — the
    registry module alone lints clean."""
    assert run_lint(
        ROOT, subdirs=("citus_tpu/planner/explain.py",)) == []
    assert run_lint(ROOT, subdirs=("citus_tpu/config.py",)) == []
    assert run_lint(
        ROOT, subdirs=("citus_tpu/stats/tracing.py",)) == []


def test_counter_registry_in_sync(tree_scan):
    assert [f for f in tree_scan[0]
            if f.rule == "counter-registry"] == []


def test_explain_tag_registry_in_sync(tree_scan):
    assert [f for f in tree_scan[0]
            if f.rule == "explain-tag-registry"] == []


def test_span_registry_in_sync(tree_scan):
    assert [f for f in tree_scan[0]
            if f.rule == "span-registry"] == []


# the host path's names (PR 37) and the file that records each
HOST_PATH_SPANS = {
    "mesh.fetch.wait": "citus_tpu/executor/runner.py",
    "mesh.fetch.pull": "citus_tpu/executor/runner.py",
    "subplan.store.type": "citus_tpu/session.py",
    "subplan.store.intern": "citus_tpu/session.py",
    "subplan.store.append": "citus_tpu/session.py",
    "subplan.drop": "citus_tpu/session.py",
    "subplan.feed": "citus_tpu/executor/feed.py",
    "gc.pause": "citus_tpu/stats/tracing.py",
}


@pytest.mark.parametrize("name", sorted(HOST_PATH_SPANS))
def test_host_path_span_is_held_both_ways(tree_scan, tmp_path, name):
    """Declared and recorded in the tree; and the rule would say so if
    either half went: an entry without a record site is dead, a record
    site without an entry is a ghost."""
    from citus_tpu.stats.tracing import SPAN_NAMES

    assert name in SPAN_NAMES
    with open(os.path.join(ROOT, HOST_PATH_SPANS[name])) as f:
        assert f'trace_span("{name}"' in f.read()
    assert not [f for f in tree_scan[0]
                if f.rule == "span-registry" and repr(name) in f.message]
    pkg = tmp_path / "citus_tpu"
    (pkg / "stats").mkdir(parents=True)
    declared = f"SPAN_NAMES = {{{name!r}: 'x', 'statement': 'root'}}\n"
    recorded = ("from .stats.tracing import span_name, trace_span\n"
                "def f():\n"
                "    span_name('statement')\n"
                f"    with trace_span({name!r}):\n"
                "        pass\n")

    def findings():
        return [(f.path, f.message) for f in run_lint(str(tmp_path))
                if f.rule == "span-registry"]

    (pkg / "stats" / "tracing.py").write_text(declared)
    (pkg / "site.py").write_text(recorded)
    assert findings() == []
    (pkg / "site.py").write_text("def f():\n    span_name('statement')\n")
    (dead,) = findings()
    assert dead[0] == "citus_tpu/stats/tracing.py" and repr(name) in dead[1]
    (pkg / "stats" / "tracing.py").write_text(
        "SPAN_NAMES = {'statement': 'root'}\n")
    (pkg / "site.py").write_text(recorded)
    (ghost,) = findings()
    assert ghost[0] == "citus_tpu/site.py" and repr(name) in ghost[1]


def test_config_registry_in_sync_modulo_baseline(tree_scan):
    findings = [f for f in tree_scan[0] if f.rule == "config-registry"]
    baseline = load_baseline(os.path.join(ROOT, BASELINE_NAME))
    fresh, _stale = unbaselined(findings, baseline)
    assert fresh == []


# ---------------------------------------------------------------------------
# runtime lock-order sanitizer
# ---------------------------------------------------------------------------
@pytest.fixture
def tsan():
    from citus_tpu.analysis import sanitizer

    sanitizer.reset()
    yield sanitizer
    sanitizer.disable()
    sanitizer.reset()


def test_sanitizer_catches_seeded_inversion(tsan):
    """Acceptance self-test: a deliberate ABBA inversion is caught —
    deterministically, without any actual deadlock or second thread."""
    with tsan.enabled():
        a = threading.Lock()
        b = threading.Lock()
        with a:
            with b:
                pass
        with pytest.raises(tsan.LockOrderViolation):
            with b:
                with a:
                    pass
    assert len(tsan.violations()) == 1
    v = tsan.violations()[0]
    assert v.first != v.second
    assert "inverting acquisition" in str(v)


def test_sanitizer_catches_cross_thread_inversion(tsan):
    with tsan.enabled():
        a = threading.Lock()
        b = threading.Lock()

        def t1():
            with a:
                with b:
                    pass

        th = threading.Thread(target=t1)
        th.start()
        th.join()
        caught: list = []

        def t2():
            try:
                with b:
                    with a:
                        pass
            except tsan.LockOrderViolation as e:
                caught.append(e)

        th2 = threading.Thread(target=t2)
        th2.start()
        th2.join()
    assert caught, "inversion on the second thread was not raised"


def test_sanitizer_self_deadlock(tsan):
    with tsan.enabled():
        lk = threading.Lock()
        lk.acquire()
        with pytest.raises(tsan.LockOrderViolation):
            lk.acquire()
        lk.release()
    # the probe acquire (blocking=False) must NOT false-positive:
    # Condition._is_owned uses it on plain Locks
    with tsan.enabled():
        lk2 = threading.Lock()
        with lk2:
            assert lk2.acquire(False) is False


def test_sanitizer_no_raise_mode_records_once(tsan):
    with tsan.enabled(raise_on_violation=False):
        a = threading.Lock()
        b = threading.Lock()
        with a:
            with b:
                pass
        for _ in range(5):       # the SAME inversion, repeatedly
            with b:
                with a:
                    pass         # recorded once, not raised
    # deduped: a no-raise harness in a hot loop must not accumulate
    # thousands of identical stacks
    assert len(tsan.violations()) == 1


def test_sanitizer_release_after_disable_no_phantom(tsan):
    with tsan.enabled():
        lk = threading.Lock()
        lk.acquire()
    lk.release()   # after disable(): must still clear the held stack
    tsan.reset()
    with tsan.enabled():
        a = threading.Lock()
        with a:    # would record a phantom lk→a edge otherwise
            pass
        assert tsan.stats()["order_edges"] == 0
    assert tsan.violations() == []


def test_cli_rejects_missing_path():
    proc = subprocess.run(
        [sys.executable, "-m", "citus_tpu.analysis",
         "citus_tpu/wlm/admision.py"],   # typo'd on purpose
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2
    assert "no such file" in proc.stderr


def test_sanitizer_rlock_and_condition_compat(tsan):
    with tsan.enabled():
        r = threading.RLock()
        with r:
            with r:   # reentrant: no self-deadlock report
                pass
        cv = threading.Condition()          # wraps a tracked RLock
        cvl = threading.Condition(threading.Lock())

        def waiter():
            with cv:
                cv.wait(timeout=0.2)

        th = threading.Thread(target=waiter)
        th.start()
        time.sleep(0.02)
        with cv:
            cv.notify_all()
        th.join()
        with cvl:
            cvl.notify_all()
    assert tsan.violations() == []


def test_tsan_env_var_arms_at_import():
    """CITUS_TPU_TSAN=1 arms the sanitizer at `import citus_tpu`, so
    every manager lock a subsequently opened session creates is
    tracked (the chaos soak arms the same machinery in-process)."""
    env = dict(os.environ, CITUS_TPU_TSAN="1")
    proc = subprocess.run(
        [sys.executable, "-c",
         "import citus_tpu, threading\n"
         "from citus_tpu.analysis import sanitizer\n"
         "assert sanitizer.stats()['enabled']\n"
         "assert type(threading.Lock()).__name__ == 'TsanLock'\n"
         "print('armed')"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "armed" in proc.stdout


def test_sanitizer_consistent_engine_order_is_clean(tsan):
    """A tiny end-to-end: session open + DDL + DML + a transaction
    with every lock tracked — the engine's real acquisition orders
    must be violation-free (the chaos soak runs the big version)."""
    import citus_tpu

    with tsan.enabled():
        import tempfile

        d = tempfile.mkdtemp()
        s = citus_tpu.connect(data_dir=d, n_devices=2)
        s.execute("CREATE TABLE t1 (id INT, v INT)")
        s.execute("SELECT create_distributed_table('t1', 'id', 2)")
        s.execute("INSERT INTO t1 VALUES (1, 10), (2, 20)")
        s.execute("BEGIN")
        s.execute("UPDATE t1 SET v = 11 WHERE id = 1")
        s.execute("COMMIT")
        assert int(s.execute(
            "SELECT sum(v) FROM t1").rows()[0][0]) == 31
        s.close()
        assert tsan.stats()["acquisitions"] > 0
    assert tsan.violations() == []
