"""tools/t1_times.py — tier-1 duration-report parsing."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "tools"))

from t1_times import budget_cutoff, by_file, parse_durations  # noqa: E402

SAMPLE = """\
============================= slowest durations ==============================
12.50s call     tests/test_a.py::test_big
0.50s setup    tests/test_a.py::test_big
3.00s call     tests/test_b.py::TestC::test_mid
0.10s teardown tests/test_b.py::TestC::test_mid
1.00s call     tests/test_c.py::test_small
(3 durations < 0.005s hidden)
1 passed in 17.10s
"""


def test_parse_durations_sums_phases():
    totals = parse_durations(SAMPLE)
    assert totals["tests/test_a.py::test_big"] == 13.0
    assert totals["tests/test_b.py::TestC::test_mid"] == 3.1
    assert totals["tests/test_c.py::test_small"] == 1.0


def test_by_file_groups():
    files = by_file(parse_durations(SAMPLE))
    assert files == {"tests/test_a.py": 13.0, "tests/test_b.py": 3.1,
                     "tests/test_c.py": 1.0}


def test_budget_cutoff_orders_alphabetically():
    totals = parse_durations(SAMPLE)
    assert budget_cutoff(totals, budget=14.0) == ["tests/test_b.py",
                                                  "tests/test_c.py"]
    assert budget_cutoff(totals, budget=100.0) == []


def test_budget_cutoff_mirrors_conftest_front_loading():
    """The tool must rank in the suite's ACTUAL run order: conftest
    front-loads test_wlm.py/test_tools.py, so they consume budget
    FIRST even though they sort last alphabetically."""
    totals = {"tests/test_a.py::t": 5.0, "tests/test_wlm.py::t": 5.0}
    # 6s budget: test_wlm (front-loaded) fits, test_a is cut off
    assert budget_cutoff(totals, budget=6.0) == ["tests/test_a.py"]


# ---------------------------------------------------------------------------
# tools/trace_summarize.py smoke (tier-1): a recorded slow trace is
# summarizable end to end
# ---------------------------------------------------------------------------
def _record_slow_trace(data_dir: str):
    """Drive the recorder directly (no Session): one statement with a
    busy span, slow threshold 1 ms so the trace persists."""
    import time

    from citus_tpu.config import Settings
    from citus_tpu.stats.tracing import TraceRecorder, trace_span

    rec = TraceRecorder(data_dir,
                        Settings({"trace_slow_statement_ms": 1}))
    h = rec.begin("select 1")
    with trace_span("plan"):
        time.sleep(0.003)
    with trace_span("execute"):
        with trace_span("combine"):
            time.sleep(0.002)
    return rec.end(h)


def test_trace_summarize_prints_phase_breakdown(tmp_path, capsys):
    import trace_summarize

    trace = _record_slow_trace(str(tmp_path))
    assert trace is not None and trace.wall_ms >= 1
    assert trace_summarize.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "phase breakdown" in out
    assert "plan" in out and "total" in out
    assert "slowest spans" in out


def test_trace_summarize_prints_children_under_their_parents(tmp_path,
                                                            capsys):
    """The host path's children (PR 37) need no change to the tool: a
    span prints with its tree path, so `mesh.fetch.pull` stands under
    `mesh.fetch` and a `gc.pause` under whatever it stalled."""
    import gc
    import time

    import trace_summarize
    from citus_tpu.config import Settings
    from citus_tpu.stats.tracing import TraceRecorder, trace_span

    rec = TraceRecorder(str(tmp_path),
                        Settings({"trace_slow_statement_ms": 1}))
    h = rec.begin("select 1")
    with trace_span("execute"):
        with trace_span("plan"):
            with trace_span("subplan"):
                with trace_span("subplan.store"):
                    with trace_span("subplan.store.type", rows=1, cols=1):
                        gc.collect()
                    with trace_span("subplan.store.append"):
                        time.sleep(0.002)
        with trace_span("mesh.fetch"):
            with trace_span("mesh.fetch.wait"):
                time.sleep(0.002)
            with trace_span("mesh.fetch.pull"):
                pass
        with trace_span("subplan.drop"):
            pass
    assert rec.end(h) is not None
    assert trace_summarize.main([str(tmp_path), "--top", "20"]) == 0
    out = capsys.readouterr().out
    store = "execute/plan/subplan/subplan.store"
    for path in (f"{store}/subplan.store.type",
                 f"{store}/subplan.store.type/gc.pause",
                 f"{store}/subplan.store.append",
                 "execute/mesh.fetch/mesh.fetch.wait",
                 "execute/mesh.fetch/mesh.fetch.pull",
                 "execute/subplan.drop"):
        assert f"  {path}\n" in out, path


def test_trace_summarize_errors_cleanly_without_traces(tmp_path, capsys):
    import trace_summarize

    assert trace_summarize.main([str(tmp_path)]) == 1
    assert "trace_summarize:" in capsys.readouterr().err


# -- the native host library is built here, from the committed sources --

def test_native_library_is_not_tracked():
    import subprocess

    import pytest

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if not os.path.isdir(os.path.join(root, ".git")):
        pytest.skip("not a git checkout")
    tracked = subprocess.run(
        ["git", "ls-files", "citus_tpu/native"], cwd=root, check=True,
        capture_output=True, text=True).stdout.split()
    assert tracked and not [f for f in tracked if ".so" in f], tracked


def test_native_library_loads_here():
    import citus_tpu.native as native

    assert native.load_error() is None
    assert native.get_lib() is not None


def test_changed_source_hash_forces_a_native_rebuild(tmp_path):
    """Stale is decided by a hash of the two .cpp files stored beside
    the library, not by mtimes a checkout or a copy sets arbitrarily."""
    import shutil

    import citus_tpu.native as native

    build = str(tmp_path)
    for f in ("hashdict.cpp", "stripecodec.cpp"):
        shutil.copy(os.path.join(os.path.dirname(native.__file__), f),
                    build)
    so = os.path.join(build, "_native.so")
    native._build_and_load(build)
    with open(so + ".sha256") as f:
        first = f.read()
    built_at = os.stat(so).st_mtime_ns

    # same sources, library made to look OLDER than them: no rebuild
    os.utime(so, ns=(1, 1))
    native._build_and_load(build)
    assert os.stat(so).st_mtime_ns == 1

    # one changed byte in a source: rebuilt, whatever the mtimes say
    with open(os.path.join(build, "hashdict.cpp"), "a") as f:
        f.write("\n// changed\n")
    os.utime(os.path.join(build, "hashdict.cpp"), ns=(0, 0))
    native._build_and_load(build)
    with open(so + ".sha256") as f:
        assert f.read() != first
    assert os.stat(so).st_mtime_ns >= built_at

    # a library with no stamp beside it is one built elsewhere: rebuilt
    os.unlink(so + ".sha256")
    os.utime(so, ns=(1, 1))
    native._build_and_load(build)
    assert os.stat(so).st_mtime_ns != 1 and os.path.exists(so + ".sha256")


# -- where JAX's persistent compilation cache goes (citus_tpu/runtime.py) --

def _configure_as(monkeypatch, backend, env_dir):
    """Run ensure_jax_configured's first-call branch against a recording
    jax.config.update, as the backend `backend` would see it."""
    import jax

    import citus_tpu.runtime as runtime

    updates = {}
    monkeypatch.setattr(runtime, "_configured", False)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    runtime.ensure_jax_configured()
    return updates


def test_compile_cache_dir_is_the_environments_when_it_names_one(
        monkeypatch, tmp_path):
    updates = _configure_as(monkeypatch, "tpu", str(tmp_path))
    assert "jax_compilation_cache_dir" not in updates
    assert "jax_enable_compilation_cache" not in updates


def test_compile_cache_dir_is_fixed_inside_the_checkout_otherwise(
        monkeypatch):
    import citus_tpu.runtime as runtime

    updates = _configure_as(monkeypatch, "tpu", None)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert updates["jax_compilation_cache_dir"] == \
        os.path.join(root, ".jax_cache") == runtime.COMPILE_CACHE_DIR


def test_compile_cache_stays_off_on_the_cpu_backend(monkeypatch, tmp_path):
    # even when JAX_PLATFORMS would read "tpu,cpu": the backend is asked
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    updates = _configure_as(monkeypatch, "cpu", str(tmp_path))
    assert updates["jax_enable_compilation_cache"] is False
    assert "jax_compilation_cache_dir" not in updates
