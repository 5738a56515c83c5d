#!/usr/bin/env python3
"""benchmark/run.py — one run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, the chips the cell asks for.  Everything a cell is made of
is found by name, in files of its own under this directory:

    BENCHMARK.json workloads[] -> configs/<config>.json   the deployment
                               -> traffic/<traffic>.json  the load
    traffic statements[]       -> statements/<name>.json + .sql
    statement "reference"      -> references/<name>.py    build(), compare()
    config "dataset"           -> datasets/<name>.py      generate(), load()
    BENCHMARK.json per_layer[] -> layer_metrics/<name>.json | .py

so a later PR adds a cell, a configuration, a traffic mix or a per-layer
metric with new files and new entries and edits nothing that is here.

A run: make the rows from --seed (or reuse this checkout's copy of
them), load them through the program's ingest path, compute the plain
numpy reference, answer the cell's statements until no execution
compiles or retries any more, reopen the data directory and time the
first answer, warm up, then drive the traffic for --seconds and compare
EVERY answer of the window with the reference.  Every line printed
before the last says what set-up did and how long it took; the last
line is the result the driver reads.  With --trace 0 the span recorder
is left at its defaults and nothing is read from it; with --trace 1 the
per-layer metrics are read from spans, counters and a `jax.profiler`
trace of a few seconds of the window.

No accelerator, or fewer chips than the cell asks for: a non-zero exit
and no result line.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, to within the interpreter's own

import argparse
import importlib
import json
import os
import shutil
import sys
import threading

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# the two values selftest.py overrides to rehearse the harness on the
# CPU: the platform JAX must report, and where a run keeps its data
REQUIRED_PLATFORM = "tpu"
DATA_ROOT = os.path.join(ROOT, ".benchdata", "benchmark")
MAX_WARMUP_EXECUTIONS = 10
# seed directories kept for one configuration (0.3 GB each at SF1): the
# least recently used go, and are made again from JAX's compile cache
KEEP_SEEDS = 8

if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.xtrace import STATEMENT_ANNOTATION  # noqa: E402


def emit(obj: dict) -> None:
    print(json.dumps(obj, default=str), flush=True)


def read_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def plugin(kind: str, name: str):
    """The module benchmark/<kind>/<name>.py."""
    return importlib.import_module(f"benchmark.{kind}.{name}")


# ---------------------------------------------------------------------------
# the cell, from data

class Cell:
    def __init__(self, workload: str):
        self.bench = read_json(ROOT, "BENCHMARK.json")
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                             f"(have {sorted(cells)})")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        cfg_entry = next(c for c in self.bench["configs"]
                         if c["name"] == self.entry["config"])
        self.config = read_json(ROOT, cfg_entry["file"])
        self.traffic = read_json(BENCH_DIR, "traffic",
                                 self.entry["traffic"] + ".json")
        self.statements = []
        for item in self.traffic["statements"]:
            st = read_json(BENCH_DIR, "statements",
                           item["statement"] + ".json")
            with open(os.path.join(BENCH_DIR, "statements", st["sql"])) as f:
                st["text"] = f.read()
            st["weight"] = int(item.get("weight", 1))
            st["ref_module"] = plugin("references", st["reference"])
            self.statements.append(st)
        self.dataset = plugin("datasets", self.config["dataset"])

    def metrics(self, group: str) -> list[dict]:
        return [m for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def reports(self, metric: str) -> bool:
        return any(m["name"] == metric for m in self.metrics("end_to_end"))


# ---------------------------------------------------------------------------
# compile watch: every XLA compile (or persistent-cache load) of the
# process, counted by JAX itself — a statement's own flag costs nothing

class CompileWatch:
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


def program_state(counters: list[dict], ec, watch: CompileWatch) -> dict:
    """Everything that moves when an execution compiled, loaded a
    program or retried with other capacities; `counters` are the
    sessions' counter snapshots."""
    def total(name: str) -> int:
        return sum(c[name] for c in counters)

    return {"xla_compiles": watch.count,
            "exec_cache_compiles": ec.snapshot()["compiles_total"],
            "plan_cache_misses": total("exec_cache_hits_total")
            + total("exec_cache_misses_total")
            + total("exec_cache_rejects_total"),
            "capacity_retries": total("capacity_retries")}


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


# ---------------------------------------------------------------------------
# spans: one statement's tree (stats/tracing.py) flattened to ms by name

def spans_ms(doc: dict | None) -> dict[str, float] | None:
    if doc is None:
        return None
    out: dict[str, float] = {}

    def walk(span):
        out[span["name"]] = out.get(span["name"], 0.0) + span["dur_ms"]
        for c in span.get("children", ()):
            walk(c)

    walk(doc["root"])
    return out


# ---------------------------------------------------------------------------
# data and reference: made from the seed, kept in the checkout

class SeedData:
    """<DATA_ROOT>/<config>/seed<n>/: the data directory (with its
    exec_cache/), the reference answers, and a manifest that says what
    they were made from."""

    def __init__(self, cell: Cell, seed: int):
        self.cell, self.seed = cell, seed
        self.dir = os.path.join(DATA_ROOT, cell.config["name"],
                                f"seed{seed}")
        self.data_dir = os.path.join(self.dir, "data")
        self.manifest_path = os.path.join(self.dir, "manifest.json")
        self.params = cell.config["dataset_params"]
        self.want = {"config": cell.config["name"], "seed": seed,
                     "dataset": cell.config["dataset"],
                     "dataset_params": self.params,
                     "generator_version": cell.dataset.GENERATOR_VERSION}
        self.rows: dict[str, int] = {}
        self.refs: list[dict] = []
        self._tables = None
        self.seconds = {"generate": 0.0, "load": 0.0, "reference": 0.0}

    def tables(self) -> dict:
        if self._tables is None:
            t0 = time.perf_counter()
            self._tables = self.cell.dataset.generate(self.params, self.seed)
            self.seconds["generate"] += time.perf_counter() - t0
        return self._tables

    def ref_path(self, st: dict) -> str:
        return os.path.join(self.dir, "reference", st["reference"] + ".npz")

    def prepare(self, connect) -> dict:
        """Leaves a loaded data directory and every statement's
        reference; returns what was reused."""
        manifest = None
        if os.path.exists(self.manifest_path):
            manifest = read_json(self.manifest_path)
            if {k: manifest.get(k) for k in self.want} != self.want:
                manifest = None
        if manifest is None and os.path.isdir(self.dir):
            shutil.rmtree(self.dir)  # half-made, or made from other rows
        if manifest is None:
            self.evict()
        else:
            os.utime(self.manifest_path)
        reused = {"data": manifest is not None, "reference": []}
        if manifest is None:
            os.makedirs(self.dir)
            t0 = time.perf_counter()
            sess = connect(self.data_dir)
            try:
                data = self.tables()
                t0 += self.seconds["generate"]
                loaded = self.cell.dataset.load(sess, data, self.params)
                self.rows = self.cell.dataset.row_counts(data)
                if loaded != self.rows:
                    raise RuntimeError(
                        f"ingest reported {loaded}, generated {self.rows}")
            finally:
                sess.close()
            self.seconds["load"] = time.perf_counter() - t0
        else:
            self.rows = manifest["rows"]
        for st in self.cell.statements:
            path = self.ref_path(st)
            if os.path.exists(path):
                reused["reference"].append(st["name"])
            else:
                tables = self.tables()
                t0 = time.perf_counter()
                os.makedirs(os.path.dirname(path), exist_ok=True)
                np.savez(path + ".tmp.npz", **st["ref_module"].build(tables))
                os.replace(path + ".tmp.npz", path)
                self.seconds["reference"] += time.perf_counter() - t0
            with np.load(path) as z:
                self.refs.append({k: z[k] for k in z.files})
        self._tables = None
        if manifest is None:
            # the commit point: a directory without it is made again
            with open(self.manifest_path + ".tmp", "w") as f:
                json.dump({**self.want, "rows": self.rows}, f, indent=1)
            os.replace(self.manifest_path + ".tmp", self.manifest_path)
        return reused

    def evict(self) -> None:
        """Room for one more seed: all but the KEEP_SEEDS - 1 most
        recently used directories of this configuration go."""
        parent = os.path.dirname(self.dir)
        if not os.path.isdir(parent):
            return

        def used(d: str) -> float:
            m = os.path.join(parent, d, "manifest.json")
            return os.path.getmtime(m) if os.path.exists(m) else 0.0

        for d in sorted(os.listdir(parent), key=used)[:-(KEEP_SEEDS - 1)]:
            shutil.rmtree(os.path.join(parent, d))

    def check_store(self, sess) -> None:
        have = self.cell.dataset.stored_row_counts(sess, list(self.rows))
        if have != self.rows:
            raise RuntimeError(
                f"{self.data_dir} holds {have}, the manifest says "
                f"{self.rows}: remove {self.dir}")


# ---------------------------------------------------------------------------
# one client: a session and the statements it sends

def TraceAnnotation(name: str):
    import jax.profiler

    return jax.profiler.TraceAnnotation(name)


def clamp_sql(sql: str) -> str:
    from citus_tpu.stats import tracing

    return tracing.clamp_sql(sql)


class Client:
    def __init__(self, run: "Run", sess, index: int):
        self.run, self.sess, self.index = run, sess, index
        self.records: list[dict] = []

    def execute(self, st_idx: int, due: float | None = None) -> dict:
        """One statement, timed from the client's side: the clock stops
        when the rows are on the host.  An exception is a failed
        statement, not the end of the run."""
        run = self.run
        sql = run.cell.statements[st_idx]["text"]
        compiles0 = run.watch.count
        rec = {"stmt": st_idx, "rows": None, "error": None}
        annotate = run.profiling
        if annotate:
            ann = TraceAnnotation(STATEMENT_ANNOTATION)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            rec["rows"] = self.sess.execute(sql).rows()
        except Exception as e:  # counted in `failed`, reported on a line
            rec["error"] = repr(e)
        t1 = time.perf_counter()
        if annotate:
            ann.__exit__(None, None, None)
        rec["t0"], rec["t1"] = (t0 if due is None else due), t1
        rec["late_s"] = 0.0 if due is None else max(0.0, t0 - due)
        rec["compiled"] = run.watch.count != compiles0
        if run.trace:
            doc = self.sess.stats.tracing.last_trace()
            rec["spans"] = spans_ms(doc) if doc is not None and \
                doc.get("sql") == clamp_sql(sql) else None
        self.records.append(rec)
        return rec


class Run:
    """State of one run; what a per-layer metric's reader is given."""

    def __init__(self, cell: Cell, args, device: dict, peaks: dict):
        self.cell, self.traffic, self.seed = cell, cell.traffic, args.seed
        self.seconds, self.trace = float(args.seconds), bool(args.trace)
        self.device, self.peaks = device, peaks
        self.trace_dir = os.path.join(DATA_ROOT, "traces", cell.name)
        self.watch = CompileWatch()
        self.profiling = False  # set while the profiler records
        self.data = SeedData(cell, args.seed)
        self.first_answer: dict | None = None
        self.window: dict = {}
        self.records: list[dict] = []
        self.device_trace: dict | None = None

    # -- sessions ----------------------------------------------------------
    def connect(self, data_dir: str):
        import citus_tpu

        settings = dict(self.cell.config.get("session_settings", {}))
        if self.trace:
            # a setting, not a change to the program: no statement's
            # span tree is sampled out of the traced run
            settings["trace_fast_statement_ms"] = 0
        return citus_tpu.connect(data_dir=data_dir,
                                 n_devices=self.cell.config["n_devices"],
                                 **settings)

    def check(self, st_idx: int, rows) -> list[str]:
        st = self.cell.statements[st_idx]
        tol = st["ref_module"].tolerance(self.data.rows)
        bad, _err = st["ref_module"].compare(rows, self.data.refs[st_idx],
                                             tol)
        return bad

    def until_stable(self, client: Client, ec, what: str) -> list[dict]:
        """Execute every statement of the cell until two successive
        executions of it compile nothing, load nothing and retry
        nothing; each answer is held to the reference.  Returns the
        executions' records."""
        for st_idx, st in enumerate(self.cell.statements):
            quiet = 0
            for _ in range(MAX_WARMUP_EXECUTIONS):
                counters = client.sess.stats.counters
                before = program_state([counters.snapshot()], ec, self.watch)
                rec = client.execute(st_idx)
                moved = delta(program_state([counters.snapshot()], ec,
                                            self.watch), before)
                if rec["error"]:
                    raise RuntimeError(f"{what}: {st['name']}: "
                                       f"{rec['error']}")
                bad = self.check(st_idx, rec["rows"])
                if bad:
                    raise RuntimeError(f"{what}: {st['name']} is wrong "
                                       f"before the window: {bad[:4]}")
                quiet = 0 if any(moved.values()) else quiet + 1
                if quiet == 2:
                    break
            else:
                raise RuntimeError(
                    f"{what}: {st['name']} still compiles or retries "
                    f"after {MAX_WARMUP_EXECUTIONS} executions: {moved}")
        done, client.records = client.records, []
        return done


# ---------------------------------------------------------------------------
# the general load generator: a closed loop of N clients, or an open loop
# at a fixed rate.  Every seed gives the same statements and arrivals in
# another order.

def statement_order(cell: Cell, seed: int, client: int) -> list[int]:
    base = [i for i, st in enumerate(cell.statements)
            for _ in range(st["weight"])]
    rng = np.random.default_rng([0x5EED, client, int(seed)])
    return [base[i] for i in rng.permutation(len(base))]


def arrivals(traffic: dict, seed: int, seconds: float) -> np.ndarray:
    """Due times of an open loop: exponential gaps at `rate_per_s`, the
    gaps drawn once (`arrival_seed`) and permuted by the seed."""
    rate = float(traffic["rate_per_s"])
    n = max(1, int(rate * seconds))
    gaps = np.random.default_rng(
        [0xA771, int(traffic.get("arrival_seed", 0))]).exponential(
            1.0 / rate, n)
    gaps *= seconds / gaps.sum() * (n / (n + 1.0))
    perm = np.random.default_rng([0xA772, int(seed)]).permutation(n)
    return np.cumsum(gaps[perm])


def drive(run: Run, clients: list[Client]) -> None:
    """The measured window.  Closed loop: each client sends its next
    statement when the last has answered, and starts none after
    `seconds`.  Open loop: statements are due on a schedule whatever the
    system does, and a statement's latency counts from when it was due.
    With --trace 1 a few seconds in the middle are profiled."""
    cell, traffic, seconds = run.cell, run.traffic, run.seconds
    prof_s = min(float(traffic.get("profile_seconds", 4)), 0.5 * seconds)
    prof = {"state": "off" if not run.trace else "armed"}
    lock = threading.Lock()
    open_loop = traffic["loop"] == "open"
    due = arrivals(traffic, run.seed, seconds) if open_loop else None
    nxt = [0]
    t_start = time.perf_counter()

    def stop_profile(now: float) -> None:
        import jax.profiler

        prof.update(state="done", t1=now)
        run.profiling = False
        jax.profiler.stop_trace()

    def maybe_profile(now: float) -> None:
        # client 0 alone starts and stops the profiler
        if prof["state"] == "armed" and now - t_start >= 0.25 * seconds:
            import jax.profiler

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            shutil.rmtree(run.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(run.trace_dir, profiler_options=opts)
            run.profiling = True
            prof.update(state="on", t0=time.perf_counter())
        elif prof["state"] == "on" and now - prof["t0"] >= prof_s:
            stop_profile(now)

    def client_loop(client: Client) -> None:
        order = statement_order(cell, run.seed, client.index)
        k = 0
        while True:
            now = time.perf_counter()
            if client.index == 0:
                maybe_profile(now)
                now = time.perf_counter()
            if open_loop:
                with lock:
                    i = nxt[0]
                    nxt[0] += 1
                if i >= len(due):
                    return
                wait = t_start + due[i] - now
                if wait > 0:
                    time.sleep(wait)
                client.execute(order[i % len(order)], due=t_start + due[i])
            else:
                if now - t_start >= seconds:
                    return
                client.execute(order[k % len(order)])
                k += 1

    threads = [threading.Thread(target=client_loop, args=(c,),
                                name=f"bench-client-{c.index}")
               for c in clients[1:]]
    for t in threads:
        t.start()
    client_loop(clients[0])
    for t in threads:
        t.join()
    if prof["state"] == "on":  # the window ended inside the profile
        stop_profile(time.perf_counter())
    records = sorted((r for c in clients for r in c.records),
                     key=lambda r: r["t1"])
    run.records = records
    run.window = {"t_start": t_start,
                  "seconds": (records[-1]["t1"] - t_start) if records
                  else 0.0,
                  "profile": ({"t0": prof["t0"], "t1": prof["t1"]}
                              if prof["state"] == "done" else None)}


# ---------------------------------------------------------------------------
# metrics

def end_to_end(run: Run) -> dict[str, float]:
    """Every end-to-end metric the harness can take; the cell's entries
    in BENCHMARK.json say which it reports.  All from the host clock at
    the client's side."""
    ok = [r for r in run.records if r["ok"]]
    lat = np.array([r["t1"] - r["t0"] for r in ok]) * 1e3
    out = {"setup_s": run.window["t_start"] - _T0}
    if len(ok):
        out["stmts_per_s"] = len(ok) / run.window["seconds"]
        out["latency_p50_ms"] = float(np.percentile(lat, 50))
        out["latency_p95_ms"] = float(np.percentile(lat, 95))
    if run.first_answer is not None:
        out["first_answer_s"] = run.first_answer["seconds"]
    return out


def layer_metric(run: Run, name: str):
    """benchmark/layer_metrics/<name>.py `read(run)`, or <name>.json
    reduced by `layer_metrics/reduce.py`.  None: nothing to read."""
    base = os.path.join(BENCH_DIR, "layer_metrics", name)
    if os.path.exists(base + ".py"):
        return plugin("layer_metrics", name).read(run)
    from benchmark.layer_metrics import reduce

    return reduce.read(run, read_json(base + ".json"))


def memory_peak_bytes(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


# ---------------------------------------------------------------------------

def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = Cell(args.workload)

    # before anything else: no accelerator, no run and no result line
    import jax

    devs = jax.devices()
    if devs[0].platform != REQUIRED_PLATFORM or len(devs) < cell.chips:
        print(f"benchmark: JAX found {len(devs)} device(s) of platform "
              f"{devs[0].platform!r}; {cell.name} needs {cell.chips} of "
              f"{REQUIRED_PLATFORM!r}: nothing was run", file=sys.stderr)
        return 2
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    peaks = read_json(BENCH_DIR, "peaks.json")
    if device["platform"] == "tpu" and device["kind"] not in peaks:
        print(f"benchmark: no peaks for device kind {device['kind']!r} in "
              "benchmark/peaks.json", file=sys.stderr)
        return 2
    try:
        return measure(Run(cell, args, device, peaks))
    except Exception:
        # the one handler: it ends the run without a result line
        import traceback

        traceback.print_exc()
        return 1


def measure(run: Run) -> int:
    from citus_tpu.executor.execcache import exec_cache_for

    cell, data, device = run.cell, run.data, run.device
    head = {**device, "seed": run.seed, "workload": cell.name}
    emit({"phase": "start", **head, "chips_used": cell.config["n_devices"],
          "seconds": run.seconds, "trace": int(run.trace),
          "import_s": time.perf_counter() - _T0,
          "JAX_COMPILATION_CACHE_DIR":
              os.environ.get("JAX_COMPILATION_CACHE_DIR")})

    # -- rows and reference, from the seed ---------------------------------
    reused = data.prepare(run.connect)
    emit({"phase": "data", **head, "reused_data": reused["data"],
          "reused_reference": reused["reference"], "rows": data.rows,
          "dir": os.path.relpath(data.dir, ROOT),
          "generate_s": data.seconds["generate"],
          "load_s": data.seconds["load"],
          "reference_s": data.seconds["reference"]})

    ec = exec_cache_for(data.data_dir)
    # -- the first session: until the programs are at their fixed point ----
    t0 = time.perf_counter()
    sess = run.connect(data.data_dir)
    clients: list[Client] = []
    try:
        data.check_store(sess)
        cache0, xla0 = ec.snapshot(), (run.watch.count, run.watch.seconds)
        client = Client(run, sess, 0)
        done = run.until_stable(client, ec, "first statement")
        cache1 = ec.snapshot()
        first_s = time.perf_counter() - t0
        # `first_execution_s`: connect() to the first answer on this
        # process's first session — with `reused_exec_cache`, the same
        # stretch as first_answer_s, in a process that is not warm yet
        emit({"phase": "first_statement", **head, "executions": len(done),
              "seconds": first_s, "first_execution_s": done[0]["t1"] - t0,
              "reused_exec_cache": cache1["compiles_total"]
              == cache0["compiles_total"],
              "exec_cache": delta(
                  {k: cache1[k] for k in ("hits_total", "compiles_total")},
                  cache0),
              "xla_compiles": run.watch.count - xla0[0],
              "xla_compile_s": run.watch.seconds - xla0[1]})
        if cell.reports("first_answer_s"):
            # a restart: close, reopen the data directory, first answer
            sess.close()
            sess = None
            sess, run.first_answer = reopen(run, data, ec)
            emit({"phase": "reopen", **head, **{
                k: run.first_answer[k] for k in
                ("seconds", "exec_cache", "spans_ms", "scan")}})
            client = Client(run, sess, 0)
        clients.append(client)
        for i in range(1, int(cell.traffic.get("clients", 1))):
            clients.append(Client(run, run.connect(data.data_dir), i))
        t0 = time.perf_counter()
        n = sum(len(run.until_stable(c, ec, "warm-up")) for c in clients)
        emit({"phase": "warm_up", **head, "executions": n,
              "seconds": time.perf_counter() - t0})

        # -- the window -----------------------------------------------------
        counters0 = [c.sess.stats.counters.snapshot() for c in clients]
        before = program_state(counters0, ec, run.watch)
        drive(run, clients)
        counters1 = [c.sess.stats.counters.snapshot() for c in clients]
        run.window["programs"] = delta(
            program_state(counters1, ec, run.watch), before)
        run.window["counters"] = {
            k: sum(c1[k] - c0[k] for c0, c1 in zip(counters0, counters1))
            for k in counters0[0]}
        peak = memory_peak_bytes(clients[0].sess.mesh.devices.flat)
    finally:
        for s in [c.sess for c in clients] or [sess]:
            if s is not None:
                s.close()

    correct, failed = judge(run, head)

    # -- the result line -------------------------------------------------------
    result = {"correct": correct, "attempted": len(run.records),
              "failed": failed, "metrics": {},
              "device": {**device, "memory_peak_bytes": peak}}
    if run.trace:
        reduce_profile(run, result)
        wanted, values = cell.metrics("per_layer"), None
    else:
        wanted, values = cell.metrics("end_to_end"), end_to_end(run)
    for m in wanted:
        v = values.get(m["name"]) if values is not None \
            else layer_metric(run, m["name"])
        if v is not None:
            result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    emit(result)
    return 0 if correct and failed == 0 else 1


def judge(run: Run, head: dict) -> tuple[bool, int]:
    """Every answer of the window against the reference, after the
    window: (correct, failed)."""
    from citus_tpu.stats import tracing

    verdicts: dict[tuple, list] = {}
    for r in run.records:
        if r["error"] is not None:
            r["bad"] = [r["error"]]
        else:
            # one comparison for each distinct answer
            key = (r["stmt"], repr(r["rows"]))
            if key not in verdicts:
                verdicts[key] = run.check(r["stmt"], r["rows"])
            r["bad"] = verdicts[key]
        r["ok"] = not r["bad"] and not r["compiled"]
    errors = sum(1 for r in run.records if r["error"] is not None)
    wrong = sum(1 for r in run.records if r["bad"]) - errors
    compiled = sum(1 for r in run.records if r["compiled"])
    # a program resolved inside the window that no statement's own flag
    # caught (a load from the executable cache) still fails the window
    unflagged = max(0, max(run.window["programs"].values()) - compiled)
    failed = sum(1 for r in run.records if not r["ok"]) + unflagged
    open_spans = tracing.open_span_count()
    correct = wrong == 0 and errors == 0 and open_spans == 0 \
        and len(run.records) > 0
    emit({"phase": "window", **head, "seconds": run.window["seconds"],
          "attempted": len(run.records), "wrong": wrong, "errors": errors,
          "compiled_in_window": compiled, "programs": run.window["programs"],
          "distinct_answers": len(verdicts), "open_spans": open_spans,
          "mismatches": [r["bad"][0] for r in run.records if r["bad"]][:8],
          "generator_late_s_max": max((r["late_s"] for r in run.records),
                                      default=0.0),
          "rows_per_stmt": {
              st["name"]: sum(run.data.rows[t] for t in st["reads"])
              for st in run.cell.statements}})
    return correct, failed


def reopen(run: Run, data: SeedData, ec):
    """`connect()` on the data directory to the first correct answer of
    the cell's first statement, with the statement's program in the
    executable cache: the clock starts before connect()."""
    st = run.cell.statements[0]
    before = ec.snapshot()
    t0 = time.perf_counter()
    sess = run.connect(data.data_dir)
    try:
        sess.executor.scan_stats.reset()
        rows = sess.execute(st["text"]).rows()
        seconds = time.perf_counter() - t0
        after = ec.snapshot()
        moved = {k: after[k] - before[k] for k in
                 ("hits_total", "misses_total", "rejects_total",
                  "compiles_total")}
        bad = run.check(0, rows)
        if moved["hits_total"] < 1 or moved["compiles_total"] \
                or moved["rejects_total"]:
            bad.append("the reopened session was not served from the "
                       f"executable cache: {moved}")
        if bad:
            raise RuntimeError(f"first answer: {bad[:4]}")
        scan = sess.executor.scan_stats.snapshot()
        return sess, {
            "seconds": seconds, "exec_cache": moved,
            "spans_ms": spans_ms(sess.stats.tracing.last_trace()),
            "scan": {k: scan[k] for k in
                     ("feeds_pipelined", "bytes_on_wire", "bytes_decoded")}}
    except BaseException:
        sess.close()
        raise


def reduce_profile(run: Run, result: dict) -> None:
    """The traced run's device numbers: `device.busy_s`/`window_s` and
    `breakdown`, from the profiler's trace of the profiled stretch."""
    from benchmark import xtrace

    prof = run.window["profile"]
    if prof is None:
        raise RuntimeError("--trace 1, and no stretch of the window was "
                           "profiled")
    n = sum(1 for r in run.records if prof["t0"] <= r["t1"] <= prof["t1"])
    path = xtrace.newest_xplane(run.trace_dir)
    dt = None if path is None else xtrace.reduce_trace(
        path, prof["t1"] - prof["t0"], n,
        run.cell.config["n_devices"] if run.device["platform"] == "tpu"
        else None)
    if dt is None and run.device["platform"] == "tpu":
        # never quietly: a traced run on the chip without device numbers
        # ends without a result line
        raise RuntimeError(
            f"--trace 1 on the chip, and {path or run.trace_dir} holds no "
            f"operation on a '{xtrace.OPS_LINE}' line of a device plane")
    run.device_trace = dt
    spans = [r["spans"] for r in run.records if r.get("spans")]
    emit({"phase": "profile", **run.device, "seed": run.seed,
          "workload": run.cell.name, "statements_profiled": n,
          "xplane": path and os.path.relpath(path, ROOT), "reduced": dt,
          "fetch_ms_median": float(np.median(
              [s.get("mesh.dispatch", 0.0) + s.get("mesh.fetch", 0.0)
               for s in spans])) if spans else None,
          "statements_with_spans": len(spans)})
    if dt is not None:
        result["device"]["busy_s"] = dt["busy_s"]
        result["device"]["window_s"] = dt["window_s"]
        result["breakdown"] = {"device_ops": dt["device_ops"],
                               "idle_gaps": dt["idle_gaps"]}


if __name__ == "__main__":
    sys.exit(main())
