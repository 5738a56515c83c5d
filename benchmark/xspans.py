"""The names the program writes into a `jax.profiler` trace, read back:
device time by stage of the device program, and the device's idle time
by the host span that was open while it waited.

The program (citus_tpu/stats/tracing.py) puts two kinds of names on the
profiler's clock.  `stage_scope("x")` is `jax.named_scope("ct.x")`: every
operation traced inside carries `…/ct.x/…` in its `op_name` path, and a
sub-scope (`ct.pack` inside `ct.repartition`) follows its stage in that
path.  `trace_span("y")` enters `jax.profiler.TraceAnnotation("ct:y")`:
the statement's span tree appears as nested events on the line of the
thread that ran it, the root `ct:statement` with a `stmt` number, and
the spans of a producer thread on that thread's line with the same
`stmt`.  A program that writes neither (any commit before PR 27) gives a
trace in which nothing starts with `ct`: the `idle_*` readers then return
None and the result line leaves them out, and the `stage_*` readers put
all of the operations' time under `stage_unscoped_ms`, which is what it
is.  The same happens to a program that does write scopes but was handed
an executable compiled by one that does not: JAX's persistent cache keys
a program without its locations, and the scopes are locations.

What is reduced, from the newest `.xplane.pb` of the traced run (found as
run.py finds it), over the busiest device (xtrace's choice: the largest
union of operation intervals):

(i)   Operations and their stage.  An `XLA Ops` event names its HLO
      instruction; its op_name path is the `tf_op` stat
      (`jit(packed_fn)/ct.bucket_probe/ct.pack/gather:`).  The chip's
      trace keeps that stat on the event's METADATA, which
      `jax.profiler.ProfileData` does not show (seen in PR 27's chip
      runs: an event's own stats are `device_offset_ps`,
      `device_duration_ps`, `Time Scale Multiplier`), so
      `metadata_paths()` reads just that table from the file's bytes
      and events join it by plane and name; a fixture carries the path
      on the event.  An operation belongs to the innermost stage of its path:
      the last `ct.<name>` component that is a stage (`STAGES`), with
      the `ct.<name>` after it, if any, as sub-scope; a fusion carries
      the path XLA gave the fusion instruction, which is its root's.
      Times are self times (an operation's duration minus that of the
      operations nested in it, as xtrace counts them), so the stages
      and "(unscoped)" add up to the operations' total.
(ii)  The `ct:` events of every host line, rebuilt into per-statement
      trees: on a line, containment in time is the parent link; a span
      that has no `ct:statement` around it on its own line joins the
      statement whose `stmt` it carries.
(iii) The idle gaps between the busiest device's operations, each split
      among the innermost `ct:` spans open on the statement's own thread
      at each instant.  A gap that straddles two spans is cut at the
      boundary; what lies inside no `ct:statement` is "(outside)" — the
      harness's loop and `.rows()`.  With several clients an instant
      covered by k statements gives each 1/k.

Before the result line two lines are printed, once a run: `{"phase":
"stages"}` (ms a statement by stage and by stage/sub-scope, the ten
longest operations as `stage/sub · label`) and `{"phase": "host_gaps"}`
(idle ms a statement by innermost span and by metric, the ten longest
gaps with their spans, and the part outside every statement).  The ten
`layer_metrics/stage_*.py` and `idle_*.py` read the same reduction,
which is kept on `run` so that the trace is parsed once.

`selftest_spans.py` holds this file to `fixtures/spans_hand_built.xspace.txt`
and to a cut of a chip trace; `--fixture` below writes such a cut.
"""

from __future__ import annotations

import bisect
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import xtrace  # noqa: E402

SPAN_PREFIX = "ct:"
UNSCOPED = "(unscoped)"
OUTSIDE = "(outside)"
# the stages a device program is made of (citus_tpu/stats/tracing.py
# STAGE_NAMES without its sub-scopes); the yardstick's own copy, so that
# a renamed stage shows as unscoped time and not as a moved metric
STAGES = frozenset((
    "feed_unpack", "decode", "scan_out", "repartition", "bucket_probe",
    "lookup_join", "join_out", "agg_grid", "agg_bucket", "agg_sort",
    "agg_out", "agg_global", "topk", "window", "project", "output_pack"))
# idle_<metric>_ms: the spans whose idle time it sums.  A span below one
# of these (scan.* under feed, compile.cache_load under compile) counts
# with it; `statement` and `execute` themselves are the time no span of
# the program names.  `gate`, `route`, `caps` and `settle` are the spans
# PR 27 added for what its first traced runs showed under `execute`
IDLE_METRICS = {
    "plan": ("parse", "queue", "gate", "plan"),
    "dispatch": ("route", "feed", "caps", "compile", "mesh.dispatch"),
    "fetch": ("mesh.fetch", "settle"),
    "combine": ("combine",),
    "unspanned": ("statement", "execute"),
}
_CT_SCOPE = re.compile(r"(?:^|/)ct\.([A-Za-z0-9_]+)")
PATH_STAT = "tf_op"


# ---------------------------------------------------------------------------
# the op_name paths on event metadata: XSpace's wire format, as far as
# needed (tsl/profiler/protobuf/xplane.proto: XSpace.planes = 1;
# XPlane.name = 2, event_metadata = 4, stat_metadata = 5, both maps of
# key = 1, value = 2; XEventMetadata.name = 2, stats = 5;
# XStatMetadata.id = 1, name = 2; XStat.metadata_id = 1, str_value = 5,
# ref_value = 7, a stat_metadata id whose name is the string)

def _fields(buf: bytes):
    """(field number, wire type, value) of one message: an int for a
    varint, the bytes for a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 1:
            val, i = buf[i:i + 8], i + 8
        elif wire == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield key >> 3, wire, val


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def metadata_paths(path: str) -> dict[str, dict[str, str]]:
    """{plane name: {event name: op_name path}} for the events whose
    metadata holds a `tf_op` stat, from an `.xplane.pb` file."""
    with open(path, "rb") as f:
        space = f.read()
    out: dict[str, dict[str, str]] = {}
    for field, wire, plane in _fields(space):
        if field != 1 or wire != 2:
            continue
        name, stat_names, metas = "", {}, []
        for pf, pw, val in _fields(plane):
            if pf == 2 and pw == 2:
                name = val.decode("utf-8", "replace")
            elif pf in (4, 5) and pw == 2:
                entry = next((v for f2, w2, v in _fields(val)
                              if f2 == 2 and w2 == 2), b"")
                if pf == 4:
                    metas.append(entry)
                else:
                    sm = {f3: v for f3, _w, v in _fields(entry)}
                    stat_names[sm.get(1, 0)] = sm.get(2, b"").decode(
                        "utf-8", "replace")
        if xtrace.DEVICE_PLANE.match(name) is None:
            continue
        paths = out.setdefault(name, {})
        for meta in metas:
            ev_name, found = "", None
            for mf, mw, val in _fields(meta):
                if mf == 2 and mw == 2:
                    ev_name = val.decode("utf-8", "replace")
                elif mf == 5 and mw == 2:
                    st = {f4: v for f4, _w, v in _fields(val)}
                    if stat_names.get(st.get(1)) != PATH_STAT:
                        continue
                    found = (st[5].decode("utf-8", "replace") if 5 in st
                             else stat_names.get(st.get(7)))
            if found:
                paths[ev_name] = found
    return out


def stage_of(path: str | None) -> tuple[str, str | None]:
    """(stage, sub-scope) of an op_name path: the innermost stage and
    the `ct.` component after it.  ("(unscoped)", None) without one; a
    path whose `ct.` components name no stage (a kernel run outside the
    compiler) is filed under its first component."""
    if not path:
        return UNSCOPED, None
    comps = _CT_SCOPE.findall(path)
    if not comps:
        return UNSCOPED, None
    at = max((i for i, c in enumerate(comps) if c in STAGES), default=0)
    return comps[at], (comps[at + 1] if at + 1 < len(comps) else None)


# ---------------------------------------------------------------------------
# reading the trace

def read_events(path_or_data) -> dict:
    """{"devices": {index: [(start_ns, end_ns, name, path)]} from each
    device plane's `XLA Ops` line, "host": {(plane, line id, line name):
    [(start_ns, end_ns, span name, stmt | None)]} from the `ct:` events
    of every host line}."""
    from jax.profiler import ProfileData

    on_metadata: dict[str, dict[str, str]] = {}
    if isinstance(path_or_data, str):
        on_metadata = metadata_paths(path_or_data)
        data = ProfileData.from_file(path_or_data)
    else:
        data = path_or_data
    devices: dict[int, list] = {}
    host: dict[tuple, list] = {}
    for plane in data.planes:
        dev = xtrace.DEVICE_PLANE.match(plane.name)
        if dev is None and not plane.name.startswith("/host:"):
            continue
        for n_line, line in enumerate(plane.lines):
            if dev is not None:
                if line.name != xtrace.OPS_LINE:
                    continue
                out = devices.setdefault(int(dev.group(2)), [])
                paths = dict(on_metadata.get(plane.name, {}))
                for ev in line.events:
                    if ev.name not in paths:
                        paths[ev.name] = dict(ev.stats).get(PATH_STAT)
                    s = float(ev.start_ns)
                    out.append((s, s + float(ev.duration_ns), ev.name,
                                paths[ev.name]))
            else:
                spans = []
                for ev in line.events:
                    if not ev.name.startswith(SPAN_PREFIX):
                        continue
                    stmt = dict(ev.stats).get("stmt")
                    s = float(ev.start_ns)
                    spans.append((s, s + float(ev.duration_ns),
                                  ev.name[len(SPAN_PREFIX):],
                                  None if stmt is None else int(stmt)))
                if spans:
                    host[(plane.name, n_line, line.name)] = sorted(
                        spans, key=lambda x: (x[0], -x[1]))
    return {"devices": devices, "host": host}


# ---------------------------------------------------------------------------
# (ii) per-statement trees

def build_trees(host: dict) -> dict:
    """{"statements": {stmt: tree}, "orphans": n}: a tree is {"name",
    "t0", "t1", "line", "line_no", "stmt", "children": [...]}; a
    producer thread's spans hang under the statement's root with their
    own line."""
    statements: dict[int, dict] = {}
    loose: list[dict] = []
    for key, spans in host.items():
        stack: list[dict] = []
        for s, e, name, stmt in spans:
            node = {"name": name, "t0": s, "t1": e, "line": key[2],
                    "line_no": key[1], "stmt": stmt, "children": []}
            while stack and stack[-1]["t1"] <= s:
                stack.pop()
            if stack:
                stack[-1]["children"].append(node)
            elif name == "statement" and stmt is not None:
                statements[stmt] = node
            else:
                loose.append(node)
            stack.append(node)
    orphans = 0
    for node in loose:
        root = statements.get(node["stmt"])
        if root is None:
            orphans += 1  # its statement began before the session did
        else:
            root["children"].append(node)
    return {"statements": statements, "orphans": orphans}


def _innermost_segments(spans: list) -> list[tuple[float, float, tuple]]:
    """One line's `ct:` spans cut into the stretches during which one
    span was the innermost open one: sorted, disjoint (start, end, path
    of names from the root), only inside a `ct:statement`."""
    out: list[tuple[float, float, tuple]] = []
    stack: list[tuple[float, str]] = []  # (end, name)
    cursor = 0.0

    def emit(upto: float) -> None:
        nonlocal cursor
        if stack and upto > cursor and stack[0][1] == "statement":
            out.append((cursor, upto, tuple(n for _, n in stack)))
        cursor = max(cursor, upto)

    for s, e, name, _stmt in spans:
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        emit(s)
        stack.append((e, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return out


# ---------------------------------------------------------------------------
# (iii) idle gaps by host span

def split_gaps(gaps: list[tuple[float, float]],
               lines: list[list[tuple[float, float, tuple]]]) -> tuple:
    """(ns by innermost path, [(ns, {path: ns}) per gap]): each gap's
    length divided among the segments that overlap it; a stretch covered
    by k lines' statements gives each 1/k, one covered by none goes to
    ("(outside)",)."""
    total: dict[tuple, float] = {}
    per_gap = []
    starts = [[seg[0] for seg in segs] for segs in lines]
    for a, b in gaps:
        pieces = []
        for segs, st in zip(lines, starts):
            i = max(bisect.bisect_right(st, a) - 1, 0)
            while i < len(segs) and segs[i][0] < b:
                s, e, path = segs[i]
                if e > a:
                    pieces.append((max(s, a), min(e, b), path))
                i += 1
        edges = sorted({a, b, *(p[0] for p in pieces),
                        *(p[1] for p in pieces)})
        mine: dict[tuple, float] = {}
        for x, y in zip(edges, edges[1:]):
            open_ = [p[2] for p in pieces if p[0] <= x and p[1] >= y]
            for path in open_ or [(OUTSIDE,)]:
                mine[path] = mine.get(path, 0.0) + (y - x) / max(
                    len(open_), 1)
        for path, ns in mine.items():
            total[path] = total.get(path, 0.0) + ns
        per_gap.append((b - a, mine))
    return total, per_gap


def idle_metric_of(path: tuple) -> str | None:
    """Which idle_*_ms a path's time counts in: the innermost of its
    spans that a metric names."""
    for name in reversed(path):
        for metric, names in IDLE_METRICS.items():
            if name in names:
                return metric
    return None


# ---------------------------------------------------------------------------

def reduce_spans(path_or_data, n_statements: int) -> dict | None:
    """The reduction of one profiled stretch, or None when the trace
    has no device plane (a CPU rehearsal)."""
    ev = read_events(path_or_data)
    devices = {d: e for d, e in ev["devices"].items() if e}
    if not devices:
        return None
    busy = {d: sum(e - s for s, e in xtrace._union(
        [(s, e) for s, e, _, _ in evs])) for d, evs in devices.items()}
    busiest = max(busy, key=busy.get)
    events = devices[busiest]
    n = max(int(n_statements), 1)
    per_stmt = 1e-6 / n  # ns over the stretch → ms a statement

    # (i) self time by (stage, sub, label)
    keyed = {}
    for _s, _e, name, path in events:
        if name not in keyed:
            keyed[name] = (*stage_of(path), xtrace.label(name))
    self_ns = xtrace._self_times([(s, e, name) for s, e, name, _ in events])
    by_stage: dict[str, float] = {}
    by_sub: dict[str, float] = {}
    by_op: dict[str, float] = {}
    for name, ns in self_ns.items():
        stage, sub, label = keyed[name]
        where = stage if sub is None else f"{stage}/{sub}"
        by_stage[stage] = by_stage.get(stage, 0.0) + ns
        by_sub[where] = by_sub.get(where, 0.0) + ns
        op = f"{where} · {label}"
        by_op[op] = by_op.get(op, 0.0) + ns
    scoped = any(k != UNSCOPED for k in by_stage)

    # (ii) trees, (iii) gaps
    trees = build_trees(ev["host"])
    merged = xtrace._union([(s, e) for s, e, _, _ in events])
    gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
    lines = [segs for segs in map(_innermost_segments, ev["host"].values())
             if segs]
    by_path, per_gap = split_gaps(gaps, lines)
    by_span: dict[str, float] = {}
    by_metric = dict.fromkeys(IDLE_METRICS, 0.0)
    other = 0.0
    for path, ns in by_path.items():
        by_span[path[-1]] = by_span.get(path[-1], 0.0) + ns
        metric = idle_metric_of(path)
        if metric is not None:
            by_metric[metric] += ns
        elif path != (OUTSIDE,):
            other += ns
    longest = sorted(per_gap, key=lambda g: -g[0])[:10]

    def ms(d: dict) -> dict:
        return {k: v * per_stmt for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])}

    return {
        "n_statements": n_statements,
        "busiest_device": busiest,
        "scoped": scoped,
        "spanned": bool(trees["statements"]),
        "ops_ms": sum(self_ns.values()) * per_stmt,
        "busy_ms": busy[busiest] * per_stmt,
        "stage_ms": ms(by_stage),
        "stage_sub_ms": ms(by_sub),
        "top_ops": [[k, v * per_stmt] for k, v in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:10]],
        "idle_ms": sum(b - a for a, b in gaps) * per_stmt,
        "idle_by_span_ms": ms(by_span),
        "idle_metric_ms": {k: v * per_stmt for k, v in by_metric.items()},
        "idle_other_spans_ms": other * per_stmt,
        "idle_outside_ms": by_path.get((OUTSIDE,), 0.0) * per_stmt,
        "gaps": len(gaps),
        "longest_gaps": [
            [ns / 1e6, {"/".join(p): v / 1e6 for p, v in sorted(
                parts.items(), key=lambda kv: -kv[1])}]
            for ns, parts in longest],
        "statements_seen": len(trees["statements"]),
        "spans_per_statement": (
            sum(_count(t) for t in trees["statements"].values())
            / len(trees["statements"])) if trees["statements"] else 0.0,
        "producer_lines": sorted({
            c["line"] for t in trees["statements"].values()
            for c in t["children"] if c["stmt"] is not None}),
        "orphan_spans": trees["orphans"],
    }


def _count(tree: dict) -> int:
    return 1 + sum(_count(c) for c in tree["children"])


# ---------------------------------------------------------------------------
# what the layer metrics call

def of_run(run) -> dict | None:
    """The reduction of this run's trace, made and printed once."""
    if hasattr(run, "_xspans"):
        return run._xspans
    run._xspans = None
    prof = (run.window or {}).get("profile")
    path = xtrace.newest_xplane(run.trace_dir) if prof else None
    if path is None:
        return None
    n = sum(1 for r in run.records if prof["t0"] <= r["t1"] <= prof["t1"])
    red = reduce_spans(path, n)
    if red is None:
        return None
    run._xspans = red
    head = {**run.device, "seed": run.seed, "workload": run.cell.name,
            "statements_profiled": n, "busiest_device": red["busiest_device"]}
    print(json.dumps({
        "phase": "stages", **head, "scoped": red["scoped"],
        "ops_ms_per_stmt": red["ops_ms"], "busy_ms_per_stmt": red["busy_ms"],
        "by_stage_ms": red["stage_ms"], "by_sub_scope_ms": red["stage_sub_ms"],
        "longest_ops_ms": red["top_ops"]}), flush=True)
    print(json.dumps({
        "phase": "host_gaps", **head, "spanned": red["spanned"],
        "idle_ms_per_stmt": red["idle_ms"], "gaps": red["gaps"],
        "by_span_ms": red["idle_by_span_ms"],
        "by_metric_ms": red["idle_metric_ms"],
        "other_spans_ms": red["idle_other_spans_ms"],
        "outside_statements_ms": red["idle_outside_ms"],
        "longest_gaps_ms": red["longest_gaps"],
        "statements_seen": red["statements_seen"],
        "spans_per_statement": red["spans_per_statement"],
        "producer_lines": red["producer_lines"],
        "orphan_spans": red["orphan_spans"]}), flush=True)
    return red


def stage_ms(run, *stages: str) -> float | None:
    """ms a statement of self time in the named stages on the busiest
    device (0.0 for a stage no operation carries); None without a
    device trace."""
    red = of_run(run)
    if red is None:
        return None
    return sum(red["stage_ms"].get(s, 0.0) for s in stages)


def idle_ms(run, metric: str) -> float | None:
    """idle_<metric>_ms; None where the program wrote no span into the
    trace."""
    red = of_run(run)
    if red is None or not red["spanned"]:
        return None
    return red["idle_metric_ms"][metric]


# ---------------------------------------------------------------------------
# fixtures

def to_text_proto(path_or_data, start_ms: float = 0.0,
                  keep_ms: float | None = None) -> str:
    """A cut of a trace as an XSpace text proto, for `fixtures/`: the
    device planes' `XLA Ops` events, their names cut to the label and
    their op_name path a `tf_op` stat on the event itself (a reference
    into the plane's stat names, which `ProfileData` resolves), and the
    host lines' `ct:` events with `stmt`; the operations that start in
    [start_ms, start_ms + keep_ms) after the first one, and the spans
    that overlap that stretch."""
    ev = read_events(path_or_data)
    lo = min(s for evs in ev["devices"].values() for s, _, _, _ in evs) \
        + start_ms * 1e6
    hi = float("inf") if keep_ms is None else lo + keep_ms * 1e6

    def kept(evs, overlap=False):
        return [e for e in evs
                if (e[1] > lo and e[0] < hi if overlap
                    else lo <= e[0] < hi)]

    t0 = min([lo] + [sp[0] for spans in ev["host"].values()
                     for sp in kept(spans, overlap=True)])

    out = []
    pid = 0
    for d, evs in sorted(ev["devices"].items()):
        pid += 1
        rows = [(s, e, xtrace.label(name), path or "")
                for s, e, name, path in kept(evs)]
        names = {n: i for i, n in enumerate(
            sorted({r[2] for r in rows}), 1)}
        refs = {p: i for i, p in enumerate(
            sorted({r[3] for r in rows if r[3]}), 2)}
        out.append(f'planes {{ id: {pid} name: "/device:TPU:{d}"')
        out.append(f'  stat_metadata {{ key: 1 value {{ id: 1 '
                   f'name: "{PATH_STAT}" }} }}')
        for p, i in refs.items():
            out.append(f'  stat_metadata {{ key: {i} value {{ id: {i} '
                       f'name: "{_esc(p)}" }} }}')
        for n, i in names.items():
            out.append(f'  event_metadata {{ key: {i} value {{ id: {i} '
                       f'name: "{_esc(n)}" }} }}')
        out.append(f'  lines {{ id: 1 name: "{xtrace.OPS_LINE}" '
                   'timestamp_ns: 0')
        for s, e, n, p in rows:
            stat = f" stats {{ metadata_id: 1 ref_value: {refs[p]} }}" \
                if p else ""
            out.append(f"    events {{ metadata_id: {names[n]} "
                       f"offset_ps: {int(round((s - t0) * 1000))} "
                       f"duration_ps: {int(round((e - s) * 1000))}"
                       f"{stat} }}")
        out.append("  }")
        out.append("}")
    by_plane: dict[str, list] = {}
    for (plane, _n, line), spans in ev["host"].items():
        by_plane.setdefault(plane, []).append(
            (line, kept(spans, overlap=True)))
    for plane, lines in by_plane.items():
        pid += 1
        names = sorted({sp[2] for _, spans in lines for sp in spans})
        ids = {n: i for i, n in enumerate(names, 1)}
        out.append(f'planes {{ id: {pid} name: "{plane}"')
        out.append('  stat_metadata { key: 1 value { id: 1 name: "stmt" } }')
        for n, i in ids.items():
            out.append(f'  event_metadata {{ key: {i} value {{ id: {i} '
                       f'name: "{SPAN_PREFIX}{_esc(n)}" }} }}')
        for lid, (line, spans) in enumerate(lines, 1):
            if not spans:
                continue
            out.append(f'  lines {{ id: {lid} name: "{_esc(line)}" '
                       'timestamp_ns: 0')
            for s, e, n, stmt in spans:
                stat = "" if stmt is None else \
                    f" stats {{ metadata_id: 1 int64_value: {stmt} }}"
                out.append(f"    events {{ metadata_id: {ids[n]} "
                           f"offset_ps: {int(round((s - t0) * 1000))} "
                           f"duration_ps: {int(round((e - s) * 1000))}"
                           f"{stat} }}")
            out.append("  }")
        out.append("}")
    return "\n".join(out) + "\n"


def _esc(text: str) -> str:
    return text.replace("\\", "\\\\").replace('"', '\\"')


def main(argv: list[str] | None = None) -> int:
    """`python3 benchmark/xspans.py <xplane.pb | log dir> -n <statements>`
    prints the reduction; `--fixture OUT --start-ms A --keep-ms N` writes
    a cut of the trace."""
    import argparse

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("path")
    ap.add_argument("-n", "--statements", type=int, default=1)
    ap.add_argument("--fixture")
    ap.add_argument("--start-ms", type=float, default=0.0)
    ap.add_argument("--keep-ms", type=float)
    args = ap.parse_args(argv)
    path = args.path if os.path.isfile(args.path) \
        else xtrace.newest_xplane(args.path)
    if args.fixture:
        with open(args.fixture, "w") as f:
            f.write(to_text_proto(path, args.start_ms, args.keep_ms))
        return 0
    print(json.dumps(reduce_spans(path, args.statements), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
