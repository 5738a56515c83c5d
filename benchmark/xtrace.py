"""Reduction of a `jax.profiler` trace (`.xplane.pb`) to the device
numbers the benchmark reports: busy seconds per device (the union of the
intervals in which an operation ran), self time per operation, collective
time, and the idle gaps labelled by what the harness's loop was doing.

Read with `jax.profiler.ProfileData` and nothing else.  The harness wraps
every statement of the profiled stretch in one
`jax.profiler.TraceAnnotation(STATEMENT_ANNOTATION)`; those host events
and the device's operation events are on one clock in the trace (checked
here: `clock_aligned`).  `selftest.py` checks this file against
`fixtures/`.

What a v5e trace holds (seen in PR 25's chip runs; one plane per chip,
`/device:TPU:<n>`): the line `XLA Ops` carries one event per executed HLO
instruction, named by its whole HLO text and nested where an instruction
(`while`, `conditional`, a call) runs others; `Async XLA Ops` the spans
of asynchronous copies and collectives from `-start` to `-done`, which
are not the core's time and are not counted as busy; `XLA Modules` one
event per executed program.  The harness's annotations are on the
`python3` line of the plane `/host:CPU`.
"""

from __future__ import annotations

import glob
import os
import re

STATEMENT_ANNOTATION = "bench.stmt"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
# what the chip's trace names an operation: the instruction's whole HLO
# text, `%fusion.47 = s32[8007680]{0:T(1024)} fusion(...), kind=kCustom, ...`
# (a hand-built plane may give the bare name, `fusion.47`)
_HLO = re.compile(r"^%?(?P<inst>\S+) = (?P<shape>\(.*?\)|\S+) "
                  r"(?P<opcode>[\w\-]+)\(")
# an instruction is a collective when its opcode is one of these, with
# or without the async `-start` / `-done` halves
COLLECTIVE = re.compile(
    r"^(all-to-all|all-reduce|all-gather|collective-permute|"
    r"reduce-scatter|collective-broadcast|ragged-all-to-all)"
    r"(-start|-done)?$")
_SUFFIX = re.compile(r"\.\d+$")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_KIND = re.compile(r"\bkind=(\w+)")


def newest_xplane(log_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    return found[-1] if found else None


def opcode(name: str) -> str:
    """The HLO opcode of an operation event: parsed from the HLO text,
    or, for a bare name, the name without its number
    (`all-to-all.3` → `all-to-all`)."""
    m = _HLO.match(name)
    if m:
        return m.group("opcode")
    return _SUFFIX.sub("", name.lstrip("%").split(" ")[0])


def label(name: str) -> str:
    """A short name for `breakdown`: instruction, opcode and result
    shape without layouts, `fusion.47 fusion:kCustom s32[8007680]`."""
    m = _HLO.match(name)
    if not m:
        return name.lstrip("%")
    shape = _LAYOUT.sub("", m.group("shape"))
    kind = _KIND.search(name)
    op = m.group("opcode") + (":" + kind.group(1) if kind else "")
    return f"{m.group('inst')} {op} {shape}"[:96]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _self_times(events: list[tuple[float, float, str]]) -> dict[str, float]:
    """Self time by name over one line's events (start, end, name):
    an event's duration minus that of the events directly inside it."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [end, name, self_ns]

    def close(top) -> None:
        out[top[1]] = out.get(top[1], 0.0) + max(top[2], 0.0)

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] -= e - s
        stack.append([e, name, e - s])
    while stack:
        close(stack.pop())
    return out


def read_events(path_or_data) -> dict:
    """{"devices": {index: [(start_ns, end_ns, name), ...]} from each
    device plane's `XLA Ops` line, "statements": [(start_ns, end_ns)]
    from the harness's annotations on any host line}."""
    from jax.profiler import ProfileData

    data = (ProfileData.from_file(path_or_data)
            if isinstance(path_or_data, str) else path_or_data)
    devices: dict[int, list] = {}
    statements: list[tuple[float, float]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m is not None:
                if line.name != OPS_LINE:
                    continue
                devices.setdefault(int(m.group(2)), []).extend(
                    (float(ev.start_ns), float(ev.start_ns + ev.duration_ns),
                     ev.name) for ev in line.events)
            elif plane.name.startswith("/host:"):
                statements.extend(
                    (float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
                    for ev in line.events
                    if ev.name == STATEMENT_ANNOTATION)
    return {"devices": devices, "statements": sorted(statements)}


def reduce_trace(path_or_data, window_s: float, n_statements: int,
                 n_devices_used: int | None = None) -> dict | None:
    """The device numbers of one profiled stretch, or None when the
    trace holds no device operation (a CPU rehearsal: the metrics that
    read this are then left out of the line).

    `window_s` and `n_statements` are the harness's own: the host-clock
    length of the profiled loop and the statements it completed there.
    """
    ev = read_events(path_or_data)
    devices = {d: e for d, e in ev["devices"].items() if e}
    if not devices:
        return None
    if n_devices_used is not None and len(devices) != n_devices_used:
        raise RuntimeError(
            f"the trace holds operations of {len(devices)} device(s), "
            f"the cell ran on {n_devices_used}")
    busy, op_self, collective, gaps = {}, {}, {}, {}
    stmts = ev["statements"]
    lo = min(s for e in devices.values() for s, _, _ in e)
    hi = max(t for e in devices.values() for _, t, _ in e)
    aligned = bool(stmts) and stmts[0][0] <= lo and hi <= stmts[-1][1] + 1e6
    for d, events in devices.items():
        merged = _union([(s, e) for s, e, _ in events])
        busy[d] = sum(e - s for s, e in merged) / 1e9
        self_ns = _self_times(events)
        op_self[d] = self_ns
        collective[d] = sum(v for k, v in self_ns.items()
                            if COLLECTIVE.match(opcode(k))) / 1e9
        gaps[d] = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
    busiest = max(busy, key=busy.get)
    n = max(n_statements, 1)
    by_label: dict[str, float] = {}
    by_opcode: dict[str, float] = {}
    for name, ns in op_self[busiest].items():
        by_label[label(name)] = by_label.get(label(name), 0.0) + ns
        by_opcode[opcode(name)] = by_opcode.get(opcode(name), 0.0) + ns
    by_op = sorted(by_label.items(), key=lambda kv: -kv[1])
    return {
        "n_devices": len(devices),
        "n_statements": n_statements,
        "window_s": window_s,
        "busy_s_by_device": {str(d): busy[d] for d in sorted(busy)},
        "busy_s": sum(busy.values()) / len(busy),
        "busiest_device": busiest,
        "device_busy_ms_per_stmt": busy[busiest] * 1e3 / n,
        "collective_ms_per_stmt": collective[busiest] * 1e3 / n,
        "clock_aligned": aligned,
        "device_ops": [[name, ns / 1e9] for name, ns in by_op[:10]],
        "device_opcodes": [[k, v / 1e9] for k, v in sorted(
            by_opcode.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": _label_gaps(gaps[busiest], stmts if aligned else []),
    }


def _label_gaps(gaps: list[tuple[float, float]],
                stmts: list[tuple[float, float]]) -> list[list]:
    """At most 10 entries [label, seconds], longest first.  The first
    two are totals.  A gap is "between statements" when a statement of
    the harness's loop ends or begins in it (the device waits while the
    host fetches and combines one answer and parses, plans and
    dispatches the next) and "inside a statement" when one statement
    spans it (the device waits between two of one statement's
    programs or operations)."""
    import bisect

    edges = sorted([s for s, _ in stmts] + [e for _, e in stmts])
    labelled = []
    for a, b in gaps:
        if not edges:
            label = "unlabelled (no statement annotation on this clock)"
        else:
            i = bisect.bisect_left(edges, a)
            label = ("between statements" if i < len(edges) and edges[i] <= b
                     else "inside a statement")
        labelled.append((label, (b - a) / 1e9))
    totals: dict[str, list] = {}
    for label, s in labelled:
        t = totals.setdefault(label, [0, 0.0])
        t[0] += 1
        t[1] += s
    out = [[f"{label}: total of {n} gaps", s]
           for label, (n, s) in sorted(totals.items(),
                                       key=lambda kv: -kv[1][1])]
    out += [[f"{label}: one gap", s] for label, s in
            sorted(labelled, key=lambda g: -g[1])[:10 - len(out)]]
    return out


def to_text_proto(path_or_data, keep_ns: float | None = None,
                  keep_lines=(OPS_LINE,)) -> str:
    """A trimmed copy of a trace as an XSpace text proto: the device
    planes' kept lines and the harness's statement annotations, events
    that start within `keep_ns` of the first kept event.  Names, starts
    and durations only — what the reduction reads.  For `fixtures/`
    (`ProfileData.from_text_proto` reads it back)."""
    from jax.profiler import ProfileData

    data = (ProfileData.from_file(path_or_data)
            if isinstance(path_or_data, str) else path_or_data)
    kept = []  # (plane, line, [(start_ns, dur_ns, name)])
    for plane in data.planes:
        dev = DEVICE_PLANE.match(plane.name)
        if dev is None and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if dev is not None and line.name not in keep_lines:
                continue
            evs = [(float(e.start_ns), float(e.duration_ns), e.name)
                   for e in line.events
                   if dev is not None or e.name == STATEMENT_ANNOTATION]
            if evs:
                kept.append((plane.name, line.name, evs))
    t0 = min(e[0] for _, _, evs in kept for e in evs)
    out, by_plane = [], {}
    for plane, line, evs in kept:
        by_plane.setdefault(plane, []).append((line, evs))
    for pid, (plane, lines) in enumerate(by_plane.items(), 1):
        names = sorted({e[2] for _, evs in lines for e in evs})
        ids = {n: i for i, n in enumerate(names, 1)}
        out.append(f'planes {{ id: {pid} name: "{plane}"')
        for n, i in ids.items():
            esc = n.replace("\\", "\\\\").replace('"', '\\"')
            out.append(f'  event_metadata {{ key: {i} value '
                       f'{{ id: {i} name: "{esc}" }} }}')
        for lid, (line, evs) in enumerate(lines, 1):
            out.append(f'  lines {{ id: {lid} name: "{line}" '
                       f'timestamp_ns: 0')
            for s, d, n in evs:
                if keep_ns is None or s - t0 <= keep_ns:
                    out.append(
                        f"    events {{ metadata_id: {ids[n]} offset_ps: "
                        f"{int(round((s - t0) * 1000))} duration_ps: "
                        f"{int(round(d * 1000))} }}")
            out.append("  }")
        out.append("}")
    return "\n".join(out) + "\n"


def main(argv: list[str] | None = None) -> int:
    """`python3 benchmark/xtrace.py <xplane.pb | log dir>`: what the
    trace holds, plane by plane; `--fixture OUT --keep-ms N` writes the
    trimmed text proto."""
    import argparse
    import json

    from jax.profiler import ProfileData

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("path")
    ap.add_argument("--fixture")
    ap.add_argument("--keep-ms", type=float)
    args = ap.parse_args(argv)
    path = args.path if os.path.isfile(args.path) \
        else newest_xplane(args.path)
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(json.dumps({"plane": plane.name,
                          "stats": [list(map(str, s)) for s in plane.stats]}))
        for line in plane.lines:
            evs = list(line.events)
            print(json.dumps({
                "line": line.name, "events": len(evs),
                "first": [[e.name, e.start_ns, e.duration_ns,
                           [list(map(str, s)) for s in e.stats][:6]]
                          for e in evs[:4]]}))
    if args.fixture:
        with open(args.fixture, "w") as f:
            f.write(to_text_proto(data, None if args.keep_ms is None
                                  else args.keep_ms * 1e6))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
