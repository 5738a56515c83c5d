"""Print the phase breakdown of a recorded statement trace.

    python tools/trace_summarize.py <data_dir | trace.json> [--top N]

Given a data_dir, picks the NEWEST slow-query trace under
``<data_dir>/slow_traces/`` (written when a statement exceeds
``trace_slow_statement_ms``); given a file, summarizes that trace.
Output: the statement, its wall clock, the per-phase attribution the
EXPLAIN ANALYZE ``Timing:`` line shows (same phase names — both come
from stats/tracing.phase_breakdown), and the N slowest individual
spans with their tree paths.  For a timeline over the device's
operations, capture a `jax.profiler` trace of the live session: the
same spans are in it as ``ct:`` events (stats/tracing.py).
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
    __file__)), ".."))


def newest_slow_trace(data_dir: str) -> str | None:
    from citus_tpu.stats.tracing import SLOW_TRACE_DIR

    d = os.path.join(data_dir, SLOW_TRACE_DIR)
    if not os.path.isdir(d):
        return None
    names = sorted(n for n in os.listdir(d)
                   if n.startswith("trace_") and n.endswith(".json"))
    return os.path.join(d, names[-1]) if names else None


def load_trace(path: str) -> dict:
    """`path` is a trace JSON file, a data_dir, or a slow_traces dir."""
    from citus_tpu.stats.tracing import SLOW_TRACE_DIR

    if os.path.isdir(path):
        if os.path.basename(path) == SLOW_TRACE_DIR:
            path = os.path.dirname(path)
        p = newest_slow_trace(path)
        if p is None:
            raise FileNotFoundError(
                f"no slow-query traces under {path!r} (is "
                "trace_slow_statement_ms set low enough?)")
        path = p
    with open(path) as f:
        return json.load(f)


def summarize(doc: dict, top: int = 10) -> list[str]:
    """Render one trace dict (Trace.to_dict() / persisted slow-trace
    JSON) as report lines."""
    from citus_tpu.stats.tracing import PHASE_ORDER, phase_breakdown

    root = doc.get("root") or {}
    wall = doc.get("wall_ms") or root.get("dur_ms", 0.0)
    lines = [
        f"statement: {doc.get('sql', '?')!r}",
        f"class:     {doc.get('class', '?')}",
        f"wall:      {wall:.2f} ms"
        + ("  [truncated trace]" if doc.get("truncated") else "")
        + (f"  [error: {doc['error']}]" if doc.get("error") else ""),
        "",
        "phase breakdown (Timing):",
    ]
    ph = phase_breakdown(root)
    total = max(ph.get("total", 0.0), 1e-12)
    for name in PHASE_ORDER + ("other",):
        v = ph.get(name, 0.0)
        if v <= 0.0:
            continue
        share = 100.0 * v / total
        lines.append(f"  {name:<10s} {v * 1000.0:10.2f} ms  "
                     f"{share:5.1f}%")
    lines.append(f"  {'total':<10s} {total * 1000.0:10.2f} ms")
    # slowest individual spans with their tree path
    flat: list[tuple[float, str]] = []

    def walk(span: dict, path: str) -> None:
        p = f"{path}/{span['name']}" if path else span["name"]
        flat.append((span.get("dur_ms", 0.0), p))
        for c in span.get("children", ()):
            walk(c, p)

    for c in root.get("children", ()):
        walk(c, "")
    flat.sort(key=lambda t: -t[0])
    if flat:
        lines += ["", f"slowest spans (top {min(top, len(flat))}):"]
        for dur, path in flat[:top]:
            lines.append(f"  {dur:10.2f} ms  {path}")
    return lines


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    top = 10
    args = []
    it = iter(argv)
    for a in it:
        if a == "--top":
            nxt = next(it, None)
            if nxt is None or not nxt.isdigit():
                print("trace_summarize: --top needs an integer",
                      file=sys.stderr)
                return 2
            top = int(nxt)
        elif a in ("-h", "--help"):
            print(__doc__)
            return 0
        else:
            args.append(a)
    if len(args) != 1:
        print("usage: python tools/trace_summarize.py "
              "<data_dir | trace.json> [--top N]", file=sys.stderr)
        return 2
    try:
        doc = load_trace(args[0])
    except (OSError, ValueError) as e:
        print(f"trace_summarize: {e}", file=sys.stderr)
        return 1
    try:
        for line in summarize(doc, top=top):
            print(line)
    except BrokenPipeError:
        pass  # piped into head — normal CLI citizenship
    return 0


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(main())
