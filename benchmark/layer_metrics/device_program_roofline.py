"""`device_program_roofline` (%): the least time the chips could take to read
the statement's columns from HBM once, over the time the busiest chip
was busy per statement.

Bytes are the yardstick's own: for each table the statement reads
(`statements/<name>.json` `reads`), rows × the decoded, device-resident
width of each column (`datasets/<name>.py` `column_widths`, under the
configuration's compute dtype).  They do not follow what the program
chooses to keep on the device, so a program that reads less than this
can pass 100 % only by reading the columns packed — and then the
definition, not the program, is due for a benchmark PR.  Bandwidth is
the published HBM peak of the device kind (`peaks.json`), times the
devices of the mesh: each holds its share of the rows.
"""

from __future__ import annotations


def statement_bytes(run, st: dict) -> int:
    widths = run.cell.dataset.column_widths(
        run.cell.config.get("session_settings", {}).get(
            "compute_dtype", "float32"))
    return sum(run.data.rows[table] * sum(widths[c] for c in cols)
               for table, cols in st["reads"].items())


def read(run):
    dt = run.device_trace
    if dt is None or not dt["device_busy_ms_per_stmt"]:
        return None
    peak = run.peaks[run.device["kind"]]["hbm_bytes_per_s"]
    sts = run.cell.statements
    total_w = sum(st["weight"] for st in sts)
    mean_bytes = sum(statement_bytes(run, st) * st["weight"]
                     for st in sts) / total_w
    least_s = mean_bytes / (peak * dt["n_devices"])
    return 100.0 * least_s / (dt["device_busy_ms_per_stmt"] / 1e3)
