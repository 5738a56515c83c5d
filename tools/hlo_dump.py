#!/usr/bin/env python3
"""tools/hlo_dump.py — the device programs of the benchmark's statements,
as text that two commits can be compared by.

    python3 tools/hlo_dump.py <checkout> <out_dir> <n_devices>

Loads TPC-H (SF 0.01, `benchmark/selftest.py`'s scale), SSB (SF 0.05,
`tests/test_ssb.py`'s: at 0.01 Q4.1 has no row to answer with) and TPC-H
with Zipf(1) customer keys (SF 0.1, the smallest decade whose 15,001
`c_custkey` slots are past the flat grid's limit, with `group_by_kernel`
forced onto the bucketed grid that the planner picks by itself only on
the chip) through <checkout>'s program on <n_devices> CPU devices, answers
Q1, Q3, SSB Q4.1 and Q13 (both of its programs) until their capacities
have settled, and writes the optimized HLO of every plan-cache entry to
<out_dir>/<config>.<n>dev.<i>.hlo, without what is no part of JAX's
persistent-cache key: each operation's `metadata={…}` and the module's
source-location tables.  A change that must not move a device program
shows it by

    git archive <parent> | tar -x -C /tmp/parent
    for n in 1 4; do python3 tools/hlo_dump.py /tmp/parent /tmp/hlo/a $n
                     python3 tools/hlo_dump.py . /tmp/hlo/b $n; done
    diff -r /tmp/hlo/a /tmp/hlo/b

Says nothing of speed: the programs are the CPU backend's, at a toy size.
"""

from __future__ import annotations

import importlib
import json
import os
import re
import shutil
import sys
import tempfile

METADATA = re.compile(r",?\s*metadata=\{[^{}]*(?:\{[^{}]*\}[^{}]*)*\}")
# what metadata's stack_frame_id indexes: files, functions, lines, frames
TABLES = re.compile(
    r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*\n",
    re.M)
# (dataset module, configuration at one device / at more, scale factor,
#  statements, settings beyond the configuration's own)
JOBS = (
    ("tpch", ("tpch-sf1-1chip", "tpch-sf1-4chip"), 0.01,
     ("tpch_q1", "tpch_q3"), {}),
    ("ssb", ("ssb-sf1-1chip", "ssb-sf1-1chip"), 0.05, ("ssb_q4_1",), {}),
    ("tpch_zipf", ("tpch-sf1-4chip-zipf1", "tpch-sf1-4chip-zipf1"), 0.1,
     ("tpch_q13",), {"group_by_kernel": "bucketed"}),
)


def strip(text: str) -> str:
    return TABLES.sub("", METADATA.sub("", text))


def main(root: str, out: str, n_devices: int) -> None:
    root = os.path.abspath(root)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"  # compile, every time
    os.environ["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={n_devices}"
    sys.path.insert(0, root)
    import citus_tpu

    assert os.path.dirname(os.path.dirname(citus_tpu.__file__)) == root
    os.makedirs(out, exist_ok=True)
    bench = os.path.join(root, "benchmark")
    for dataset, configs, scale, statements, settings in JOBS:
        config = configs[n_devices > 1]
        with open(os.path.join(bench, "configs", config + ".json")) as f:
            cfg = json.load(f)
        params = {**cfg["dataset_params"], "scale_factor": scale}
        module = importlib.import_module(f"benchmark.datasets.{dataset}")
        data_dir = tempfile.mkdtemp(prefix="data_", dir=out)
        sess = citus_tpu.connect(data_dir=data_dir, n_devices=n_devices,
                                 exec_cache_enabled=False,
                                 **{**cfg.get("session_settings", {}),
                                    **settings})
        try:
            module.load(sess, module.generate(params, 1), params)
            for name in statements:
                with open(os.path.join(bench, "statements",
                                       name + ".json")) as f:
                    sql_file = json.load(f)["sql"]
                with open(os.path.join(bench, "statements", sql_file)) as f:
                    sql = f.read()
                for _ in range(4):
                    rows = sess.execute(sql).rows()
                print(f"{config} {n_devices} dev {name}: {len(rows)} rows")
            entries = sess.executor.plan_cache._entries.values()
            for i, entry in enumerate(entries):
                path = os.path.join(out, f"{config}.{n_devices}dev.{i}.hlo")
                with open(path, "w") as f:
                    f.write(strip(entry[0].as_text()))
        finally:
            sess.close()
            shutil.rmtree(data_dir, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], int(sys.argv[3]))
