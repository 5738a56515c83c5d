"""The chip's compiler, asked without the chip.

The TPU compiler is installed here and compiles for a device that is
described, not attached (on-chip-measurement guide §2, third rehearsal).
These tests compile — never run — the kernels and the XLA formulations
the planner picks on `tpu`, at the shapes TPC-H SF1 produces on one
chip (6.0 M lineitem rows, 1.5 M orders, a 6 M-slot order-key
directory), with `jax_enable_x64` on as the engine runs.  A refusal
that interpret mode cannot show (tiling, 64-bit types, an unsupported
gather) shows here and costs no chip time.

A shape the compiler refuses stays as `xfail(strict=True)` carrying
its message, and is statically off the default path; when a later PR
repairs or deletes the kernel, the strict xfail turns red and the
marker goes with it.

The topology is described inside a module-scoped fixture and nowhere
else: the driver runs several xdist workers, each imports every test
file, and only one process at a time may load the TPU library.  Keep
every such compile in THIS file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from citus_tpu.executor import scanpipe
from citus_tpu.executor.compiler import _round_cap
from citus_tpu.ops import pallas_kernels as pk
from citus_tpu.ops.groupby import (
    GROUP_TILE_SLOTS,
    bucketed_grid_aggregate,
    group_bucket_count,
    group_pack_shape,
)
from citus_tpu.ops.join import sorted_unique_lookup

# TPC-H SF1 on one chip (ingest/tpch.py): padded feed capacities
LINEITEM_CAP = _round_cap(6_001_520)
ORDERS_CAP = _round_cap(1_500_000)
ORDERKEY_EXTENT = 6_000_000          # o_orderkey = 4i + 1, i < 1.5 M


# group by l_orderkey: the packed slot space is the key extent plus the
# null slot, in 4096-slot tiles; its pack is [NC, C] chunks, sized by
# the rows and the tile count alone (ops.groupby.group_pack_shape)
ORDERKEY_GROUP_BUCKETS = group_bucket_count(ORDERKEY_EXTENT + 1)

@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """shape-and-dtype → an abstract argument placed on one v5e chip."""
    one = SingleDeviceSharding(topo.devices[0])

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    return arg


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


# -- Pallas kernels a config var selects: repaired, must compile --------

def test_dense_grid_aggregate_pallas_compiles(chip):
    c = _compile(lambda s, v: pk.dense_grid_aggregate_pallas(
        s, v, GROUP_TILE_SLOTS),
        chip((LINEITEM_CAP,), jnp.int32),
        chip((LINEITEM_CAP, 4), jnp.float32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("rows,buckets", [
    # group by o_custkey: 150 k groups over 1.5 M orders
    pytest.param(ORDERS_CAP, group_bucket_count(150_001), id="o_custkey"),
    # group by l_orderkey: 6 M slots over 6 M lineitem rows
    pytest.param(
        LINEITEM_CAP, ORDERKEY_GROUP_BUCKETS, id="l_orderkey",
        marks=pytest.mark.xfail(
            strict=True, raises=jax.errors.JaxRuntimeError,
            reason="'RESOURCE_EXHAUSTED: XLA:TPU compile permanent "
                   "error. Ran out of memory in memory space hbm. Used "
                   "15.92G of 15.75G hbm ... Extra memory due to "
                   "padding: 6.39G (128.0x expansion)': the kernel "
                   "takes slots as [rows, 1] and values as [rows, 128], "
                   "rows on sublanes, which the chip's tiled HBM layout "
                   "pads 128-fold")),
])
def test_bucketed_groupby_sums_pallas_compiles(chip, rows, buckets):
    chunks, chunk = group_pack_shape(rows, buckets)
    c = _compile(lambda loc, stack: pk.bucketed_groupby_sums_pallas(
        loc, stack, GROUP_TILE_SLOTS),
        chip((chunks, chunk), jnp.int32),
        chip((chunks, chunk, 3), jnp.float32))
    assert "tpu_custom_call" in c.as_text()


# -- XLA formulations the planner picks on `tpu` -------------------------

def test_sorted_unique_lookup_compiles(chip):
    """Q3's lineitem ⋈ orders lookup on the sort-and-scan arm (the
    planner's pick over a 6 M-slot key extent, planner/plan.py
    `lookup_sorted`) at the shape one chip of the four-chip mesh gives
    it at SF1, keys narrowed to int32 as Q3's are.  The program is the
    one-chip shape's but for its sizes, and compiles in 71 s to its 82
    (compiler here, PR 28): the three sorts are the cost, at any size."""
    build, probe = _round_cap(1_500_000 // 4), _round_cap(6_001_520 // 4)
    c = _compile(
        sorted_unique_lookup,
        chip((build,), jnp.int32), chip((build,), jnp.bool_),
        chip((probe,), jnp.int32))
    text = c.as_text()
    assert "gather" not in text and "scatter" not in text
    assert c.memory_analysis().temp_size_in_bytes < 1 << 30


def test_compact_compiles(chip):
    """Q3's `join_out` compaction on one chip at SF1 — 6,001,536 probe
    rows to 195,072 slots, with the columns the join carries there (the
    build index, two narrowed keys, two measures) — alone: one sort of
    the positions, one gather a column at the compacted size, no
    scatter and no scan.  The compile seconds are printed (PR 30)."""
    import time
    from types import SimpleNamespace

    from citus_tpu.executor.batch import Block
    from citus_tpu.executor.compiler import PlanCompiler

    # k: the slots the cell's program plans there (PERF.md §5)
    n, k = LINEITEM_CAP, 195_072

    def compact(blk):
        this = SimpleNamespace(_overflow=jnp.zeros((), jnp.int64))
        return PlanCompiler._compact(this, blk, k), this._overflow

    blk = Block({"__bidx__": chip((n,), jnp.int32),
                 "l_orderkey": chip((n,), jnp.int32),
                 "l_shipdate": chip((n,), jnp.int32),
                 "l_extendedprice": chip((n,), jnp.float32),
                 "l_discount": chip((n,), jnp.float32)},
                chip((n,), jnp.bool_), {})
    t0 = time.perf_counter()
    c = _compile(compact, blk)
    print(f"_compact {n} -> {k}: compiled for a described v5e in "
          f"{time.perf_counter() - t0:.1f} s")
    text = c.as_text()
    ops = [ln for ln in text.splitlines() if "ct.compact" in ln]
    assert any(" sort(" in ln for ln in ops)
    assert "scatter" not in text and "reduce-window" not in text
    assert c.memory_analysis().temp_size_in_bytes < 1 << 30


def test_bucketed_grid_aggregate_xla_compiles(chip, monkeypatch):
    """The high-cardinality group-by at SF1 (group by l_orderkey over
    6 M rows; planner/plan.py `group_bucketed`), on the branch the
    chip takes: `_onehot_ok` asks `jax.default_backend()`, which is
    the CPU here, so the test answers for the chip.  One sort carries
    the columns, the chunks are cut by slices in a loop (no gather an
    element), the one-hot product is a convolution, and the chunks of
    a bucket are added by one scatter of whole rows."""
    import citus_tpu.ops.groupby as G

    monkeypatch.setattr(G, "_onehot_ok", lambda slots, tile: True)
    total, rows = ORDERKEY_EXTENT + 1, LINEITEM_CAP
    c = _compile(
        lambda slot, valid, v, n: bucketed_grid_aggregate(
            slot, valid, [(v, "sum"), (n, "count")], total, kernel="xla"),
        chip((rows,), jnp.int32), chip((rows,), jnp.bool_),
        chip((rows,), jnp.float32), chip((rows,), jnp.int32))
    ops = [ln for ln in c.as_text().splitlines() if " = " in ln]
    assert sum(" sort(" in ln for ln in ops) == 1
    assert any(" convolution(" in ln for ln in ops)
    chunks, chunk = group_pack_shape(rows, ORDERKEY_GROUP_BUCKETS)
    assert not any(f"[{chunks * chunk}" in ln and " gather(" in ln
                   for ln in ops)
    assert c.memory_analysis().temp_size_in_bytes < 1 << 30


# -- the on-device scan decode every TPU scan now takes (scanpipe.py) ---

@pytest.mark.parametrize("lut_dtype", [jnp.float32, jnp.float64])
@pytest.mark.parametrize("code_dtype", [jnp.uint8, jnp.uint16])
def test_scan_dict_expand_compiles(chip, code_dtype, lut_dtype):
    nv = 50 if code_dtype == jnp.uint8 else 40_000
    _compile(scanpipe._dict_expand,
             chip((1, LINEITEM_CAP), code_dtype), chip((nv,), lut_dtype))


@pytest.mark.parametrize("wire,decoded", [
    (jnp.uint8, jnp.int32),     # l_linenumber, dictionary codes
    (jnp.uint16, jnp.int32),    # dates
    (jnp.uint32, jnp.int64),    # order / part / supplier keys
])
def test_scan_for_expand_compiles(chip, wire, decoded):
    _compile(scanpipe._for_expand,
             chip((1, LINEITEM_CAP), wire), chip((), decoded))


def test_scan_bits_and_valid_expand_compile(chip):
    # the validity plane at the customer table's size (150 k rows), not
    # lineitem's: this reshape formulation compiles in time linear in
    # its rows — 4 s here, 155 s at 6 M (PERF.md, open questions) — and
    # no TPC-H column is nullable, so SF1 never compiles it at all
    cap = _round_cap(150_000)
    _compile(lambda p: scanpipe._bits_expand(p, cap),
             chip((1, cap // 8), jnp.uint8))
    _compile(lambda r: scanpipe._valid_expand(r, LINEITEM_CAP),
             chip((1, 1), jnp.int32))


# -- a whole statement's program ------------------------------------------

def _compile_ssb_q4_1(topo, tmp_path, scale_factor: float):
    """SSB Q4.1's program at the shapes of `scale_factor`, as
    `Executor._compile_or_load` builds it, for one described v5e chip:
    (compiled, stage_keys, compile seconds, fact rows).  The rows are
    the benchmark's own (the columns Q4.1 reads), so statistics, extents
    and capacities are the cell's; nothing is executed."""
    import dataclasses
    import json
    import os
    import re
    import sys
    import time

    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    import citus_tpu
    from benchmark.datasets import ssb
    from citus_tpu.distributed.mesh import SHARD_AXIS, make_mesh
    from citus_tpu.executor.compiler import PlanCompiler
    from citus_tpu.executor.feed import build_feeds
    from citus_tpu.ingest.copy_from import _ingest_batch
    from citus_tpu.sql.parser import parse_one

    with open(os.path.join(root, "benchmark", "statements",
                           "ssb_q4_1.json")) as f:
        st = json.load(f)
    with open(os.path.join(root, "benchmark", "statements", st["sql"])) as f:
        sql = f.read()
    params = {"scale_factor": scale_factor, "shard_count": 8}
    data = ssb.generate(params, 5)
    fact_rows = ssb.row_counts(data)[ssb.FACT]
    sess = citus_tpu.connect(data_dir=str(tmp_path / "ssb"), n_devices=1)
    try:
        # the five tables cut to the columns Q4.1 reads (and the fact
        # table's distribution key): the program is the same, the load
        # is a tenth
        for table, cols in st["reads"].items():
            names = cols + [ssb.FACT_KEY] * (table == ssb.FACT)
            types = dict(re.findall(r"(\w+) (int|bigint|text)\b",
                                    ssb.SCHEMAS[table]))
            sess.execute(f"create table {table} (" + ", ".join(
                f"{c} {types[c]}" for c in names) + ")")
            if table == ssb.FACT:
                sess.create_distributed_table(table, ssb.FACT_KEY,
                                              shard_count=8)
            else:
                sess.create_reference_table(table)
            _ingest_batch(sess, table, names, [
                list(data[table][c]) if data[table][c].dtype == object
                else data[table][c] for c in names], pre_typed=True)
        del data
        ex = sess.executor
        plan, _cleanup = sess._plan_select(parse_one(sql))
        feeds = build_feeds(plan, ex.catalog, ex.store, ex.mesh,
                            np.dtype("float32"))
        # SF1 converges at its initial capacities: no buffer is far
        # enough over its actual to tighten (seen here at SF1 on the CPU)
        caps = ex._initial_capacities(plan, feeds)
        mesh = make_mesh(devices=topo.devices[:1])

        def abstract(arr, sharded):
            return jax.ShapeDtypeStruct(
                arr.shape, arr.dtype, sharding=NamedSharding(
                    mesh, P(SHARD_AXIS) if sharded else P()))

        feeds = {nid: dataclasses.replace(
            f, arrays={c: abstract(a, f.sharded) for c, a in f.arrays.items()},
            nulls={c: abstract(a, f.sharded) for c, a in f.nulls.items()},
            valid=abstract(f.valid, f.sharded)) for nid, f in feeds.items()}
        assert max(f.capacity for f in feeds.values()) == _round_cap(fact_rows)
        fn, feed_arrays, _meta, stage_keys = PlanCompiler(
            plan, mesh, feeds, caps, np.dtype("float32")).build()
        t0 = time.perf_counter()
        c = fn.lower(*feed_arrays).compile()
        return c, stage_keys, time.perf_counter() - t0, fact_rows
    finally:
        sess.close()


def _ops_by_size_and_scope(text: str, op: str) -> dict:
    """(leading dimension of the first result, `ct.` path) -> count of
    `op` instructions in a compiled program's text."""
    import collections
    import re

    out = collections.Counter()
    for ln in text.splitlines():
        m = re.search(r"= \(?\w+\[(\d+)(?:,\d+)*\]\S* (?:\S+ )*?"
                      + op + r"\(", ln)
        if m is None:
            continue
        path = re.search(r'op_name="([^"]*)"', ln)
        scope = "/".join(re.findall(r"ct\.(\w+)", path.group(1))) \
            if path else ""
        out[(int(m.group(1)), scope or "unscoped")] += 1
    return dict(sorted(out.items(), key=lambda kv: (-kv[0][0], kv[0][1])))


def test_ssb_q4_1_program_compiles(topo, tmp_path):
    """The benchmark cell `ssb1.q4_1`'s program at SSB SF1's shapes —
    6.0 M fact rows through four broadcast lookup joins on the dense
    directory (`supplier`, `customer`, `part`, `dwdate`: the unfiltered
    calendar last since PR 34), compacted 6.0 M → 1.8 M → 360 k slots
    on the way, 35 groups on the dense grid.  22.8 s in this sandbox (compiler here,
    PR 29); 11.2 s with the compactions' two sorts against 10.8 s with
    their scatters, in one sitting (compiler here, PR 30); printed
    below."""
    import re

    c, stage_keys, seconds, fact_rows = _compile_ssb_q4_1(topo, tmp_path, 1.0)
    assert fact_rows == 5_999_224
    print(f"ssb q4_1 at SF1 shapes: compiled for a described v5e in "
          f"{seconds:.1f} s; join_out stages "
          f"{[w for _, kind, w in stage_keys if kind == 'join_out']}")
    text = c.as_text()
    assert "ct.lookup_join/ct.dense" in text
    assert "ct.lookup_join/ct.sort" not in text  # no key extent reaches 2^18
    assert "ct.join_out" in text and "ct.agg_grid" in text
    # both compactions find their survivors by a sort (PR 30): no
    # scatter carries the sub-scope's path
    compact_ops = [ln for ln in text.splitlines()
                   if "ct.join_out/ct.compact" in ln]
    assert sum(" sort(" in ln for ln in compact_ops) == 2
    assert not any("scatter" in ln for ln in compact_ops)
    # columns cross a compaction or a lookup as a row index and are
    # gathered where they are first read (PR 32).  At the first
    # compaction's 1,800,320 slots the parent gathered eight times (five
    # fact columns, `d_year`, two probes); four are left: `lo_orderdate`
    # and `lo_custkey`, read there as join keys, and the two probes.  At
    # 360,576 slots seven became nine (three index compositions, five
    # columns through them, the `part` probe), and none of it is under
    # `ct.compact` any more.  With `dwdate` joined last (PR 34) its
    # probe and `lo_orderdate` leave the 1,800,320 slots for the
    # 360,576: two are left at the first size (`lo_custkey` and the
    # `customer` probe) and ten at the second
    sizes = [int(m.group(1)) for m in re.finditer(
        r"= \w+\[(\d+)\]\S* gather\(", text)]
    assert sizes.count(5_999_232) == 1
    assert sizes.count(1_800_320) == 2
    assert sizes.count(360_576) == 10
    assert not any(" gather(" in ln for ln in compact_ops)
    assert c.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.slow
def test_ssb10_q4_1_program_compiles(topo, tmp_path):
    """The benchmark cell `ssb10.q4_1`'s program at SSB SF10's shapes —
    60.0 M fact rows, `supplier` and `dwdate` probed through the dense
    directory, `customer` (300,000 keys) and `part` (800,000) by sort
    and scan: both lookup arms in one fragment.  Not in tier-1: the
    rows alone take 9 GB of host memory and a minute to make.  Prints
    what PERF.md §6's prediction is made from: compile seconds, gathers
    and sorts by size and `ct.` path, `memory_analysis()`."""
    c, stage_keys, seconds, fact_rows = _compile_ssb_q4_1(topo, tmp_path,
                                                          10.0)
    assert fact_rows == 59_999_933
    text, mem = c.as_text(), c.memory_analysis()
    print(f"ssb q4_1 at SF10 shapes: compiled for a described v5e in "
          f"{seconds:.1f} s; join_out stages "
          f"{[w for _, kind, w in stage_keys if kind == 'join_out']}; "
          f"temporaries {mem.temp_size_in_bytes}, arguments "
          f"{mem.argument_size_in_bytes}, output {mem.output_size_in_bytes}")
    for op in ("gather", "sort", "scatter", "reduce-window"):
        print(f"  {op}: {_ops_by_size_and_scope(text, op)}")
    assert "ct.lookup_join/ct.dense" in text
    assert "ct.lookup_join/ct.sort" in text
    sorts = _ops_by_size_and_scope(text, "sort")
    assert sum(n for (_size, scope), n in sorts.items()
               if scope == "lookup_join/sort") == 6     # three a sorted lookup
    assert sum(n for (_size, scope), n in sorts.items()
               if scope == "join_out/compact") == 2
    # feeds and temporaries together leave most of the chip's 15.75 GiB
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 6 << 30
