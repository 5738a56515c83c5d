"""The chip's compiler, asked without the chip.

The TPU compiler is installed here and compiles for a device that is
described, not attached (on-chip-measurement guide §2, third rehearsal).
These tests compile — never run — the XLA formulations the chip runs,
at the shapes TPC-H SF1 produces on one chip (6.0 M lineitem rows,
1.5 M orders, a 6 M-slot order-key directory), with `jax_enable_x64` on
as the engine runs.  A refusal that the CPU backend cannot show
(tiling, 64-bit types, an unsupported gather) shows here and costs no
chip time.

A shape the compiler refuses stays as `xfail(strict=True)` carrying
its message, and is statically off the default path; once the code is
repaired or deleted, the strict xfail turns red and the marker goes
with it.

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
from citus_tpu.ops.groupby import (
    bucketed_grid_aggregate,
    group_bucket_count,
    group_pack_shape,
)
from citus_tpu.ops.join import sorted_unique_lookup

# TPC-H SF1 on one chip (ingest/tpch.py): padded feed capacities
LINEITEM_CAP = _round_cap(6_001_520)
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


# -- XLA formulations the chip runs ---------------------------------------

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


def test_sort_path_groupby_compiles_at_its_capacity(chip):
    """The sort-path group-by as the compiler dispatches it
    (`PlanCompiler._segment_aggregate_maybe_packed`) — five int32 group
    keys packed into one int64, a nullable float32 sum and its companion
    count — over 65,536 rows under a capacity of 2,048 groups.  At the
    rows' width only the value and its null mask are gathered, in
    sorted order: the packed key and the validity come from the sort,
    the group starts from a second sort, so nothing scatters; each
    group's first row, keys and reductions are read at the capacity."""
    import collections
    import re
    from types import SimpleNamespace

    from citus_tpu.executor.compiler import Capacities, PlanCompiler
    from citus_tpu.stats.tracing import stage_scope

    n, cap = 65_536, 2_048
    # the extents of TPC-DS Q57's group keys: call centers, brands,
    # categories, years, months
    extents = (12, 533, 99, 201, 12)
    node = SimpleNamespace(key_ranges=tuple((0, e, False) for e in extents),
                           nulls_last=())
    key_meta = [(f"k{i}", False) for i in range(len(extents))]
    pc = PlanCompiler.__new__(PlanCompiler)
    pc.caps, pc.n_dev = Capacities({}, {}, {id(node): cap}), 1

    def groupby(keys, price, price_null, valid):
        pc._dense_oob = pc._overflow = jnp.zeros((), jnp.int64)
        pc._stage_actual, pc._stage_width = {}, {}
        pc._agg_sort_gather_slots = 0
        ok = ~price_null
        with stage_scope("agg_sort"):
            gk, res, gvalid = pc._segment_aggregate_maybe_packed(
                node, list(keys), key_meta,
                [(price, "sum", ok), (price, "count", ok)], valid)
        return gk, res, gvalid, pc._overflow

    c = _compile(groupby, tuple(chip((n,), jnp.int32) for _ in extents),
                 chip((n,), jnp.float32), chip((n,), jnp.bool_),
                 chip((n,), jnp.bool_))
    wide = collections.Counter()
    for ln in c.as_text().splitlines():
        m = re.search(r"= (\w+)\[(\d+)[^\]]*\]\S* (gather|scatter)\(", ln)
        if m and (m.group(3) == "scatter" or int(m.group(2)) > cap):
            wide[(m.group(3), m.group(1))] += 1
    print(f"gathers and scatters wider than {cap} slots: {dict(wide)}")
    assert wide == {("gather", "f32"): 1, ("gather", "pred"): 1}
    # the counter's count: those two at n; the first row, five keys,
    # the sum and the count's two prefix reads at the capacity
    assert pc._agg_sort_gather_slots == 2 * n + 9 * cap


def test_lookup_probes_at_its_range_capacity(chip):
    """A fused inner lookup whose build side keeps one interval of its
    keys (TPC-DS Q89's `date_dim` under `d_year in (1999)`: 365 of
    73,049 date keys), under a converged range capacity of a fifth of
    the fact side: at the fact side's width the program holds one sort
    (the positions, `ct.join_range/ct.compact`) and no gather, the
    key is gathered and the directory probed at the capacity, and the
    columns read after the lookup are gathered there too."""
    import re
    from types import SimpleNamespace

    from citus_tpu.executor.batch import Block
    from citus_tpu.executor.compiler import Capacities, PlanCompiler

    n, m = 1 << 20, 73_049
    k = _round_cap(n // 5)
    node = SimpleNamespace(join_type="inner", build_side="right",
                           residual=None,
                           right_key_extents=((2_415_022, m),),
                           lookup_sorted=False, fuse_lookup=True,
                           key_pack=None)
    pc = PlanCompiler.__new__(PlanCompiler)
    pc.caps, pc.n_dev = Capacities({}, {id(node): k}), 1
    pc.caps.join_range[id(node)] = k

    def lookup(date_sk, item_sk, price, d_date_sk, d_moy, d_year):
        pc._dense_oob = pc._overflow = jnp.zeros((), jnp.int64)
        pc._stage_actual, pc._stage_width = {}, {}
        pc._lookup_probe_slots = pc._lookup_range_compactions = 0
        probe = Block({"date": date_sk, "item": item_sk, "price": price},
                      jnp.ones(n, bool), {})
        build = Block({"d_date_sk": d_date_sk, "moy": d_moy},
                      jnp.ones(m, bool), {})
        out = pc._exec_lookup_join(node, probe, build, [date_sk],
                                   probe.valid, [d_date_sk],
                                   d_year == 1999)
        return (out.columns["item"], out.columns["moy"],
                out.columns["price"], out.valid, pc._overflow)

    c = _compile(lookup, chip((n,), jnp.int32), chip((n,), jnp.int32),
                 chip((n,), jnp.float32), chip((m,), jnp.int32),
                 chip((m,), jnp.int32), chip((m,), jnp.int32))
    widths = {}
    for ln in c.as_text().splitlines():
        op = re.search(r"= \w+\[(\d+)\]\S* (sort|gather)\(", ln)
        name = re.search(r'op_name="([^"]*)"', ln)
        if op and name:
            widths.setdefault((op.group(2), int(op.group(1))), []).append(
                name.group(1))
    assert [w for (op, w) in widths if w > k] == [n]
    assert all("ct.join_range/ct.compact" in p
               for p in widths[("sort", n)])
    probes = widths[("gather", k)]
    assert any("ct.join_range" in p for p in probes)
    assert any("ct.lookup_join/ct.dense" in p for p in probes)
    assert pc._lookup_range_compactions == 1
    assert pc._lookup_probe_slots == k


def test_bucketed_grid_aggregate_xla_compiles(chip, monkeypatch):
    """The high-cardinality group-by at SF1 (group by l_orderkey over
    6 M rows; planner/plan.py `group_bucket_eligible`), on the branch the
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
            slot, valid, [(v, "sum"), (n, "count")], total),
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


def test_tpcds_q89_inner_program_compiles(topo, tmp_path):
    """The benchmark cell `tpcds10.q89`'s device program (PR 40) — the
    derived table's block: three broadcast lookups, the six-key
    group-by and the window over its groups — at TPC-DS SF 0.1's shapes
    (288,040 fact rows), for one described v5e.  The groups under the
    window sum by a segmented scan and sort as one (key, position)
    operand, and reach the window in partition order, so the program
    holds no float64 and no sort under `ct.window`: the forms that
    compiled in 130 s here (float64 prefix sums) and 155 s at SF10
    (the window's lexsort) now take 9 and 19 (compiler here, PR 40)."""
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
    from benchmark.datasets import tpcds
    from citus_tpu.distributed.mesh import SHARD_AXIS, make_mesh
    from citus_tpu.executor.compiler import PlanCompiler
    from citus_tpu.executor.feed import build_feeds
    from citus_tpu.ingest.copy_from import _ingest_batch
    from citus_tpu.sql.parser import parse_one

    with open(os.path.join(root, "benchmark", "statements",
                           "tpcds_q89.json")) as f:
        st = json.load(f)
    with open(os.path.join(root, "benchmark", "statements", st["sql"])) as f:
        sql = f.read()
    inner = sql[sql.index("(") + 1:sql.rindex(") tmp1")]
    data = tpcds.generate({"scale_factor": 0.1}, 5)
    sess = citus_tpu.connect(data_dir=str(tmp_path / "ds"), n_devices=1)
    try:
        for table, cols in st["reads"].items():
            ddl = tpcds.SCHEMAS[table]
            types = dict(re.findall(
                r"(\w+) (integer|date|char\(\d+\)|varchar\(\d+\)|"
                r"decimal\(\d+,\d+\))", ddl))
            sess.execute(f"create table {table} (" + ", ".join(
                f"{c} {types[c]}" for c in cols) + ")")
            if table == tpcds.FACT:
                sess.create_distributed_table(table, tpcds.FACT_KEY,
                                              shard_count=8)
            else:
                sess.create_reference_table(table)
            _ingest_batch(sess, table, cols, [
                list(data[table][c]) if data[table][c].dtype == object
                else data[table][c] for c in cols], pre_typed=True)
        del data
        ex = sess.executor
        plan, _cleanup = sess._plan_select(parse_one(inner))
        assert plan.root.presorted
        feeds = build_feeds(plan, ex.catalog, ex.store, ex.mesh,
                            np.dtype("float32"))
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
        fn, feed_arrays, _meta, _keys = PlanCompiler(
            plan, mesh, feeds, caps, np.dtype("float32")).build()
        t0 = time.perf_counter()
        c = fn.lower(*feed_arrays).compile()
        seconds = time.perf_counter() - t0
    finally:
        sess.close()
    print(f"tpcds q89 inner block at SF 0.1 shapes: compiled for a "
          f"described v5e in {seconds:.1f} s")
    text = c.as_text()
    assert "ct.window/ct.scan" in text and "ct.agg_sort" in text
    assert "f64[" not in text
    assert not any(" sort(" in ln and "ct.window" in ln
                   for ln in text.splitlines())


def test_tpcds_q57_programs_compile(topo, tmp_path, monkeypatch):
    """The benchmark cell `tpcds10cs.q57`'s converged device programs
    at TPC-DS SF 0.1's shapes (144,126 catalog_sales rows), for
    one described v5e: `v1` (three broadcast lookups, the five-key
    group-by and its two stacked windows), `v2` (the CTE self-joined
    three ways) and the outer statement.  The groups reach both windows
    in their partition and ORDER BY order, so nothing under `ct.window`
    sorts; each self-join sorts its build side by one packed int64 key
    (on the chip two 32-bit halves of one operand, where the lexsort of
    four keys and validity would be ten); no float64.  Recorded as the
    statement runs on the CPU, then built again for the described chip,
    with the planner's `tpu` pick of the group-by path."""
    import dataclasses
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
    from benchmark.datasets import tpcds_catalog as ds
    from citus_tpu.distributed.mesh import SHARD_AXIS, make_mesh
    from citus_tpu.executor import compiler as C
    from citus_tpu.executor.cache import node_fingerprint

    built = {}
    real_init = C.PlanCompiler.__init__

    def recording(self, plan, mesh, feeds, caps, *args, **kw):
        # the last program of each plan is its converged one
        built[node_fingerprint(plan.root)] = (plan, feeds, caps)
        real_init(self, plan, mesh, feeds, caps, *args, **kw)

    monkeypatch.setattr(C.PlanCompiler, "__init__", recording)
    with open(os.path.join(root, "benchmark", "statements",
                           "tpcds_q57.sql")) as f:
        sql = f.read()
    data = ds.generate({"scale_factor": 0.1}, 5)
    sess = citus_tpu.connect(data_dir=str(tmp_path / "cs"), n_devices=1,
                             serving_result_cache_bytes=0)
    try:
        ds.load(sess, data, {"shard_count": 8})
        del data
        for _ in range(2):
            sess.execute(sql).rows()
    finally:
        sess.close()
    monkeypatch.undo()
    assert len(built) == 3
    mesh = make_mesh(devices=topo.devices[:1])

    def abstract(arr, sharded):
        return jax.ShapeDtypeStruct(arr.shape, arr.dtype, sharding=NamedSharding(
            mesh, P(SHARD_AXIS) if sharded else P()))

    texts, seconds = [], []
    for plan, feeds, caps in built.values():
        feeds = {nid: dataclasses.replace(
            f, arrays={c: abstract(a, f.sharded) for c, a in f.arrays.items()},
            nulls={c: abstract(a, f.sharded) for c, a in f.nulls.items()},
            valid=abstract(f.valid, f.sharded)) for nid, f in feeds.items()}
        fn, feed_arrays, _meta, _keys = C.PlanCompiler(
            plan, mesh, feeds, caps, np.dtype("float32")).build()
        t0 = time.perf_counter()
        texts.append(fn.lower(*feed_arrays).compile().as_text())
        seconds.append(time.perf_counter() - t0)
    print(f"tpcds q57 at SF 0.1 shapes: v1, v2 and the outer statement "
          f"compiled for a described v5e in "
          f"{', '.join(f'{s:.1f}' for s in seconds)} s")
    v1, v2, outer = texts
    for text in texts:
        assert "f64[" not in text
    assert "ct.window/ct.scan" in v1 and "ct.agg_sort" in v1
    assert not any(" sort(" in ln and "ct.window" in ln
                   for ln in v1.splitlines())
    join_sorts = [ln for ln in v2.splitlines()
                  if " sort(" in ln and "ct.lookup_join" in ln]
    assert len(join_sorts) == 2
    for ln in join_sorts:
        operands = re.search(r" sort\(([^)]*)\)", ln).group(1)
        assert operands.count("%") <= 2, ln
    assert "ct.window" not in v2 + outer
