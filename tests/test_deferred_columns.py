"""Deferred columns (`Block.take`, PR 32): a column crosses a compaction
or a lookup as a row index and is gathered where it is first read.

Held to eager numpy gathers on `Block` alone — eager, jitted and under
`shard_map` at four virtual devices — and through
`PlanCompiler._compact` and `PlanCompiler._exec_lookup_join`; the
mechanism itself (what is gathered, what is composed, what is never
touched) is counted in the jaxpr, and the program's own tally
(`deferred_tally`) is held to the same counts."""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from citus_tpu.executor.batch import Block, Columns, deferred_tally
from citus_tpu.executor.compiler import Capacities, PlanCompiler

N = 1000
DTYPES = ("i64", "f32", "flag", "i32")   # tests/test_compact.py's columns


def _block(n: int = N, with_nulls: bool = True) -> Block:
    pos = np.arange(n)
    cols = {"i64": jnp.asarray(pos.astype(np.int64) * 7 + (1 << 40)),
            "f32": jnp.asarray(pos.astype(np.float32) + 0.5),
            "flag": jnp.asarray(pos % 3 == 1),
            "i32": jnp.asarray((n - pos).astype(np.int32))}
    nulls = ({"f32": jnp.asarray(pos % 5 == 2), "i32": jnp.asarray(pos % 7 == 3)}
             if with_nulls else {})
    return Block(cols, jnp.ones(n, bool), nulls)


def _indexes(seed: int = 5, n: int = N, n1: int = 400, n2: int = 90):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.integers(0, n, n1).astype(np.int32)),
            jnp.asarray(rng.integers(0, n1, n2).astype(np.int32)))


def _gathers(fn, *args) -> int:
    """`gather` equations in the jaxpr of `fn(*args)`, nested ones
    (under pjit) included."""
    def count(jaxpr) -> int:
        total = 0
        for eqn in jaxpr.eqns:
            total += eqn.primitive.name == "gather"
            for sub in jax.core.jaxprs_in_params(eqn.params):
                total += count(sub)
        return total
    return count(jax.make_jaxpr(fn)(*args).jaxpr)


def _two_takes(blk: Block, i1, i2) -> Block:
    return blk.take(i1, jnp.ones(i1.shape, bool)) \
        .take(i2, jnp.ones(i2.shape, bool))


def _read_all(blk: Block):
    return dict(blk.columns), dict(blk.nulls)


# -- Block alone ------------------------------------------------------------

@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
def test_two_takes_equal_eager_gathers(jitted):
    """Every dtype a plan carries, null masks included: value and mask
    of row i2∘i1, dtype kept."""
    blk = _block()
    i1, i2 = _indexes()
    fn = (lambda b, a, c: _read_all(_two_takes(b, a, c)))
    cols, nulls = (jax.jit(fn) if jitted else fn)(blk, i1, i2)
    rows = np.asarray(i1)[np.asarray(i2)]
    assert set(cols) == set(DTYPES) and set(nulls) == {"f32", "i32"}
    for got_map, src_map in ((cols, blk.columns), (nulls, blk.nulls)):
        for cid, got in got_map.items():
            src = np.asarray(src_map[cid])
            assert np.asarray(got).dtype == src.dtype
            np.testing.assert_array_equal(np.asarray(got), src[rows])


def test_take_under_shard_map_at_four_devices():
    """Each device takes from its own shard: the indexes are local, no
    collective, and the boundary of the mapped function reads what it
    returns."""
    from citus_tpu.executor.compiler import shard_map

    per, n1, n2 = 250, 120, 40
    blk = _block(4 * per)
    rng = np.random.default_rng(9)
    i1 = rng.integers(0, per, (4, n1)).astype(np.int32)
    i2 = rng.integers(0, n1, (4, n2)).astype(np.int32)
    mesh = Mesh(np.array(jax.devices()[:4]), ("s",))

    def local(b, a, c):
        out = _two_takes(b, a, c)
        return out.select(["i64", "f32"])

    out = jax.jit(shard_map(local, mesh=mesh, in_specs=P("s"),
                            out_specs=P("s")))(
        blk, jnp.asarray(i1.reshape(-1)), jnp.asarray(i2.reshape(-1)))
    for d in range(4):
        rows = d * per + i1[d][i2[d]]
        for cid in ("i64", "f32"):
            np.testing.assert_array_equal(
                np.asarray(out.columns[cid])[d * n2:(d + 1) * n2],
                np.asarray(blk.columns[cid])[rows])
        np.testing.assert_array_equal(
            np.asarray(out.nulls["f32"])[d * n2:(d + 1) * n2],
            np.asarray(blk.nulls["f32"])[rows])
    assert set(out.columns) == {"i64", "f32"} and set(out.nulls) == {"f32"}


def test_column_never_read_leaves_no_gather():
    blk = _block()
    i1, i2 = _indexes()
    assert _gathers(lambda b, a, c: _two_takes(b, a, c).valid,
                    blk, i1, i2) == 0
    # the keys, the length and membership read nothing either
    def keys_only(b, a, c):
        out = _two_takes(b, a, c)
        assert sorted(out.columns) == sorted(DTYPES) and len(out.nulls) == 2
        assert "i64" in out.columns and "nope" not in out.columns
        return out.valid
    assert _gathers(keys_only, blk, i1, i2) == 0


def test_group_of_three_crossing_two_takes_leaves_four_gathers():
    """One composition for the group, then one gather a column: four,
    where a gather at every step is six; a second read costs nothing."""
    blk = _block(with_nulls=False)
    i1, i2 = _indexes()

    def three(b, a, c):
        out = _two_takes(b, a, c)
        got = [out.columns[cid] for cid in ("i64", "f32", "i32")]
        return got + [out.columns["i64"], out.column("f32")]

    assert _gathers(three, blk, i1, i2) == 4
    with deferred_tally() as tally:
        three(blk, i1, i2)
    # 4 columns crossed 2 takes as an index; 4 gathers were issued
    assert (tally.columns, tally.gathers) == (8, 4)


def test_column_read_between_two_takes_is_not_composed():
    """Read at the first level, it crosses the second on its gathered
    array with the new index alone: two gathers, as eager; the unread
    rest of its group still composes."""
    blk = _block(with_nulls=False)
    i1, i2 = _indexes()

    def read_between(b, a, c):
        mid = b.take(a, jnp.ones(a.shape, bool))
        key = mid.columns["i32"]               # a join key, say
        out = mid.take(c, jnp.ones(c.shape, bool))
        return key, out.columns["i32"]

    assert _gathers(read_between, blk, i1, i2) == 2
    key, got = read_between(blk, i1, i2)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(key)[np.asarray(i2)])

    def read_between_and_rest(b, a, c):
        mid = b.take(a, jnp.ones(a.shape, bool))
        key = mid.columns["i32"]
        out = mid.take(c, jnp.ones(c.shape, bool))
        return key, out.columns["i32"], out.columns["i64"], out.columns["f32"]

    # i32 twice, one composition, i64 and f32 through it
    assert _gathers(read_between_and_rest, blk, i1, i2) == 5


def test_widening_take_does_not_compose():
    """A lookup's index over a compacted build side has more rows than
    the block it reads: composing there would gather at the larger
    size, so the column is read at its own size first — three gathers,
    the middle one at the small size, none of them an index
    composition at the large one."""
    blk = _block(with_nulls=False)
    rng = np.random.default_rng(2)
    small = jnp.asarray(rng.integers(0, N, 50).astype(np.int32))
    wide = jnp.asarray(rng.integers(0, 50, 5000).astype(np.int32))
    narrow = jnp.asarray(rng.integers(0, 5000, 300).astype(np.int32))

    def chain(b, s, w, k):
        out = b.take(s, jnp.ones(s.shape, bool)) \
            .take(w, jnp.ones(w.shape, bool)) \
            .take(k, jnp.ones(k.shape, bool))
        return out.columns["i64"]

    jaxpr = jax.make_jaxpr(chain)(blk, small, wide, narrow)
    sizes = sorted(eqn.outvars[0].aval.shape[0] for eqn in jaxpr.eqns
                   if eqn.primitive.name == "gather")
    assert sizes == [50, 300, 300]
    rows = np.asarray(small)[np.asarray(wide)][np.asarray(narrow)]
    np.testing.assert_array_equal(
        np.asarray(chain(blk, small, wide, narrow)),
        np.asarray(blk.columns["i64"])[rows])


def _star_chain(blk: Block, wide, n1, n2):
    """A dimension's block through a lookup's widening take, then two
    compactions: (the block after the first compaction, after the
    second)."""
    mid = blk.take(wide, jnp.ones(wide.shape, bool)) \
        .take(n1, jnp.ones(n1.shape, bool))
    return mid, mid.take(n2, jnp.ones(n2.shape, bool))


def _star_indexes(seed: int = 3):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.integers(0, 50, 5000).astype(np.int32)),
            jnp.asarray(rng.integers(0, 5000, 1200).astype(np.int32)),
            jnp.asarray(rng.integers(0, 1200, 300).astype(np.int32)))


def _gather_sizes(fn, *args) -> list[int]:
    jaxpr = jax.make_jaxpr(fn)(*args)
    return sorted(eqn.outvars[0].aval.shape[0] for eqn in jaxpr.eqns
                  if eqn.primitive.name == "gather")


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
def test_chain_of_three_is_composed_at_the_last_takes_width(jitted):
    """Q89's `d_moy`: a lookup's index over the dimension (a widening
    take, which starts a chain), then two compactions.  The chain is
    composed from the top: the second compaction's index through the
    first's, then through the lookup's — every gather at the last
    take's 300 slots, none at the first compaction's 1,200; as many
    gathers as a composition from the bottom; numpy's value."""
    blk = _block(50, with_nulls=False)
    wide, n1, n2 = _star_indexes()

    def read(b, w, a, c):
        return _star_chain(b, w, a, c)[1].columns["i64"]

    assert _gather_sizes(read, blk, wide, n1, n2) == [300, 300, 300]
    got = (jax.jit(read) if jitted else read)(blk, wide, n1, n2)
    rows = np.asarray(wide)[np.asarray(n1)][np.asarray(n2)]
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(blk.columns["i64"])[rows])
    with deferred_tally() as tally:
        read(blk, wide, n1, n2)
    # two index gathers and the value's, as from the bottom; 900 slots
    # where the bottom-up order read 1,200 + 300 + 300
    assert (tally.gathers, tally.slots) == (3, 900)
    assert (tally.topdown, tally.recomposed) == (1, 0)


@pytest.mark.parametrize("second", ["middle_group", "second_chain"])
def test_middle_index_read_again_is_right_and_counted(second):
    """The cost composing from the top leaves: the first compaction's
    index, passed over by the top-down walk, is gathered through again —
    by its own group read later (a composition at its own 1,200 slots)
    or by a second chain taken from the same block.  The values are
    numpy's all the same, and `recomposed` counts each second pass."""
    blk = _block(50, with_nulls=False)
    wide, n1, n2 = _star_indexes()
    n3 = jnp.asarray(np.arange(0, 1200, 7, dtype=np.int32))

    def read(b, w, a, c, d):
        mid, top = _star_chain(b, w, a, c)
        first = top.columns["i64"]
        if second == "middle_group":
            return first, mid.columns["f32"]
        return first, mid.take(d, jnp.ones(d.shape, bool)).columns["f32"]

    with deferred_tally() as tally:
        first, again = read(blk, wide, n1, n2, n3)
    rows = np.asarray(wide)[np.asarray(n1)]
    np.testing.assert_array_equal(
        np.asarray(first), np.asarray(blk.columns["i64"])[rows[np.asarray(n2)]])
    rows = rows if second == "middle_group" else rows[np.asarray(n3)]
    np.testing.assert_array_equal(np.asarray(again),
                                  np.asarray(blk.columns["f32"])[rows])
    assert (tally.topdown, tally.recomposed) == (
        (1, 1) if second == "middle_group" else (2, 1))
    sizes = _gather_sizes(lambda *a: read(*a), blk, wide, n1, n2, n3)
    if second == "middle_group":
        # the top's three at 300, then the middle's index and value
        assert sizes == [300, 300, 300, 1200, 1200]
    else:
        assert sizes == [172] * 3 + [300] * 3


def test_shared_cell_is_gathered_once_and_the_scope_names_it():
    """`with_filter` and `joined` share the cells: whichever block
    reads first, the gather is made once; it runs under `ct.deferred`
    inside the reader's scope."""
    from citus_tpu.stats.tracing import stage_scope

    blk = _block(with_nulls=False)
    i1, _ = _indexes()

    def shared(b, a):
        taken = b.take(a, jnp.ones(a.shape, bool))
        other = taken.with_filter(taken.valid)
        both = taken.joined(Block({"x": a}, taken.valid), taken.valid)
        with stage_scope("agg_grid"):
            first = other.columns["i64"]
        return first, taken.columns["i64"], both.columns["i64"]

    assert _gathers(shared, blk, i1) == 1
    text = jax.jit(shared).lower(blk, i1).as_text(debug_info=True)
    assert "ct.agg_grid/ct.deferred" in text


def test_columns_is_a_mapping_to_its_readers():
    blk = _block().take(jnp.arange(10, dtype=jnp.int32), jnp.ones(10, bool))
    assert isinstance(blk.columns, Columns)
    assert blk.nulls.get("i64") is None
    got = {**blk.columns}
    assert list(got) == list(DTYPES)
    np.testing.assert_array_equal(np.asarray(got["i32"]),
                                  np.asarray(N - np.arange(10)))
    with pytest.raises(KeyError):
        blk.columns["nope"]
    out = blk.with_column("extra", jnp.zeros(10))
    assert list(out.columns) == list(DTYPES) + ["extra"]
    assert "extra" not in blk.columns


# -- through the compiler's two sites ---------------------------------------

def _compiler(join_out: dict | None = None) -> PlanCompiler:
    """A PlanCompiler as `_compact` and `_exec_lookup_join` see one:
    the accumulators, the capacities, the stage records."""
    pc = object.__new__(PlanCompiler)
    pc._overflow = jnp.zeros((), jnp.int64)
    pc._dense_oob = jnp.zeros((), jnp.int64)
    pc._lookup_probe_slots, pc.n_dev = 0, 1
    pc._stage_actual, pc._stage_width = {}, {}
    pc.caps = Capacities({}, join_out or {})
    return pc


def _star(n_probe: int = 600, n_build: int = 64, seed: int = 4):
    """A probe block whose keys hit two thirds of a build block's
    unique keys (base 100), and the build block, one column nullable."""
    rng = np.random.default_rng(seed)
    bkey = 100 + rng.permutation(n_build).astype(np.int32)
    pkey = (100 + rng.integers(0, n_build * 3 // 2, n_probe)).astype(np.int32)
    build = Block({"b_key": jnp.asarray(bkey),
                   "b_val": jnp.asarray(bkey.astype(np.int64) * 11),
                   "b_opt": jnp.asarray(bkey.astype(np.float32) / 4)},
                  jnp.ones(n_build, bool),
                  {"b_opt": jnp.asarray(bkey % 4 == 0)})
    probe = Block({"p_key": jnp.asarray(pkey),
                   "p_val": jnp.asarray(np.arange(n_probe, dtype=np.int64)),
                   "p_opt": jnp.asarray(np.arange(n_probe, dtype=np.float32))},
                  jnp.ones(n_probe, bool),
                  {"p_opt": jnp.asarray(np.arange(n_probe) % 6 == 0)})
    return probe, build, pkey, bkey


def _lookup(pc, join_type, probe, build, k=None):
    """`_exec_lookup_join` of `probe` into `build`'s dense directory;
    with `k`, its `join_out` compacts to k slots."""
    node = SimpleNamespace(join_type=join_type, build_side="right",
                           right_key_extents=((100, 200),), residual=None,
                           lookup_sorted=False, fuse_lookup=True)
    if k is not None:
        pc.caps.join_out[id(node)] = k
    return pc._exec_lookup_join(
        node, probe, build, [probe.columns["p_key"]], probe.valid,
        [build.columns["b_key"]], build.valid)


def _lookup_then_compact(probe, build, k1=448, k2=416):
    """An inner lookup whose `join_out` compacts to k1 slots, then
    `_compact` once more to k2 → (block, the compiler)."""
    pc = _compiler()
    return pc._compact(_lookup(pc, "inner", probe, build, k1), k2), pc


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
def test_lookup_then_compact_equals_numpy(jitted):
    """An inner lookup whose `join_out` compacts, then `_compact` once
    more: every probe and build column of the surviving rows, in row
    order, masks included — and no overflow, no oob."""
    probe, build, pkey, bkey = _star()
    k2 = 416

    def run(probe, build):
        out, pc = _lookup_then_compact(probe, build, 448, k2)
        return (dict(out.columns), dict(out.nulls), out.valid,
                pc._overflow, pc._dense_oob)

    cols, nulls, valid, overflow, oob = (jax.jit(run) if jitted
                                         else run)(probe, build)
    assert int(overflow) == 0 and int(oob) == 0
    where = {int(k): i for i, k in enumerate(bkey)}
    hits = np.array([i for i, k in enumerate(pkey) if int(k) in where])
    brow = np.array([where[int(pkey[i])] for i in hits])
    assert len(hits) <= k2 and np.asarray(valid).sum() == len(hits)
    for cid, rows, src in (("p_key", hits, probe), ("p_val", hits, probe),
                           ("p_opt", hits, probe), ("b_key", brow, build),
                           ("b_val", brow, build), ("b_opt", brow, build)):
        np.testing.assert_array_equal(
            np.asarray(cols[cid])[:len(hits)],
            np.asarray(src.columns[cid])[rows])
    assert set(nulls) == {"p_opt", "b_opt"}
    np.testing.assert_array_equal(np.asarray(nulls["p_opt"])[:len(hits)],
                                  np.asarray(probe.nulls["p_opt"])[hits])
    np.testing.assert_array_equal(np.asarray(nulls["b_opt"])[:len(hits)],
                                  np.asarray(build.nulls["b_opt"])[brow])


def test_lookup_then_compact_gathers_only_what_is_read():
    """The aggregate reads one probe measure and one build column after
    a lookup and two compactions.  Beyond the lookup's own gathers: the
    probe's group crossed both compactions (one composition, one
    value), the build's group crossed them on the lookup's index (two
    compositions, one value): five.  A gather at every step is 7 + 7,
    and 3 more for the build columns at the probe's size without the
    first compaction."""
    probe, build, _pkey, _bkey = _star()

    def run(probe, build):
        out, _pc = _lookup_then_compact(probe, build)
        return out.columns["p_val"], out.columns["b_val"]

    lookup_only = _gathers(
        lambda p, b: _lookup(_compiler(), "inner", p, b).valid,
        probe, build)
    assert _gathers(run, probe, build) == lookup_only + 5


def test_left_join_null_extension():
    """A probe row without a match keeps its row and reads NULL in every
    build column, nullable or not; a match reads the build row's own
    mask.  The masks are read at the join, the values stay deferred."""
    probe, build, pkey, bkey = _star()
    pc = _compiler()
    out = _lookup(pc, "left", probe, build)
    assert pc._lookup_probe_slots == len(pkey)  # lookup_probe_slots_total
    where = {int(k): i for i, k in enumerate(bkey)}
    found = np.array([int(k) in where for k in pkey])
    brow = np.array([where.get(int(k), 0) for k in pkey])
    assert 0 < found.sum() < len(pkey)
    np.testing.assert_array_equal(np.asarray(out.valid),
                                  np.asarray(probe.valid))
    np.testing.assert_array_equal(np.asarray(out.nulls["b_val"]), ~found)
    np.testing.assert_array_equal(np.asarray(out.nulls["b_key"]), ~found)
    np.testing.assert_array_equal(
        np.asarray(out.nulls["b_opt"]),
        ~found | np.asarray(build.nulls["b_opt"])[brow])
    np.testing.assert_array_equal(np.asarray(out.nulls["p_opt"]),
                                  np.asarray(probe.nulls["p_opt"]))
    np.testing.assert_array_equal(
        np.asarray(out.columns["b_val"])[found],
        np.asarray(build.columns["b_val"])[brow[found]])
    np.testing.assert_array_equal(np.asarray(out.columns["p_val"]),
                                  np.asarray(probe.columns["p_val"]))
    # a left join never compacts here, whatever the capacity table says
    assert out.capacity == probe.capacity


def test_plan_compiler_publishes_the_tally(tmp_path):
    """Through a statement: a join that compacts carries columns as an
    index, the counters move by what the compiler recorded, and the
    executable cache hands the same counts to a reopened session."""
    import citus_tpu
    from citus_tpu.ingest import tpch
    from citus_tpu.stats import counters as sc

    data_dir = str(tmp_path / "q3")
    counts = []
    for _ in range(2):
        sess = citus_tpu.connect(data_dir=data_dir, n_devices=1,
                                 serving_result_cache_bytes=0)
        try:
            if not counts:
                tpch.load_into_session(sess, sf=0.002, seed=7)
                sess.execute(tpch.QUERIES["Q3"]).rows()  # converge
            before = sess.stats.counters.snapshot()
            sess.execute(tpch.QUERIES["Q3"]).rows()
            after = sess.stats.counters.snapshot()
            counts.append(tuple(after[k] - before[k] for k in (
                sc.DEFERRED_COLUMNS_TOTAL, sc.DEFERRED_GATHERS_TOTAL,
                sc.EXEC_CACHE_HITS_TOTAL)))
        finally:
            sess.close()
    carried, gathered, _ = counts[0]
    assert carried > gathered > 0
    # the reopened session loaded the program, and its counts with it
    assert counts[1][:2] == (carried, gathered) and counts[1][2] >= 1


# -- a fused inner lookup's probe side cut to the build keys' range ---------

def _range_lookup(pc, probe, build, k_range=None, pmatch=None, bmatch=None,
                  keys=("p_key", "b_key")):
    """An inner `_exec_lookup_join` of `probe` into `build` whose probe
    side compacts to `k_range` slots before the probe; `keys` names the
    probe and build key columns (a tuple of pairs: several keys, probed
    by the sorted-bounds arm)."""
    pairs = [keys] if isinstance(keys[0], str) else list(keys)
    node = SimpleNamespace(
        join_type="inner", build_side="right", residual=None,
        right_key_extents=((100, 200),) if len(pairs) == 1 else (),
        lookup_sorted=False, fuse_lookup=True, key_pack=None)
    pc._lookup_range_compactions = 0
    pc._join_sort_operands = 0
    if k_range is not None:
        pc.caps.join_range[id(node)] = k_range
    return pc._exec_lookup_join(
        node, probe, build, [probe.columns[p] for p, _b in pairs],
        probe.valid if pmatch is None else pmatch,
        [build.columns[b] for _p, b in pairs],
        build.valid if bmatch is None else bmatch), node


LO, HI = 110, 125   # the build keys one interval keeps


def _interval_build(build, lo: int = LO, hi: int = HI):
    """The build rows whose key lies in [lo, hi): one key interval, as a
    date filter keeps."""
    key = np.asarray(build.columns["b_key"])
    return jnp.asarray((key >= lo) & (key < hi))


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
def test_range_compaction_equals_the_probe_of_every_row(jitted):
    """A build side filtered to one key interval: the probe rows inside
    it compact to the range's capacity before the probe, which runs
    there, and every probe and build column of the matches — in row
    order, masks included — is what the probe of every row gives."""
    probe, build, pkey, bkey = _star()
    bmatch = _interval_build(build)
    inside = (pkey >= LO) & (pkey < HI)
    k = 128 * -(-int(inside.sum()) // 128)
    assert k * 3 < len(pkey)

    def run(probe, build, bmatch):
        pc = _compiler()
        out, node = _range_lookup(pc, probe, build, k, bmatch=bmatch)
        return (dict(out.columns), dict(out.nulls), out.valid,
                pc._overflow, pc._dense_oob,
                pc._stage_actual[(id(node), "join_range")])

    cols, nulls, valid, overflow, oob, recorded = \
        (jax.jit(run) if jitted else run)(probe, build, bmatch)
    assert int(overflow) == 0 and int(oob) == 0
    assert int(recorded) == int(inside.sum())
    assert np.asarray(valid).shape == (k,)
    where = {int(b): i for i, b in enumerate(bkey) if LO <= b < HI}
    hits = np.array([i for i, p in enumerate(pkey) if int(p) in where])
    brow = np.array([where[int(pkey[i])] for i in hits])
    assert np.asarray(valid).sum() == len(hits) > 0
    got = np.flatnonzero(np.asarray(valid))
    for cid, rows, src in (("p_key", hits, probe), ("p_val", hits, probe),
                           ("p_opt", hits, probe), ("b_val", brow, build),
                           ("b_opt", brow, build)):
        np.testing.assert_array_equal(np.asarray(cols[cid])[got],
                                      np.asarray(src.columns[cid])[rows])
    np.testing.assert_array_equal(np.asarray(nulls["p_opt"])[got],
                                  np.asarray(probe.nulls["p_opt"])[hits])
    np.testing.assert_array_equal(np.asarray(nulls["b_opt"])[got],
                                  np.asarray(build.nulls["b_opt"])[brow])


def test_range_compaction_probes_at_its_capacity_and_is_tallied():
    """The probe and its slot count (`lookup_probe_slots_total`) run at
    the range's capacity; the program tallies one range compaction
    (`lookup_range_compactions_total`).  Without a capacity the range is
    only recorded: the probe side keeps its width and nothing is
    tallied."""
    probe, build, _pkey, _bkey = _star()
    bmatch = _interval_build(build)
    pc = _compiler()
    out, _node = _range_lookup(pc, probe, build, 128, bmatch=bmatch)
    assert out.capacity == 128 and pc._lookup_probe_slots == 128
    assert pc._lookup_range_compactions == 1
    pc = _compiler()
    out, node = _range_lookup(pc, probe, build, bmatch=bmatch)
    assert out.capacity == probe.capacity
    assert pc._lookup_probe_slots == probe.capacity
    assert pc._lookup_range_compactions == 0
    assert pc._stage_width[(id(node), "join_range")] == probe.capacity


def test_range_capacity_that_no_longer_pays_is_left_unused():
    """A range capacity installed against a wider probe side, whose
    width has shrunk since under a compaction installed with it: where
    compacting this program's probe side to it saves less than
    `compaction_pays` asks, the probe side keeps its width."""
    probe, build, _pkey, _bkey = _star()
    pc = _compiler()
    out, node = _range_lookup(pc, probe, build, 256,
                              bmatch=_interval_build(build))
    assert 256 * 3 >= probe.capacity
    assert out.capacity == probe.capacity
    assert pc._lookup_range_compactions == 0 and int(pc._overflow) == 0


def test_range_past_its_capacity_counts_as_overflow():
    """More probe rows inside the range than its capacity: the surplus
    is capacity overflow, so the host retries with the capacity grown,
    as for every compaction."""
    probe, build, pkey, _bkey = _star()
    inside = int(((pkey >= LO) & (pkey < HI)).sum())
    pc = _compiler()
    _range_lookup(pc, probe, build, 64, bmatch=_interval_build(build))
    assert inside > 64 and int(pc._overflow) == inside - 64


def test_range_of_an_empty_build_side_keeps_no_row():
    """No matchable build row: its range is empty (min above max), no
    probe row lies inside it, and the lookup keeps none."""
    probe, build, _pkey, _bkey = _star()
    pc = _compiler()
    out, node = _range_lookup(pc, probe, build, 128,
                              bmatch=jnp.zeros(build.capacity, bool))
    assert int(pc._stage_actual[(id(node), "join_range")]) == 0
    assert int(np.asarray(out.valid).sum()) == 0
    assert int(pc._overflow) == 0 and int(pc._dense_oob) == 0


def test_null_probe_keys_lie_outside_every_range():
    """A probe row whose key is NULL (outside the match mask) is not
    counted inside the range even where its stored value is, and does
    not match after the compaction."""
    probe, build, pkey, bkey = _star()
    bmatch = _interval_build(build)
    inside = (pkey >= LO) & (pkey < HI)
    null_key = inside & (np.arange(len(pkey)) % 2 == 0)
    pmatch = jnp.asarray(~null_key)
    pc = _compiler()
    out, node = _range_lookup(pc, probe, build, 128, pmatch=pmatch,
                              bmatch=bmatch)
    keep = int((inside & ~null_key).sum())
    assert int(pc._stage_actual[(id(node), "join_range")]) == keep
    matched = [i for i in range(len(pkey))
               if inside[i] and not null_key[i]
               and int(pkey[i]) in set(bkey.tolist())]
    got = np.asarray(out.columns["p_val"])[np.asarray(out.valid)]
    np.testing.assert_array_equal(got, np.asarray(matched))


def test_range_of_several_keys_holds_every_key():
    """A two-key lookup (the sorted-bounds arm): a probe row is inside
    only where each key lies inside its own build range, and the
    matches are those of the probe of every row."""
    rng = np.random.default_rng(9)
    n_build, n_probe = 48, 400
    b1 = rng.permutation(n_build).astype(np.int32) + 10
    b2 = (b1 % 6).astype(np.int32)
    p1 = rng.integers(0, 120, n_probe).astype(np.int32)
    p2 = rng.integers(0, 12, n_probe).astype(np.int32)
    build = Block({"b1": jnp.asarray(b1), "b2": jnp.asarray(b2),
                   "bv": jnp.asarray(b1.astype(np.int64) * 3)},
                  jnp.ones(n_build, bool), {})
    probe = Block({"p1": jnp.asarray(p1), "p2": jnp.asarray(p2),
                   "pv": jnp.asarray(np.arange(n_probe, dtype=np.int64))},
                  jnp.ones(n_probe, bool), {})
    keys = (("p1", "b1"), ("p2", "b2"))
    inside = (p1 >= 10) & (p1 < 10 + n_build) & (p2 < 6)
    pc = _compiler()
    out, node = _range_lookup(pc, probe, build, 128, keys=keys)
    assert out.capacity == 128 and pc._lookup_range_compactions == 1
    assert int(pc._stage_actual[(id(node), "join_range")]) == inside.sum()
    assert int(pc._overflow) == 0 and int(pc._dense_oob) == 0
    pairs = {(int(a), int(b)): i for i, (a, b) in enumerate(zip(b1, b2))}
    hits = [i for i in range(n_probe)
            if (int(p1[i]), int(p2[i])) in pairs]
    valid = np.asarray(out.valid)
    np.testing.assert_array_equal(np.asarray(out.columns["pv"])[valid], hits)
    np.testing.assert_array_equal(
        np.asarray(out.columns["bv"])[valid],
        [b1[pairs[(int(p1[i]), int(p2[i]))]] * 3 for i in hits])


# -- through statements: the capacity feedback installs the range ----------

STAR_ROWS, DATES, SPAN = 24_000, 1_200, 120


def _star_tables(seed: int = 7) -> dict:
    """A fact table `f` whose `dk` refers to a calendar `d` (one row a
    key, `yr` = dk // 120: a year is one interval of keys, as TPC-DS's
    date keys are), 5 % of `dk` NULL; and a unique `u` over a smaller
    `m` that refers to it with repeats, `u.grp` = 1 on 500 of its 4,000
    keys."""
    rng = np.random.default_rng(seed)
    dk = rng.integers(0, DATES, STAR_ROWS)
    dk_null = rng.random(STAR_ROWS) < 0.05
    perm = rng.permutation(DATES)
    uk = np.arange(48_000)
    return {
        "f": {"id": np.arange(STAR_ROWS),
              "dk": [None if n else int(x) for x, n in zip(dk, dk_null)],
              "g": np.arange(STAR_ROWS) % 7,
              "v": rng.integers(0, 1000, STAR_ROWS)},
        "d": {"dk": perm, "yr": perm // SPAN, "tag": perm % 10},
        "u": {"uk": uk, "grp": ((uk >= 500) & (uk < 1000)).astype(int),
              "w": uk},
        "m": {"mk": rng.integers(0, 4000, 5000), "x": np.arange(5000)},
    }


STAR_DDL = {"f": ("create table f (id bigint, dk int, g int, v bigint)",
                  "id"),
            "d": ("create table d (dk int, yr int, tag int)", None),
            "u": ("create table u (uk int, grp int, w bigint)", "uk"),
            "m": ("create table m (mk int, x bigint)", "mk")}
STAR_TYPES = {"id": np.int64, "v": np.int64, "w": np.int64, "x": np.int64}


def _load_star(sess, tables: dict) -> None:
    from citus_tpu.ingest.copy_from import _ingest_batch

    for name, (ddl, dist) in STAR_DDL.items():
        sess.execute(ddl)
        if dist is None:
            sess.create_reference_table(name)
        else:
            sess.create_distributed_table(name, dist, shard_count=4)
        cols = tables[name]
        batch = [cols[c] if isinstance(cols[c], list)
                 else np.asarray(cols[c]).astype(STAR_TYPES.get(c, np.int32))
                 for c in cols]
        _ingest_batch(sess, name, list(cols), batch, pre_typed=True)


@pytest.fixture(scope="module")
def star_oracle():
    from oracle import make_oracle

    return make_oracle(_star_tables(), {})


@pytest.fixture(scope="module", params=[1, 4], ids=["1dev", "4dev"])
def star_sess(request, tmp_path_factory):
    import citus_tpu

    sess = citus_tpu.connect(
        data_dir=str(tmp_path_factory.mktemp(f"range{request.param}")),
        n_devices=request.param, serving_result_cache_bytes=0)
    _load_star(sess, _star_tables())
    yield sess
    sess.close()


RANGE_SQL = {
    "interval": "select d.yr, f.g, count(*), sum(f.v) from f, d "
                "where f.dk = d.dk and d.yr = 3 group by d.yr, f.g",
    # the year of key 0, which is where a NULL key's storage sits
    "first_interval": "select f.g, count(*), sum(f.v) from f, d "
                      "where f.dk = d.dk and d.yr = 0 group by f.g",
    "every_key": "select f.g, count(*), sum(f.v) from f, d "
                 "where f.dk = d.dk and d.tag = 3 group by f.g",
    "empty": "select f.g, count(*) from f, d "
             "where f.dk = d.dk and d.yr = 99 group by f.g",
    "build_left": "select u.grp, count(*), sum(m.x), sum(u.w) from u, m "
                  "where u.uk = m.mk and u.grp = 1 group by u.grp",
    "left_join": "select f.g, count(d.yr), count(*), sum(f.v) from f "
                 "left join d on f.dk = d.dk and d.yr = 3 group by f.g",
}


def _converged(sess, oracle, name: str) -> tuple[dict, list]:
    """Three executions of RANGE_SQL[name], each held to sqlite; → the
    counters' moves over the last, and the plan-cache entries the
    statement added (its programs: the first and the converged one)."""
    from oracle import compare_results, run_oracle

    sql = RANGE_SQL[name]
    want = run_oracle(oracle, sql)
    known = set(sess.executor.plan_cache._entries)
    for _ in range(3):
        before = sess.stats.counters.snapshot()
        rows = sess.execute(sql).rows()
        after = sess.stats.counters.snapshot()
        compare_results(rows, want, ordered=False)
    added = [e for k, e in sess.executor.plan_cache._entries.items()
             if k not in known]
    assert added
    return {k: after[k] - before.get(k, 0) for k in after}, added


def _range_widths(entries) -> list[int]:
    """The probe-side widths at which the programs recorded a range."""
    return [width for e in entries for _w, kind, width in e[2]
            if kind == "join_range"]


def _compacts_to_range(entries) -> bool:
    return any("ct.join_range/ct.compact" in e[0].as_text()
               for e in entries)


@pytest.mark.parametrize("name", ["interval", "first_interval",
                                  "build_left"])
def test_lookup_into_one_key_interval_probes_its_rows_alone(
        star_sess, star_oracle, name):
    """A build side that keeps one interval of the keys (a year of the
    calendar; `u`'s 500 keys, probed from `m` with the build on the
    left): capacity feedback installs the range's capacity, and the
    converged program compacts the probe side to it before the probe
    and probes there — its probe slots are under a third of the probe
    side's width.  NULL keys (5 % of `f.dk`, stored where the first
    year's keys are) lie outside.  Answers are sqlite's."""
    moved, added = _converged(star_sess, star_oracle, name)
    assert moved["lookup_range_compactions_total"] == 1
    assert moved["capacity_retries"] == 0
    n_dev = star_sess.executor.mesh.devices.size
    width = max(_range_widths(added))   # the first program's, uncut
    assert moved["lookup_probe_slots_total"] * 3 < n_dev * width
    assert _compacts_to_range(added)


def test_range_covering_every_key_installs_nothing(star_sess, star_oracle):
    """A build side that keeps a tenth of the keys spread over the whole
    calendar: its range covers every key, no range capacity is
    installed, and the probe keeps the fact side's width; only the
    range's count is left in the program."""
    moved, added = _converged(star_sess, star_oracle, "every_key")
    assert moved["lookup_range_compactions_total"] == 0
    assert moved["lookup_probe_slots_total"] >= STAR_ROWS
    assert _range_widths(added) and not _compacts_to_range(added)


def test_empty_build_side_keeps_no_row(star_sess, star_oracle):
    """A year with no calendar row: the range is empty (its minimum
    above its maximum), no probe row lies inside it, and the statement
    answers no row, as sqlite does; the smallest capacity is installed
    for the range, so the probe runs over it."""
    moved, added = _converged(star_sess, star_oracle, "empty")
    assert star_sess.execute(RANGE_SQL["empty"]).rows() == []
    assert moved["lookup_range_compactions_total"] == 1
    assert moved["lookup_probe_slots_total"] == \
        128 * star_sess.executor.mesh.devices.size
    assert moved["capacity_retries"] == 0


def test_left_lookup_is_untouched(star_sess, star_oracle):
    """A LEFT lookup keeps every probe row: it records no range and
    never compacts to one, whatever the ON clause keeps of the build
    side."""
    moved, added = _converged(star_sess, star_oracle, "left_join")
    assert moved["lookup_range_compactions_total"] == 0
    assert moved["lookup_probe_slots_total"] >= STAR_ROWS
    assert _range_widths(added) == []
    assert not any("ct.join_range" in e[0].as_text() for e in added)


@pytest.mark.parametrize("n_dev", [1, 4])
def test_rows_past_the_range_capacity_retry_once(tmp_path, n_dev):
    """The range's capacity converged on one year; an UPDATE then moves
    half the fact rows into it.  The next execution overflows the
    range's capacity, retries once with it grown and answers as sqlite
    does; the one after runs without a retry."""
    import citus_tpu
    from oracle import compare_results, make_oracle, run_oracle

    tables = _star_tables()
    sess = citus_tpu.connect(data_dir=str(tmp_path / "d"), n_devices=n_dev,
                             serving_result_cache_bytes=0)
    try:
        _load_star(sess, tables)
        sql = RANGE_SQL["interval"]
        for _ in range(3):
            before = sess.stats.counters.snapshot()
            sess.execute(sql).rows()
        after = sess.stats.counters.snapshot()
        assert after["lookup_range_compactions_total"] \
            - before["lookup_range_compactions_total"] == 1
        update = "update f set dk = 360 + id % 120 where id % 2 = 0"
        sess.execute(update)
        oracle = make_oracle(tables, {})
        oracle.execute(update)
        want = run_oracle(oracle, sql)
        retries = []
        for _ in range(2):
            result = sess.execute(sql)
            compare_results(result.rows(), want, ordered=False)
            retries.append(result.retries)
        assert retries == [1, 0]
    finally:
        sess.close()
