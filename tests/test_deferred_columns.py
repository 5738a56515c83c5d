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
