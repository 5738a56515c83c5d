"""Kernel micro-benchmarks: Pallas vs XLA formulations on real hardware.

Runs the dense-grid segment aggregation both ways across the (N, K)
regimes the executor actually hits, prints a table, and says which
implementation the executor should route to.  This is the measurement
the BASELINE north star asks for — hand kernels where they win, measured
justification where XLA already wins.

Usage:  python bench_kernels.py          (real TPU)
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np


REPS = 16


def timeit(op, slot, values, repeats=3):
    """Per-op device time via slope timing: a single execution carries
    the host's dispatch and fetch round trip.  Run the op REPS times
    inside ONE jitted program (an epsilon perturbation defeats CSE) and
    take (t_reps - t_once) / (R-1).
    """

    def many(s, v, r):
        def body(i, acc):
            out = op(s, v + i.astype(v.dtype) * jnp.float32(1e-30))
            return acc + jnp.sum(out)

        return jax.lax.fori_loop(0, r, body, jnp.float32(0.0))

    f = jax.jit(many, static_argnums=2)
    jax.device_get(f(slot, values, 1))
    jax.device_get(f(slot, values, REPS))
    t1 = tr = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.device_get(f(slot, values, 1))
        t1 = min(t1, time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.device_get(f(slot, values, REPS))
        tr = min(tr, time.perf_counter() - t0)
    return max((tr - t1) / (REPS - 1), 1e-9)


def xla_segment_sum(slot, values, total):
    return jax.ops.segment_sum(values, slot, num_segments=total + 1)[:total]


def xla_onehot_matmul(slot, values, total):
    # the same one-hot trick expressed in plain XLA (no Pallas)
    k_pad = -(-total // 512) * 512
    onehot = (slot[:, None] ==
              jnp.arange(k_pad, dtype=jnp.int32)[None, :]).astype(
        jnp.float32)
    return jax.lax.dot_general(
        onehot, values, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)[:total]


def main(regimes=None):
    from citus_tpu.ops.pallas_kernels import (
        dense_grid_aggregate_pallas,
        segment_sum_reference,
    )

    print(f"backend: {jax.devices()[0].platform} "
          f"({jax.devices()[0].device_kind})")
    rng = np.random.default_rng(0)
    rows = []
    if regimes is None:
        regimes = [(1 << 20, 16), (1 << 20, 512), (1 << 20, 4096),
                   (1 << 23, 16), (1 << 23, 512), (1 << 23, 4096),
                   (1 << 23, 8192)]
    for n, k in regimes:
        slot = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
        vals = jnp.asarray(rng.uniform(0, 100, (n, 6)).astype(np.float32))

        t_seg = timeit(lambda s, v, total=k: xla_segment_sum(s, v, total),
                       slot, vals)
        t_oh = timeit(lambda s, v, total=k: xla_onehot_matmul(s, v, total),
                      slot, vals)
        t_pl = None
        ok = True
        try:
            f_pl = (lambda s, v, total=k:
                    dense_grid_aggregate_pallas(s, v, total))
            got = np.asarray(f_pl(slot, vals))
            want = segment_sum_reference(np.asarray(slot),
                                         np.asarray(vals), k)
            ok = np.allclose(got, want, rtol=1e-3, atol=1.0)
            t_pl = timeit(f_pl, slot, vals)
        except Exception as e:
            t_pl = None
            print(f"  pallas failed at n={n} k={k}: "
                  f"{str(e).splitlines()[0][:120]}")
        rows.append((n, k, t_seg, t_oh, t_pl, ok))
        print(f"n={n:>9} k={k:>5}  xla_segsum={t_seg * 1e3:8.2f}ms  "
              f"xla_onehot={t_oh * 1e3:8.2f}ms  "
              f"pallas={'n/a' if t_pl is None else f'{t_pl * 1e3:8.2f}ms'}"
              f"  correct={ok}")

    best_counts = {"segsum": 0, "onehot": 0, "pallas": 0}
    for n, k, t_seg, t_oh, t_pl, ok in rows:
        opts = {"segsum": t_seg, "onehot": t_oh}
        if t_pl is not None and ok:
            opts["pallas"] = t_pl
        best_counts[min(opts, key=opts.get)] += 1
    print("winner histogram:", best_counts)
    return rows


def _slope_time_once(fn, repeats=3, reps=6):
    """Per-op device time for fn(i) -> int64 scalar, slope-timed (see
    timeit: a single execution carries the dispatch round trip), with a
    traced repeat count: ONE compile a variant (a 7.5 M-row sort
    compiles for half a minute or more).  Returns (seconds an iteration,
    seconds of the first call, fn(0))."""
    f = jax.jit(lambda r: jax.lax.fori_loop(
        0, r, lambda i, acc: acc + fn(i), jnp.zeros((), jnp.int64)))
    t0 = time.perf_counter()
    first = int(jax.device_get(f(1)))
    compile_s = time.perf_counter() - t0
    jax.device_get(f(reps))
    t1 = tr = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.device_get(f(1))
        t1 = min(t1, time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.device_get(f(reps))
        tr = min(tr, time.perf_counter() - t0)
    return max((tr - t1) / (reps - 1), 1e-9), compile_s, first


def _slope_time(fn, repeats=3, reps=8):
    return _slope_time_once(fn, repeats, reps)[0]


def bench_lookup(mode="full", small=False, out="chiprun_out"):
    """The two arms of a fused unique-key lookup join (PR 28): the
    single gather from a dense directory (`dense_unique_lookup`) against
    sort-and-scan (`sorted_unique_lookup`), over key extents from 2^18
    to TPC-H SF1's 6.0 M order-key slots and over the shapes Q3 gives
    the join on one chip (build 1.5 M, probe 6.0 M) and on four (375 k,
    1.5 M).  The dense arm's time a probe row against the extent is the
    knee `ops.join.SORTED_LOOKUP_MIN_EXTENT` is set from; the sorted
    arm's is flat in the extent.  Both sides' keys move with the
    iteration, so nothing is hoisted out of the timed loop: each
    iteration builds its directory, or sorts its build side, as a
    statement does.

    mode `knee`: the dense arm alone below 2^18 slots under 1.5 M probe
    rows, and the sorted arm once at that shape.  Mode `q3`: the sorted
    arm alone at Q3's two shapes, and its parts.  Each mode writes
    `<out>/bench_lookup_<mode>.json`.

    Usage:  python bench_kernels.py lookup       (the chip)
            python bench_kernels.py lookup knee  (the chip)
            python bench_kernels.py lookup q3    (the chip)
            python bench_kernels.py lookup small (a rehearsal anywhere)
    """
    import json
    import os

    from citus_tpu.runtime import ensure_jax_configured

    ensure_jax_configured()
    import citus_tpu.ops.join as J
    dev = jax.devices()[0]
    print(f"backend: {dev.platform} ({dev.device_kind})")
    rng = np.random.default_rng(0)
    base = 1

    def inputs(extent, m, n):
        bk0 = jnp.asarray(rng.permutation(extent)[:m].astype(np.int32))
        pk0 = jnp.asarray(rng.integers(0, extent, n).astype(np.int32))
        bm = jnp.asarray(rng.random(m) < 0.9)
        return bk0, bm, pk0

    def arm(name, extent, bk0, bm, pk0):
        def run(i):
            i = i.astype(jnp.int32)
            bk = base + (bk0 + i) % extent
            pk = base + (pk0 + 7 * i) % extent
            if name == "dense":
                b, c, o = J.dense_unique_lookup(bk, bm, pk, base, extent)
            else:
                b, c, o = J.sorted_unique_lookup(bk, bm, pk)
            # every output is consumed, so nothing is dead code
            return (jnp.where(c > 0, b, 0).sum(dtype=jnp.int64)
                    + c.sum(dtype=jnp.int64) + o)
        return run

    scale = 64 if small else 1
    q3_1 = (6_000_000 // scale, 1_500_032 // scale, 6_001_536 // scale)
    q3_4 = (6_000_000 // scale, 375_168 // scale, 1_501_440 // scale)
    cases = []
    for e in (1 << 18, 1 << 19, 1 << 20, 1 << 21, 1 << 22):
        e //= scale
        cases.append((e, e // 4, e, ("dense",)))
    cases.append(((1 << 18) // scale, (1 << 16) // scale,
                  (1 << 18) // scale, ("sort",)))
    cases.append(q3_1 + (("dense", "sort"),))
    cases.append(q3_4 + (("dense", "sort"),))
    # a large extent with a small probe side: where the sort of m+n rows
    # stops paying against n gathers
    cases.append((q3_1[0], q3_1[1], 65_536 // scale, ("dense", "sort")))
    if mode == "knee":
        n = 1_501_440 // scale
        cases = [(e // scale, e // scale // 4, n, ("dense",))
                 for e in (1 << 14, 1 << 15, 1 << 16, 1 << 17, 150_016,
                           3 << 16, 1 << 18, 1 << 20)]
        cases.append(((1 << 17) // scale, (1 << 15) // scale, n,
                      ("sort",)))
    elif mode == "q3":
        cases = [q3_1 + (("sort",),), q3_4 + (("sort",),)]
    rows = []
    for extent, m, n, arms in cases:
        bk0, bm, pk0 = inputs(extent, m, n)
        want = None
        for name in arms:
            t, compile_s, got = _slope_time_once(
                arm(name, extent, bk0, bm, pk0))
            want = got if want is None else want
            rows.append({"arm": name, "extent": extent, "build": m,
                         "probe": n, "ms": t * 1e3,
                         "ns_per_probe_row": t * 1e9 / n,
                         "ns_per_row": t * 1e9 / (m + n),
                         "compile_s": compile_s, "agrees": got == want})
            print(json.dumps(rows[-1]), flush=True)
    # the parts of the sorted arm at Q3's one-chip size
    tot = 0 if mode == "knee" else q3_1[1] + q3_1[2]
    k0 = jnp.asarray(rng.integers(0, 1 << 30, tot).astype(np.int32))
    x0 = jnp.arange(tot, dtype=jnp.int32)
    parts = {
        "sort_2key_stable": lambda k: jax.lax.sort(
            (k, x0), num_keys=2)[1],
        "sort_2key": lambda k: jax.lax.sort(
            (k, x0), num_keys=2, is_stable=False)[1],
        "sort_1key": lambda k: jax.lax.sort(
            (k, x0), num_keys=1, is_stable=False)[1],
        "cumsum_cummax": lambda k: jnp.cumsum(k >> 8, dtype=jnp.int32)
        + jax.lax.cummax(k),
    }
    for name, part in parts.items() if tot else ():
        t, compile_s, _ = _slope_time_once(
            lambda i: part(k0 ^ i.astype(jnp.int32))[tot // 2]
            .astype(jnp.int64))
        rows.append({"part": name, "rows": tot, "ms": t * 1e3,
                     "ns_per_row": t * 1e9 / tot, "compile_s": compile_s})
        print(json.dumps(rows[-1]), flush=True)
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"bench_lookup_{mode}.json"), "w") as f:
        json.dump({"device": [dev.platform, dev.device_kind],
                   "rows": rows}, f, indent=1)
    return rows


def bench_compact(small=False, out="chiprun_out"):
    """`PlanCompiler._compact`'s two ways to the survivors' positions
    (PR 30): the inclusive `cumsum` of the validity mask and a
    unique-index scatter of each survivor's position into its rank (the
    form every program ran until PR 30, kept here alone) against
    `survivor_positions`' one sort of the positions, over the sizes the
    benchmark's programs compact (a dimension table of 30 k rows, one
    of 150 k, a chip's quarter of `lineitem`, SSB's second compaction,
    a whole fact table) at a shrink of 30 and of 3.3, the rule's edge.
    The mask moves with the iteration, so nothing is hoisted out of the
    timed loop; both forms' positions are consumed alike.  The parts at
    the largest size price what the sort replaced and what stays.
    Writes `<out>/bench_compact.json`.

    Usage:  python bench_kernels.py compact        (the chip)
            python bench_kernels.py compact small  (a rehearsal anywhere)
    """
    import json
    import os

    from citus_tpu.runtime import ensure_jax_configured

    ensure_jax_configured()
    from citus_tpu.executor.compiler import _round_cap, survivor_positions
    dev = jax.devices()[0]
    print(f"backend: {dev.platform} ({dev.device_kind})")
    rng = np.random.default_rng(0)

    def positions_scatter(valid, k):
        n = valid.shape[0]
        rank = jnp.cumsum(valid.astype(jnp.int32)) - 1
        por = jnp.zeros(k, jnp.int32).at[
            jnp.where(valid & (rank < k), rank, k)].set(
            jnp.arange(n, dtype=jnp.int32), mode="drop")
        return por, rank[n - 1] + 1

    forms = {"scatter": positions_scatter, "sort": survivor_positions}

    def mask(r0, i, k):
        # four fifths of the k slots filled, the draw shifted by i
        thr = int(65536 * 0.8 * k / r0.shape[0])
        return ((r0 + i.astype(jnp.int32) * 7919) & 0xFFFF) < thr

    def run(form, r0, k):
        def fn(i):
            por, n_valid = form(mask(r0, i, k), k)
            live = jnp.arange(k, dtype=jnp.int32) < n_valid
            return (jnp.where(live, por, 0).sum(dtype=jnp.int64)
                    + n_valid)
        return fn

    scale = 64 if small else 1
    rows = []
    sizes = [_round_cap(n // scale) for n in
             (30_000, 150_000, 1_501_440, 1_800_320, 6_001_536)]
    for n in sizes:
        r0 = jnp.asarray(rng.integers(0, 1 << 16, n).astype(np.int32))
        for shrink in (30, 3.3):
            k = _round_cap(int(n / shrink))
            want = None
            for name, form in forms.items():
                t, compile_s, got = _slope_time_once(run(form, r0, k))
                want = got if want is None else want
                rows.append({"form": name, "n": n, "k": k, "ms": t * 1e3,
                             "ns_per_row": t * 1e9 / n,
                             "compile_s": compile_s,
                             "agrees": got == want})
                print(json.dumps(rows[-1]), flush=True)
    # the parts, at the largest size: the scan that went with the
    # scatter, and the column gather at the compacted size that stays
    n = sizes[-1]
    r0 = jnp.asarray(rng.integers(0, 1 << 16, n).astype(np.int32))
    parts = {"cumsum": (n, lambda i: jnp.cumsum(
        mask(r0, i, n // 30).astype(jnp.int32))[n // 2].astype(jnp.int64))}
    for shrink in (30, 3.3):
        k = _round_cap(int(n / shrink))
        idx = jnp.asarray(np.sort(rng.permutation(n)[:k]).astype(np.int32))
        parts[f"gather_k_{k}"] = (k, lambda i, idx=idx, k=k: (
            r0 + i.astype(jnp.int32))[idx].sum(dtype=jnp.int64))
    for name, (elems, part) in parts.items():
        t, compile_s, _ = _slope_time_once(part)
        rows.append({"part": name, "n": n, "elements": elems,
                     "ms": t * 1e3, "ns_per_element": t * 1e9 / elems,
                     "compile_s": compile_s})
        print(json.dumps(rows[-1]), flush=True)
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "bench_compact.json"), "w") as f:
        json.dump({"device": [dev.platform, dev.device_kind],
                   "rows": rows}, f, indent=1)
    return rows


def bench_carry(small=False, out="chiprun_out"):
    """What it costs to carry C = 1…5 columns through two compactions,
    6,001,536 → 1,800,320 → 360,576 slots (`ssb1.q4_1`'s), read after
    the second (PR 32): a gather of every column at each step (the form
    every program ran until PR 32, kept here alone) against
    `Block.take`, which carries them as a row index — one composition
    of the two indexes at the last size, then one gather a column
    there.  A third form reads two of five columns after the first
    step, as Q4.1 reads two join keys there.  Both compactions'
    positions are given (their sorts are `compact`'s sweep); the
    columns move with the iteration, so nothing is hoisted out of the
    timed loop.  Writes `<out>/bench_carry.json`.

    Usage:  python bench_kernels.py carry        (the chip)
            python bench_kernels.py carry small  (a rehearsal anywhere)
    """
    import json
    import os

    from citus_tpu.runtime import ensure_jax_configured

    ensure_jax_configured()
    from citus_tpu.executor.batch import Block
    from citus_tpu.executor.compiler import _round_cap
    dev = jax.devices()[0]
    print(f"backend: {dev.platform} ({dev.device_kind})")
    rng = np.random.default_rng(0)
    scale = 64 if small else 1
    n0, k1, k2 = (_round_cap(n // scale)
                  for n in (6_001_536, 1_800_320, 360_576))
    por1 = jnp.asarray(np.sort(rng.permutation(n0)[:k1]).astype(np.int32))
    por2 = jnp.asarray(np.sort(rng.permutation(k1)[:k2]).astype(np.int32))
    base = [jnp.asarray(rng.integers(0, 1 << 20, n0).astype(np.int32))
            for _ in range(5)]
    live1, live2 = jnp.ones(k1, bool), jnp.ones(k2, bool)

    def total(arrays):
        return sum(a.sum(dtype=jnp.int64) for a in arrays)

    def eager(cols, keys):
        mid = [c[por1] for c in cols]
        return total([m[por2] for m in mid] + mid[:keys])

    def deferred(cols, keys):
        blk = Block({str(j): c for j, c in enumerate(cols)},
                    jnp.ones(n0, bool))
        mid = blk.take(por1, live1)
        read = [mid.columns[str(j)] for j in range(keys)]
        last = mid.take(por2, live2)
        return total([last.columns[str(j)] for j in range(len(cols))]
                     + read)

    forms = {"eager": eager, "deferred": deferred}
    rows = []
    for n_cols, keys in [(c, 0) for c in range(1, 6)] + [(5, 2)]:
        # slots gathered: every column at both steps | the columns read
        # in between at the first, one composition if any column is
        # left to carry, every column at the second
        slots = {"eager": n_cols * (k1 + k2),
                 "deferred": keys * k1 + (n_cols > keys) * k2 + n_cols * k2}
        want = None
        for name, form in forms.items():
            def fn(i, form=form):
                return form([b + i.astype(jnp.int32)
                             for b in base[:n_cols]], keys)
            t, compile_s, got = _slope_time_once(fn)
            want = got if want is None else want
            rows.append({"form": name, "columns": n_cols,
                         "read_after_first": keys,
                         "sizes": [n0, k1, k2], "ms": t * 1e3,
                         "slots_gathered": slots[name],
                         "ns_per_slot": t * 1e9 / slots[name],
                         "compile_s": compile_s, "agrees": got == want})
            print(json.dumps(rows[-1]), flush=True)
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "bench_carry.json"), "w") as f:
        json.dump({"device": [dev.platform, dev.device_kind],
                   "rows": rows}, f, indent=1)
    return rows


def _pack_chunks_by_gather(slot, valid, columns, n_buckets, tile, nc,
                           chunk):
    """`ops.groupby._pack_chunks` as ISSUE 36 first wrote it, kept here
    alone: an argsort by slot, then every lane of every chunk gathers
    its sorted position's source row and each column at that row —
    an element gather a column over all NC x C slots, where the
    program's form carries the columns through the sort and cuts whole
    chunks out of them."""
    from citus_tpu.ops.groupby import _chunk_layout

    n = slot.shape[0]
    trash = n_buckets * tile
    key = jnp.where(valid, slot, trash).astype(jnp.int32)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    cb, base, live = _chunk_layout(key[order], n_buckets, tile, nc, chunk)
    lane = jnp.arange(chunk, dtype=jnp.int32)[None, :]
    pos, lane_ok = base[:, None] + lane, lane < live[:, None]
    src = order[jnp.clip(pos, 0, n - 1)]
    packed = {c: jnp.where(lane_ok, col[src], jnp.zeros((), col.dtype))
              for c, col in columns.items()}
    return packed, jnp.where(lane_ok, key[src], trash), lane_ok, cb


def bench_groupby(regimes=None, repeats=3, reps=8, out="chiprun_out",
                  pallas=False):
    """High-cardinality GROUP BY A/B: the sort path (packed-key
    `segment_aggregate`, exactly what the executor runs) against the
    bucketed dense-grid path (`ops.groupby.bucketed_grid_aggregate`)
    at each candidate chunk size of its pack — 1,024 rows, 4,096, and
    the uniform expectation `_round_cap(ceil(n / n_buckets))` — the
    measurement behind `ops.groupby.GROUP_CHUNK_ROWS`, the planner's
    `group_bucket_eligible` gate and the `group_by_kernel` config var.
    Beside them `_pack_chunks_by_gather`, the pack's element-gather
    form, at 4,096.

    A regime is (n, k, skew): n input slots, k = the packed slot
    space's size, and the key uniform over it or SKEWED — all but
    0.1 % of the rows in the first tile, what a Zipf key or a count
    column does to a value-range partition (Q13's two group-bys on
    `tpch4z.q13`, whose shapes lead the chip's list).  Prints a line a
    (regime, form) and a winner histogram, and writes
    `<out>/bench_groupby.json`.  Runs on any backend, with small
    default regimes on the CPU; a time is a device time only on the
    chip.  `pallas` adds the Pallas tile kernel (interpret mode on the
    CPU is parity-checked, never timed).

    Usage:  python bench_kernels.py groupby [pallas]
    """
    import json
    import os

    from citus_tpu.runtime import ensure_jax_configured

    ensure_jax_configured()  # int64 packed keys need x64 standalone
    import citus_tpu.ops.groupby as G
    from citus_tpu.executor.compiler import _round_cap
    from citus_tpu.ops.aggregate import segment_aggregate
    dev = jax.devices()[0]
    platform = dev.platform
    if regimes is None:
        regimes = ([(1 << 18, 1 << 16, "uniform"),
                    (1 << 18, 1 << 16, "skewed")]
                   if platform == "cpu" else
                   # Q13's inner group-by a chip (the join's pair
                   # buffer over 37 tiles of c_custkey), its outer (30
                   # tiles of c_count), then 1 M and 8 M rows over 16
                   # and 256 tiles (k > n is planner-ineligible:
                   # occupancy < 1/4 keeps the sort path)
                   [(834_048, 150_002, "uniform"),
                    (834_048, 150_002, "skewed"),
                    (150_016, 118_800, "skewed"),
                    (1 << 20, 1 << 16, "uniform"),
                    (1 << 20, 1 << 16, "skewed"),
                    (1 << 23, 1 << 20, "uniform"),
                    (1 << 23, 1 << 20, "skewed")])
    tile = G.GROUP_TILE_SLOTS
    print(f"backend: {platform} ({dev.device_kind}); tile = {tile} slots")
    rng = np.random.default_rng(0)
    rows = []
    for n, k, skew in regimes:
        nb = G.group_bucket_count(k)
        base = rng.integers(0, k, n)
        if skew == "skewed":
            base = np.where(rng.random(n) < 0.999,
                            rng.integers(0, min(tile, k), n), base)
        slot0 = jnp.asarray(base.astype(np.int64))
        valid = jnp.asarray(rng.random(n) > 0.05)
        v0 = jnp.asarray(rng.uniform(0, 100, n).astype(np.float32))
        v1 = jnp.asarray(rng.uniform(0, 100, n).astype(np.float32))
        ones = jnp.asarray(np.ones(n, np.int32))

        def sort_path(i):
            # the rotation stays inside a tile, so a skewed key stays
            # skewed at every iteration
            s = slot0 - slot0 % tile + (slot0 + i) % tile
            s = jnp.minimum(s, k - 1)
            packed = jnp.where(valid, s, jnp.iinfo(jnp.int64).max)
            _gk, res, _gv, ng = segment_aggregate(
                [packed],
                [(v0, "sum", None), (v1, "sum", None),
                 (ones, "count", None)], valid, out_keys=[s])
            return (res[2].sum() + ng).astype(jnp.int64)

        def bucketed(i, kernel="xla"):
            s = slot0 - slot0 % tile + (slot0 + i) % tile
            s32 = jnp.minimum(s, k - 1).astype(jnp.int32)
            res, rps = G.bucketed_grid_aggregate(
                s32, valid,
                [(v0, "sum"), (v1, "sum"), (ones, "count")], k,
                kernel=kernel)
            return (res[2].sum().astype(jnp.int64)
                    + (rps > 0).sum()).astype(jnp.int64)

        chunks = sorted({1024, 4096, _round_cap(-(-n // nb))})
        forms = [("sort", None, None, sort_path)]
        forms += [(f"chunked_{c}", c, G._pack_chunks, bucketed)
                  for c in chunks]
        forms.append(("chunked_4096_by_gather", 4096,
                      _pack_chunks_by_gather, bucketed))
        if pallas and platform != "cpu":
            forms.append(("chunked_4096_pallas", 4096, G._pack_chunks,
                          functools.partial(bucketed, kernel="pallas")))
        chunk_rows, pack = G.GROUP_CHUNK_ROWS, G._pack_chunks
        want = None
        try:
            for name, c, pack_form, fn in forms:
                if c is not None:
                    G.GROUP_CHUNK_ROWS, G._pack_chunks = c, pack_form
                try:
                    t, compile_s, got = _slope_time_once(fn, repeats, reps)
                except Exception as e:  # a form the compiler refuses
                    print(f"  {name} failed: "
                          f"{str(e).splitlines()[0][:160]}")
                    continue
                # identical row totals AND live-group counts, a form:
                # a broken one is never crowned below
                want = got if want is None else want
                rows.append({
                    "form": name, "n": n, "k": k, "skew": skew,
                    "buckets": nb, "chunk": c,
                    "slots": (None if c is None
                              else (-(-n // c) + nb) * c),
                    "ms": t * 1e3, "ns_per_row": t * 1e9 / n,
                    "compile_s": compile_s, "agrees": got == want})
                print(json.dumps(rows[-1]), flush=True)
        finally:
            G.GROUP_CHUNK_ROWS, G._pack_chunks = chunk_rows, pack
    best: dict[str, int] = {}
    for n, k, skew in regimes:
        timed = [r for r in rows if (r["n"], r["k"], r["skew"])
                 == (n, k, skew) and r["agrees"]]
        if timed:
            w = min(timed, key=lambda r: r["ms"])["form"]
            best[w] = best.get(w, 0) + 1
    print("winner histogram:", best)
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "bench_groupby.json"), "w") as f:
        json.dump({"device": [dev.platform, dev.device_kind],
                   "tile": tile, "rows": rows}, f, indent=1)
    return rows


def bench_stripe_codec(gb: float = 0.5):
    """Native C++ stripe decode vs the pure-Python chunk loop —
    host-side only, no device.

    On a single core both paths bottleneck on the same zstd decompress,
    so the single-thread gap (~2x) is Python loop + concatenate overhead
    only; the native path auto-threads across chunks (n_threads=0 →
    hardware concurrency), which is where co-located many-core hosts
    take the reference-style C-reader win.  Run directly:
        python -c "import bench_kernels as b; b.bench_stripe_codec()"
    """
    import os
    import tempfile
    import time

    from citus_tpu.storage import format as F
    from citus_tpu.types import DataType

    rng = np.random.default_rng(0)
    ncols = 4
    n = max(1, int(gb * 1e9 / 8 / ncols))
    cols = {f"c{i}": rng.integers(0, 1000, n).astype(np.float64)
            for i in range(ncols)}
    schema = [(f"c{i}", DataType.FLOAT64) for i in range(ncols)]
    validity = {"c0": rng.random(n) > 0.1}
    d = tempfile.mkdtemp(prefix="codec_bench_")
    path = os.path.join(d, "s.stripe")
    F.write_stripe(path, schema, cols, validity, codec="zstd")
    logical = n * 8 * ncols
    print(f"stripe: {os.path.getsize(path) / 1e6:.0f} MB on disk, "
          f"{logical / 1e9:.2f} GB logical")
    r = F.StripeReader(path)
    r.read()  # warm the page cache

    def run(label, fn):
        t0 = time.perf_counter()
        v, m, _ = fn()
        dt = time.perf_counter() - t0
        print(f"  {label:<22} {dt * 1e3:8.1f} ms   "
              f"{logical / dt / 1e9:6.2f} GB/s")
        return dt, v, m

    t_nat, v1, m1 = run("native (default)", r.read)
    orig = F.StripeReader._read_native
    F.StripeReader._read_native = lambda self, c, ch, cid: None
    t_py, v2, m2 = run("python chunk loop", r.read)
    F.StripeReader._read_native = orig
    for c in v1:
        assert np.array_equal(v1[c], v2[c]) and \
            np.array_equal(m1[c], m2[c]), c
    print(f"  speedup: {t_py / t_nat:.2f}x  (single-host; decompress-"
          "bound floor is shared, threads scale the native side)")
    import shutil

    shutil.rmtree(d, ignore_errors=True)


if __name__ == "__main__":
    import sys

    if len(sys.argv) > 1 and sys.argv[1] == "lookup":
        bench_lookup(next((a for a in sys.argv[2:] if a != "small"),
                          "full"), small="small" in sys.argv[2:])
    elif len(sys.argv) > 1 and sys.argv[1] == "compact":
        bench_compact(small="small" in sys.argv[2:])
    elif len(sys.argv) > 1 and sys.argv[1] == "carry":
        bench_carry(small="small" in sys.argv[2:])
    elif len(sys.argv) > 1 and sys.argv[1] == "groupby":
        bench_groupby(pallas="pallas" in sys.argv[2:])
    else:
        main()
