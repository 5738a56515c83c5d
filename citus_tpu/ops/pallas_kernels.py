"""Pallas TPU kernels for the aggregation and join-probe hot paths.

BASELINE.json's north star calls for hand kernels on the hot ops (the
reference's equivalents are C inner loops: per-tuple hash-aggregate
transition functions reached from the plans in
planner/multi_logical_optimizer.c).  The XLA formulation used by
ops/aggregate.py covers most shapes well; the one place XLA lowers badly
on TPU is `jax.ops.segment_sum` with mid-sized segment counts — it emits
a serialized scatter-add.  This kernel replaces it with the MXU-friendly
formulation: one-hot × values matmuls accumulated in VMEM scratch across
a sequential row-tile grid.

    sums[k, a] = Σ_{i: slot[i]=k} values[i, a]

The grid walks row tiles; a [K, A] f32 scratch lives in VMEM for the
whole pass (TPU grid steps run sequentially on one core, so scratch
accumulation is safe); each step builds an f32 one-hot tile chunked over
K and feeds the MXU with f32 accumulation (one-hot entries are exact in
any float dtype; values stay f32 so sums match the XLA path).

Whether this beats the XLA segment ops on real hardware is measured by
bench_kernels.py; the executor only routes through it when
`enable_pallas_aggregate` is on and the measurement said yes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_TILE = 1024       # rows per grid step
K_CHUNK = 512         # one-hot width per MXU feed
PROBE_CHUNK = 512     # probe rows streamed per step through one tile
BITS_CHUNK = 128      # packed bytes per bit-unpack step (→ 1024 lanes)
# block index 0 for index maps: under jax_enable_x64 a Python literal
# traces as int64, which the TPU kernel compiler refuses in an index map
_Z = np.int32(0)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _kernel(slot_ref, val_ref, out_ref, acc_ref, *, n_chunks: int):
    """One grid step: accumulate this row tile into [K, A] scratch."""
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    slots = slot_ref[:]                       # [T, 1] int32
    vals = val_ref[:]                         # [T, A] f32
    for c in range(n_chunks):
        base = c * K_CHUNK
        ids = jax.lax.broadcasted_iota(
            jnp.int32, (ROW_TILE, K_CHUNK), 1) + base
        onehot = (slots == ids).astype(jnp.float32)   # [T,1]→[T,Kc]
        part = jax.lax.dot_general(
            onehot, vals,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [Kc, A]
        sl = pl.ds(base, K_CHUNK)
        acc_ref[sl, :] = acc_ref[sl, :] + part

    @pl.when(step == pl.num_programs(0) - 1)
    def _flush():
        out_ref[:] = acc_ref[:]

@functools.partial(jax.jit, static_argnames=("total", "interpret"))
def dense_grid_aggregate_pallas(slot: jnp.ndarray,
                                values: jnp.ndarray, total: int,
                                interpret: bool = False
                                ) -> jnp.ndarray:
    """MXU segment-sum: slot [N] int32 (== total ⇒ ignored row),
    values [N, A] float32 → sums [total, A] float32."""
    n = slot.shape[0]
    a = values.shape[1]
    n_pad = _round_up(max(n, ROW_TILE), ROW_TILE)
    k_pad = _round_up(total + 1, K_CHUNK)  # +1 keeps a trash slot
    a_pad = _round_up(a, 128)
    grid = n_pad // ROW_TILE
    # slots as [N, 1]: a block whose LAST dim equals the whole array
    # dim satisfies the TPU tiling rule, and [T, 1] == [T, Kc]
    # broadcasts without any in-kernel reshape (Mosaic rejects
    # (8,128)→(1024,1) shape casts)
    slot_p = jnp.full((n_pad, 1), k_pad - 1, jnp.int32).at[:n, 0].set(
        jnp.where(slot >= total, k_pad - 1, slot))
    vals_p = jnp.zeros((n_pad, a_pad), jnp.float32) \
        .at[:n, :a].set(values.astype(jnp.float32))

    kernel = functools.partial(_kernel, n_chunks=k_pad // K_CHUNK)
    out = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((ROW_TILE, 1), lambda i: (i, _Z)),
            pl.BlockSpec((ROW_TILE, a_pad), lambda i: (i, _Z)),
        ],
        out_specs=pl.BlockSpec((k_pad, a_pad), lambda i: (_Z, _Z)),
        out_shape=jax.ShapeDtypeStruct((k_pad, a_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((k_pad, a_pad), jnp.float32)],
        interpret=interpret,
    )(slot_p, vals_p)
    return out[:total, :a]


def _probe_kernel(tile_ref, loc_ref, out_ref):
    """One grid step: gather PROBE_CHUNK probes against the resident
    directory tile.  The tile block's index map ignores the chunk
    grid dimension, so Pallas keeps it in VMEM across all of a
    bucket's probe chunks — the directory streams HBM→VMEM exactly
    once while probe chunks pipeline through it."""
    out_ref[:] = jnp.take_along_axis(tile_ref[:], loc_ref[:], axis=1)

@functools.partial(jax.jit, static_argnames=("interpret",))
def bucketed_probe_pallas(dir2d: jnp.ndarray, loc2d: jnp.ndarray,
                          interpret: bool = False) -> jnp.ndarray:
    """VMEM-tiled directory probe for the bucketed join path.

    dir2d [n_buckets, tile] int32 — directory values per bucket tile
    (tile is VMEM-sized, ops.join.PROBE_TILE_SLOTS by default);
    loc2d [n_buckets, cap] int32 — tile-local probe slots, packed by
    bucket (garbage lanes must hold a clipped in-range slot).
    Returns [n_buckets, cap] int32 gathered directory values.

    Grid = (bucket, probe chunk); the in-kernel gather is a 2D
    lane-dimension take_along_axis.  The TPU kernel compiler refuses
    it as written (tests/test_tpu_compile.py carries its message: a
    lane gather reaches one 128-lane vreg, not a 32768-slot tile), so
    it runs in interpret mode only; the executor routes through XLA
    unless join_probe_kernel says otherwise."""
    k, tile = dir2d.shape
    _, cap = loc2d.shape
    cap_pad = _round_up(max(cap, PROBE_CHUNK), PROBE_CHUNK)
    if cap_pad != cap:
        loc2d = jnp.zeros((k, cap_pad), jnp.int32).at[:, :cap].set(
            loc2d)
    out = pl.pallas_call(
        _probe_kernel,
        grid=(k, cap_pad // PROBE_CHUNK),
        in_specs=[
            pl.BlockSpec((1, tile), lambda i, j: (i, 0)),
            pl.BlockSpec((1, PROBE_CHUNK), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((1, PROBE_CHUNK), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((k, cap_pad), jnp.int32),
        interpret=interpret,
    )(dir2d, loc2d)
    return out[:, :cap]


def _groupby_kernel(slot_ref, val_ref, out_ref, acc_ref, *,
                    n_chunks: int):
    """One grid step: accumulate ROW_TILE packed rows of bucket b
    into that bucket's [tile, A] VMEM scratch.  The grid is
    (bucket, row chunk) with the row dimension fastest, so each
    bucket's chunks run back-to-back and the scratch accumulation
    is safe (TPU grid steps are sequential on one core)."""
    r = pl.program_id(1)

    @pl.when(r == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    slots = slot_ref[:]                       # [T, 1] int32
    vals = val_ref[:]                         # [T, A] f32
    for c in range(n_chunks):
        base = c * K_CHUNK
        ids = jax.lax.broadcasted_iota(
            jnp.int32, (ROW_TILE, K_CHUNK), 1) + base
        onehot = (slots == ids).astype(jnp.float32)
        part = jax.lax.dot_general(
            onehot, vals,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [Kc, A]
        sl = pl.ds(base, K_CHUNK)
        acc_ref[sl, :] = acc_ref[sl, :] + part

    @pl.when(r == pl.num_programs(1) - 1)
    def _flush():
        out_ref[:] = acc_ref[:]

@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def bucketed_groupby_sums_pallas(loc2d: jnp.ndarray,
                                 stack: jnp.ndarray, tile: int,
                                 interpret: bool = False
                                 ) -> jnp.ndarray:
    """Bucket-tiled MXU segment-sum for the bucketed group-by path.

    loc2d [n_buckets, cap] int32 — tile-local slots packed by
    bucket (garbage lanes hold slot 0 with ZEROED values, as
    pack_by_target emits them, so they contribute exact zeros);
    stack [n_buckets, cap, A] f32 — value columns, same packing.
    Returns [n_buckets, tile, A] f32 per-tile sums.

    The same one-hot-matmul-in-VMEM-scratch algorithm as
    dense_grid_aggregate_pallas, batched over buckets: grid =
    (bucket, row chunk), scratch [tile, A] lives across a bucket's
    row chunks.  Whether this beats the batched-XLA one-hot
    dot_general on real hardware is bench_kernels.py groupby's
    call — the executor routes through XLA unless the measurement
    (group_by_kernel config var) says otherwise."""
    nb, cap = loc2d.shape
    a = stack.shape[2]
    cap_pad = _round_up(max(cap, ROW_TILE), ROW_TILE)
    k_pad = _round_up(tile, K_CHUNK)
    a_pad = _round_up(a, 128)
    row_steps = cap_pad // ROW_TILE

    slot_flat = jnp.zeros((nb * cap_pad, 1), jnp.int32)
    slot_flat = slot_flat.reshape(nb, cap_pad, 1).at[:, :cap, 0].set(
        loc2d).reshape(nb * cap_pad, 1)
    val_flat = jnp.zeros((nb * cap_pad, a_pad), jnp.float32) \
        .reshape(nb, cap_pad, a_pad).at[:, :cap, :a].set(
        stack.astype(jnp.float32)).reshape(nb * cap_pad, a_pad)

    kernel = functools.partial(_groupby_kernel,
                               n_chunks=k_pad // K_CHUNK)
    out = pl.pallas_call(
        kernel,
        grid=(nb, row_steps),
        in_specs=[
            pl.BlockSpec((ROW_TILE, 1),
                         lambda b, r: (b * row_steps + r, _Z)),
            pl.BlockSpec((ROW_TILE, a_pad),
                         lambda b, r: (b * row_steps + r, _Z)),
        ],
        out_specs=pl.BlockSpec((k_pad, a_pad), lambda b, r: (b, _Z)),
        out_shape=jax.ShapeDtypeStruct((nb * k_pad, a_pad),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((k_pad, a_pad), jnp.float32)],
        interpret=interpret,
    )(slot_flat, val_flat)
    return out.reshape(nb, k_pad, a_pad)[:, :tile, :a]


def _bitunpack_kernel(packed_ref, out_ref):
    """One grid step: unpack BITS_CHUNK packed bytes into
    BITS_CHUNK×8 byte-per-bit lanes (MSB-first — numpy packbits
    order).  A lane-dimension gather picks each output bit's source
    byte (like the probe kernel's take_along_axis, and refused by the
    TPU kernel compiler for the same reason)."""
    p = packed_ref[:].astype(jnp.int32)            # [1, C]
    j = jax.lax.broadcasted_iota(jnp.int32, (1, p.shape[1] * 8), 1)
    byte = jnp.take_along_axis(p, j // 8, axis=1)
    out_ref[:] = ((byte >> (7 - (j % 8))) & 1).astype(jnp.uint8)

@functools.partial(jax.jit, static_argnames=("cap", "interpret"))
def bit_unpack_pallas(packed: jnp.ndarray, cap: int,
                      interpret: bool = False) -> jnp.ndarray:
    """Validity-plane unpack: packed [rows, cap//8] uint8 (numpy
    packbits, MSB-first) → [rows, cap] bool.  Interpret mode only: the
    TPU kernel compiler refuses it (tests/test_tpu_compile.py), so the
    pipelined scan expands bit planes with scanpipe._bits_expand."""
    rows, w = packed.shape
    w_pad = _round_up(max(w, BITS_CHUNK), BITS_CHUNK)
    if w_pad != w:
        packed = jnp.zeros((rows, w_pad), jnp.uint8) \
            .at[:, :w].set(packed)
    out = pl.pallas_call(
        _bitunpack_kernel,
        grid=(rows, w_pad // BITS_CHUNK),
        in_specs=[pl.BlockSpec((1, BITS_CHUNK),
                               lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((1, BITS_CHUNK * 8),
                               lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, w_pad * 8),
                                       jnp.uint8),
        interpret=interpret,
    )(packed)
    return out[:, :cap].astype(bool)

def _dictdecode_kernel(lut_ref, codes_ref, out_ref):
    """One grid step: gather PROBE_CHUNK codes against the resident
    LUT tile (index map ignores the chunk grid dim, so the LUT
    streams HBM→VMEM once per row — the probe kernel's pattern)."""
    out_ref[:] = jnp.take_along_axis(lut_ref[:], codes_ref[:],
                                     axis=1)

@functools.partial(jax.jit, static_argnames=("interpret",))
def dict_decode_pallas(codes: jnp.ndarray, lut: jnp.ndarray,
                       interpret: bool = False) -> jnp.ndarray:
    """Dictionary decode: codes [rows, cap] (uint8/uint16 wire dtype)
    + lut [n_values] → out[r, i] = lut[codes[r, i]].  Interpret mode
    only: the TPU kernel compiler refuses it
    (tests/test_tpu_compile.py), so the pipelined scan decodes with
    scanpipe._dict_expand."""
    rows, cap = codes.shape
    nv = lut.shape[0]
    l_pad = _round_up(max(nv, 128), 128)
    lut2 = jnp.zeros((1, l_pad), lut.dtype).at[0, :nv].set(lut)
    cap_pad = _round_up(max(cap, PROBE_CHUNK), PROBE_CHUNK)
    c = codes.astype(jnp.int32)
    if cap_pad != cap:
        c = jnp.zeros((rows, cap_pad), jnp.int32).at[:, :cap].set(c)
    out = pl.pallas_call(
        _dictdecode_kernel,
        grid=(rows, cap_pad // PROBE_CHUNK),
        in_specs=[
            pl.BlockSpec((1, l_pad), lambda i, j: (0, 0)),
            pl.BlockSpec((1, PROBE_CHUNK), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((1, PROBE_CHUNK),
                               lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, cap_pad), lut.dtype),
        interpret=interpret,
    )(lut2, c)
    return out[:, :cap]


def bit_unpack_reference(packed: np.ndarray, cap: int) -> np.ndarray:
    """numpy oracle for the bit unpack."""
    p = np.asarray(packed)
    bits = np.unpackbits(p, axis=-1)
    return bits[..., :cap].astype(bool)


def dict_decode_reference(codes: np.ndarray, lut: np.ndarray
                          ) -> np.ndarray:
    """numpy oracle for the dictionary decode."""
    return np.asarray(lut)[np.asarray(codes).astype(np.int64)]


def groupby_sums_reference(loc2d: np.ndarray, stack: np.ndarray,
                           tile: int) -> np.ndarray:
    """numpy oracle for the bucket-tiled segment sum."""
    nb, cap = np.asarray(loc2d).shape
    a = np.asarray(stack).shape[2]
    out = np.zeros((nb, tile, a), np.float32)
    for b in range(nb):
        np.add.at(out[b], np.asarray(loc2d)[b],
                  np.asarray(stack)[b].astype(np.float32))
    return out


def probe_gather_reference(dir2d: np.ndarray,
                           loc2d: np.ndarray) -> np.ndarray:
    """numpy oracle for the tiled probe gather."""
    return np.take_along_axis(np.asarray(dir2d), np.asarray(loc2d), axis=1)


def segment_sum_reference(slot: np.ndarray, values: np.ndarray,
                          total: int) -> np.ndarray:
    """numpy oracle for tests."""
    out = np.zeros((total, values.shape[1]), np.float32)
    keep = slot < total
    np.add.at(out, slot[keep], values[keep].astype(np.float32))
    return out
