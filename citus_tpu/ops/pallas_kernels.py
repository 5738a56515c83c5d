"""Pallas TPU kernels for the aggregation hot paths.

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


def bit_unpack_reference(packed: np.ndarray, cap: int) -> np.ndarray:
    """numpy oracle for the bit unpack (scanpipe._bits_expand)."""
    p = np.asarray(packed)
    bits = np.unpackbits(p, axis=-1)
    return bits[..., :cap].astype(bool)


def dict_decode_reference(codes: np.ndarray, lut: np.ndarray
                          ) -> np.ndarray:
    """numpy oracle for the dictionary decode (scanpipe._dict_expand)."""
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


def segment_sum_reference(slot: np.ndarray, values: np.ndarray,
                          total: int) -> np.ndarray:
    """numpy oracle for tests."""
    out = np.zeros((total, values.shape[1]), np.float32)
    keep = slot < total
    np.add.at(out, slot[keep], values[keep].astype(np.float32))
    return out
