"""Device-side hashing: the bit-exact twin of catalog.distribution.

The routing contract: host ingest (numpy) and device shuffles (jax) MUST
compute identical hash tokens, or rows land on the wrong shard after a
repartition (`all_to_all`) and joins silently lose rows.  Tests assert
bit-equality between this module and catalog/distribution.py.

Reference analogue: the worker-side hash evaluation in
worker_partition_query_result (/root/reference/src/backend/distributed/
executor/partitioned_intermediate_results.c) — there per-row C hashing over
libpq tuples; here whole-column uint32 VPU ops.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..catalog.distribution import HASH_TOKEN_COUNT, INT32_MIN


def fmix32_jax(x: jnp.ndarray) -> jnp.ndarray:
    """murmur3 finalizer over uint32 arrays (shifts/xors/mults — pure VPU)."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def hash_token_jax(values: jnp.ndarray) -> jnp.ndarray:
    """Column → signed int32 hash tokens (matches distribution.hash_token).

    Requires x64 mode: with it off, jnp.asarray silently downcasts int64
    columns to int32 *before* this function sees them, so the 64-bit mix
    never runs and parity with the host silently breaks.  Entry points call
    runtime.ensure_jax_configured(); this guard catches stragglers."""
    from ..runtime import require_x64

    require_x64()
    dt = values.dtype
    if dt in (jnp.int64, jnp.uint64):
        v = values.astype(jnp.uint64)
        lo = (v & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
        hi = (v >> jnp.uint64(32)).astype(jnp.uint32)
        # PG hashint8-style width fold (see distribution.hash_token): makes
        # int64 hashing agree with int32 for in-range values, so executor
        # key casts to int64 keep host/device routing parity
        nonneg = hi < jnp.uint32(0x80000000)
        folded = lo ^ jnp.where(nonneg, hi, ~hi)
        return fmix32_jax(folded).astype(jnp.int32)
    if dt == jnp.float64:
        # bit pattern, not value: int64 view
        return hash_token_jax(
            jnp.asarray(values).view(jnp.int64))
    if dt == jnp.float32:
        return fmix32_jax(jnp.asarray(values).view(jnp.uint32)).astype(jnp.int32)
    if dt == jnp.bool_:
        values = values.astype(jnp.int32)
    return fmix32_jax(values.astype(jnp.int32).view(jnp.uint32)).astype(jnp.int32)


def shard_index_from_token(tokens: jnp.ndarray, shard_count: int) -> jnp.ndarray:
    """Uniform-increment owner lookup (closed form; no binary search).

    Matches distribution.shard_index_for_token: contiguous ranges of width
    HASH_TOKEN_COUNT // shard_count starting at INT32_MIN.
    """
    increment = HASH_TOKEN_COUNT // shard_count
    offset = tokens.astype(jnp.int64) - INT32_MIN
    idx = offset // increment
    return jnp.minimum(idx, shard_count - 1).astype(jnp.int32)


def shard_index_for_values_jax(values: jnp.ndarray, shard_count: int) -> jnp.ndarray:
    return shard_index_from_token(hash_token_jax(values), shard_count)


def combine_hash64(parts: list[jnp.ndarray]) -> jnp.ndarray:
    """Mix several key columns into one uint64 (group-by composite key).

    Used ONLY where collisions are tolerable or verified downstream; exact
    multi-key comparisons use ops.join lexicographic search instead.
    """
    acc = jnp.zeros(parts[0].shape, dtype=jnp.uint64)
    for p in parts:
        h = hash_token_jax(p).astype(jnp.uint64) & jnp.uint64(0xFFFFFFFF)
        acc = acc * jnp.uint64(0x100000001B3) ^ h
    return acc
