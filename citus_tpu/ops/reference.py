"""numpy references for the device formulations: the scan decode's
bit unpack and dictionary gather (executor/scanpipe.py) and the
grouped sums of the aggregation grids (ops/groupby.py).  Plain loops
over numpy, independent of the code they check."""

from __future__ import annotations

import numpy as np


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
    """numpy oracle for the bucket-tiled segment sum
    (groupby._onehot_bucket_sums, with its tile axis last there)."""
    nb, cap = np.asarray(loc2d).shape
    a = np.asarray(stack).shape[2]
    out = np.zeros((nb, tile, a), np.float32)
    for b in range(nb):
        np.add.at(out[b], np.asarray(loc2d)[b],
                  np.asarray(stack)[b].astype(np.float32))
    return out


def segment_sum_reference(slot: np.ndarray, values: np.ndarray,
                          total: int) -> np.ndarray:
    """numpy oracle for a segment sum over [total] slots; a slot at or
    past `total` is dropped."""
    out = np.zeros((total, values.shape[1]), np.float32)
    keep = slot < total
    np.add.at(out, slot[keep], values[keep].astype(np.float32))
    return out
