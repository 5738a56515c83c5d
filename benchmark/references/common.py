"""Shared by the plain references: dates, the float tolerance and the
relative-error rule.  Copied from `chip_smoke.py` (PR 23), where the
tolerance was fixed from the dtype before any run."""

from __future__ import annotations

import datetime
import math

import numpy as np

_F32_EPS = float(np.finfo(np.float32).eps)
_EPOCH = datetime.date(1970, 1, 1)


def days(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - _EPOCH).days


def iso(day: int) -> str:
    return (_EPOCH + datetime.timedelta(days=int(day))).isoformat()


def float_tol(n_rows: int) -> float:
    """Relative tolerance for a float32 sum over `n_rows` rows: the
    engine stores and accumulates DOUBLE PRECISION columns in float32
    (`compute_dtype`), the reference in float64, and the rounding error
    of a float32 sum grows like a random walk in the worst (sequential)
    summation order.  5.8e-4 at SF1's 6.0 M `lineitem` rows."""
    return max(1e-6, 2.0 * math.sqrt(max(n_rows, 1)) * _F32_EPS)


def rel_err(got, want) -> float:
    return abs(float(got) - float(want)) / max(abs(float(want)), 1.0)


def close(got, want, tol: float) -> bool:
    return rel_err(got, want) <= tol
