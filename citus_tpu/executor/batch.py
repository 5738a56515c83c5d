"""Block: the device-side batch of rows (columns + row validity + nulls).

The tuple-at-a-time TupleTableSlot world of the reference
(executor/tuple_destination.c) collapses into one pytree of fixed-shape
arrays: a whole shard (or shuffle partition) processed as vectors.  Filters
never shrink arrays — they clear `valid` bits — so every shape stays static
under jit (the XLA contract, SURVEY §7 design stance).

A column may be *deferred*: held as (source array, row index) by
`Block.take` and gathered where it is first read.  On the v5e a gather
costs 6.6 ns an element at any size, so a column that crosses a
compaction or a lookup only to cross the next one unread is carried as
the index alone (PERF.md §6, PR 32).
"""

from __future__ import annotations

import threading
from collections.abc import MutableMapping
from contextlib import contextmanager
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..stats.tracing import stage_scope


class DeferredTally:
    """What one trace carried as an index (`columns`: arrays that
    crossed a `take` without a gather, a null mask counting as one) and
    what it gathered for them afterwards (`gathers`: values, null masks
    and index compositions).  Their difference is what deferring
    saved, by the program's own count.  `slots`: the gathers' index
    sizes summed, what they cost; `topdown`: chains of three or more
    indexes composed from the top; `recomposed`: the times a middle
    index such a chain gathered through was gathered through again, by
    another chain or composed at its own size by a later read of its
    own group."""

    def __init__(self):
        self.columns = 0
        self.gathers = 0
        self.slots = 0
        self.topdown = 0
        self.recomposed = 0


# the tally of the trace running on this thread (tracing runs on the
# calling thread, like exprs.set_device_params); none outside one
_tracing = threading.local()


@contextmanager
def deferred_tally():
    """`with deferred_tally() as t:` — every `take` and every deferred
    gather traced inside, on this thread, counts into `t`."""
    outer = getattr(_tracing, "tally", None)
    _tracing.tally = tally = DeferredTally()
    try:
        yield tally
    finally:
        _tracing.tally = outer


def _tally(**counts: int) -> None:
    tally = getattr(_tracing, "tally", None)
    if tally is not None:
        for name, n in counts.items():
            setattr(tally, name, getattr(tally, name) + n)


def _gather(src, idx):
    """`src[idx]`, counted, under the `deferred` sub-scope of whichever
    stage reads."""
    _tally(gathers=1, slots=int(idx.shape[0]))
    with stage_scope("deferred"):
        return src[idx]


class _RowIndex:
    """The row index a group of deferred columns shares: `idx` alone
    for columns that were arrays when the `take` was made, `below[idx]`
    for a group that was itself still deferred — composed on first use,
    once for the group.  A chain is composed from the top down: `idx`
    is gathered through each index below that is still uncomposed, then
    through the first composed one, so every gather runs at the size of
    `idx`, the narrowest of the chain (`Block.take` composes only on a
    take that narrows or keeps the size), never at a middle one's."""

    __slots__ = ("_idx", "_below", "_passed")

    def __init__(self, idx, below: "_RowIndex | None" = None):
        self._idx = idx
        self._below = below
        self._passed = False   # a top-down walk gathered through it

    def get(self):
        if self._below is not None:
            if self._passed:
                _tally(recomposed=1)
            idx, below = self._idx, self._below
            if below._below is not None:
                _tally(topdown=1)
            while below._below is not None:
                if below._passed:
                    _tally(recomposed=1)
                below._passed = True
                idx = _gather(below._idx, idx)
                below = below._below
            self._idx = _gather(below._idx, idx)
            self._below = None
        return self._idx


class _Deferred:
    """One column (or null mask) held as (source, row index); the
    gather is made on the first read and kept, for every block that
    shares the cell.  The source is an array or another such cell,
    read first (a widening `take` leaves it so)."""

    __slots__ = ("src", "rows", "value")

    def __init__(self, src, rows: _RowIndex):
        self.src = src
        self.rows = rows
        self.value = None

    def get(self):
        if self.value is None:
            src = self.src
            if isinstance(src, _Deferred):
                src = src.get()
            self.value = _gather(src, self.rows.get())
            self.src = self.rows = None
        return self.value


class Columns(MutableMapping):
    """cid → [N] array, some of them deferred: a dict to its readers
    (`cols[cid]`, `.get`, `.items()`, `dict(cols)` read, so gather);
    `in`, `len` and iteration over the keys read nothing."""

    __slots__ = ("_cells",)

    def __init__(self, arrays=()):
        self._cells = dict(arrays)   # cid → array | _Deferred

    def __getitem__(self, cid):
        cell = self._cells[cid]
        return cell.get() if isinstance(cell, _Deferred) else cell

    def __setitem__(self, cid, arr):
        self._cells[cid] = arr

    def __delitem__(self, cid):
        del self._cells[cid]

    def __iter__(self):
        return iter(self._cells)

    def __len__(self):
        return len(self._cells)

    def __contains__(self, cid):
        return cid in self._cells

    def __repr__(self):
        return f"Columns({list(self._cells)})"

    def joined(self, other: "Columns") -> "Columns":
        """Both sets of columns (`other`'s on a clash), nothing read."""
        return Columns({**self._cells, **other._cells})

    def _take(self, fresh: _RowIndex, composed: dict | None) -> "Columns":
        """Every column deferred on `fresh`; with `composed` (a memo by
        group for one `take`) a column still unread stays on its source,
        its group's index composed with the new one."""
        out = {}
        for cid, cell in self._cells.items():
            if not isinstance(cell, _Deferred) or cell.value is not None:
                out[cid] = _Deferred(self[cid], fresh)
            elif composed is None:
                out[cid] = _Deferred(cell, fresh)
            else:
                rows = composed.get(id(cell.rows))
                if rows is None:
                    rows = composed[id(cell.rows)] = \
                        _RowIndex(fresh._idx, cell.rows)
                out[cid] = _Deferred(cell.src, rows)
        return Columns(out)


def _flatten_columns(cols: Columns):
    # a jit or shard_map boundary reads everything; keys sorted as a dict's
    cids = tuple(sorted(cols))
    return tuple(cols[c] for c in cids), cids


jax.tree_util.register_pytree_node(
    Columns, _flatten_columns,
    lambda cids, arrays: Columns(zip(cids, arrays)))


@jax.tree_util.register_dataclass
@dataclass
class Block:
    """columns: name → [N] array; valid: [N] row mask;
    nulls: name → [N] True-where-NULL (absent key = no nulls)."""

    columns: Columns
    valid: jnp.ndarray
    nulls: Columns = field(default_factory=Columns)

    def __post_init__(self):
        if isinstance(self.columns, dict):
            self.columns = Columns(self.columns)
        if isinstance(self.nulls, dict):
            self.nulls = Columns(self.nulls)

    @property
    def capacity(self) -> int:
        return int(self.valid.shape[0])

    def column(self, name: str) -> jnp.ndarray:
        return self.columns[name]

    def null_mask(self, name: str) -> jnp.ndarray:
        """[N] bool, True where value is NULL."""
        if name in self.nulls:
            return self.nulls[name]
        return jnp.zeros(self.valid.shape, dtype=jnp.bool_)

    def not_null(self, name: str) -> jnp.ndarray:
        return ~self.null_mask(name)

    def with_filter(self, mask: jnp.ndarray) -> "Block":
        return Block(self.columns, self.valid & mask, self.nulls)

    def select(self, names: list[str]) -> "Block":
        return Block({n: self.columns[n] for n in names}, self.valid,
                     {n: self.nulls[n] for n in self.nulls if n in names})

    def with_column(self, name: str, values: jnp.ndarray,
                    null_mask: jnp.ndarray | None = None) -> "Block":
        cols = Columns(self.columns._cells)
        cols[name] = values
        nulls = Columns(self.nulls._cells)
        if null_mask is not None:
            nulls[name] = null_mask
        else:
            nulls.pop(name, None)
        return Block(cols, self.valid, nulls)

    def take(self, idx: jnp.ndarray, valid: jnp.ndarray) -> "Block":
        """The rows `idx` of this block, `valid` their row mask, and
        nothing gathered: every column and null mask is deferred on
        `idx` and gathered where it is first read, once.  Columns
        deferred by one `take` are a group.  A group still unread at the
        next `take` has its index chained to the new one; the chain is
        composed when the first of the group is read, from the top down
        (`_RowIndex.get`): one gather at the newest index's size for
        each index below it, for the whole group, and none at a middle
        index's larger size.  A column that was read in between is
        deferred on its gathered array with the new index alone.  Only a
        `take` that narrows or keeps the size chains (a compaction's, a
        top-k's); one that widens (a lookup's index over a smaller
        build side) would compose at the larger size, so a column
        still unread there is read at its own size first, as a gather
        at every step would, and the lookup's index starts a chain.  So
        no column ever costs more gathered elements than a gather at
        every step, and one that is read right after a `take` costs that
        one gather."""
        _tally(columns=len(self.columns) + len(self.nulls))
        fresh = _RowIndex(idx)
        composed = {} if idx.shape[0] <= self.capacity else None
        return Block(self.columns._take(fresh, composed), valid,
                     self.nulls._take(fresh, composed))

    def joined(self, other: "Block", valid: jnp.ndarray) -> "Block":
        """This block's columns beside `other`'s, row for row, under the
        row mask `valid`; nothing is read."""
        return Block(self.columns.joined(other.columns), valid,
                     self.nulls.joined(other.nulls))

    def row_count(self) -> jnp.ndarray:
        return self.valid.sum()


def block_from_numpy(values: dict[str, np.ndarray],
                     validity: dict[str, np.ndarray] | None = None,
                     capacity: int | None = None,
                     compute_dtype=None) -> Block:
    """Host arrays → padded device Block.

    Per-column validity from storage becomes `nulls`; rows beyond the real
    row count are padding (valid=False).  float64 storage downcasts to
    `compute_dtype` when given (the TPU f32 policy).
    """
    n = len(next(iter(values.values())))
    cap = capacity or n
    if cap < n:
        raise ValueError(f"capacity {cap} < rows {n}")
    cols = {}
    nulls = {}
    for name, arr in values.items():
        if compute_dtype is not None and arr.dtype == np.float64:
            arr = arr.astype(compute_dtype)
        pad = np.zeros(cap - n, dtype=arr.dtype)
        cols[name] = jnp.asarray(np.concatenate([arr, pad]))
        if validity and name in validity:
            v = np.asarray(validity[name], dtype=bool)
            if not v.all():
                nulls[name] = jnp.asarray(np.concatenate(
                    [~v, np.zeros(cap - n, dtype=bool)]))
    valid = jnp.asarray(np.concatenate(
        [np.ones(n, dtype=bool), np.zeros(cap - n, dtype=bool)]))
    return Block(cols, valid, nulls)


def block_to_numpy(block: Block) -> tuple[dict[str, np.ndarray], np.ndarray, dict[str, np.ndarray]]:
    """Device Block → host (columns, valid, nulls) as numpy."""
    cols = {n: np.asarray(a) for n, a in block.columns.items()}
    valid = np.asarray(block.valid)
    nulls = {n: np.asarray(a) for n, a in block.nulls.items()}
    return cols, valid, nulls


def compact_to_numpy(block: Block) -> tuple[dict[str, np.ndarray], dict[str, np.ndarray]]:
    """Gather only valid rows host-side (final result materialization)."""
    cols, valid, nulls = block_to_numpy(block)
    out = {n: a[valid] for n, a in cols.items()}
    out_nulls = {n: a[valid] for n, a in nulls.items()}
    return out, out_nulls
