"""Pipelined columnar scan: overlapped prefetch/decode/transfer with
optional on-device decode of compressed column payloads.

The eager feed path (executor/feed.py `_feed_scan`) is three strictly
serial phases: read+decode EVERY stripe, assemble padded [n_dev, cap]
buffers for EVERY column, then device_put them one after another, with
the host decoder idle during every transfer.  This module restores the
overlap the reference's stripe reader gets for
free from its row-at-a-time pull loop (columnar_reader.c:323), done the
TPU-native way — fixed-shape feeds, one producer thread, a bounded
queue:

* **prefetch + decode** (producer thread): columns are read one at a
  time across all visible stripes through the native threaded codec,
  with the chunk-group skip set computed ONCE per stripe over the full
  projection's stats (skipped chunks are never fetched) and pinned for
  every column so rows stay aligned.  The producer runs
  `scan_prefetch_depth` columns ahead of the consumer.
* **double-buffered async transfer**: the producer also *places* each
  assembled column through the ONE accounted seam
  (`DeviceMemoryAccountant.place`, category ``prefetch``) — so column
  i+1 decodes and column i+2's stripes stream off disk while column
  i's bytes are still in flight to the device.  Prefetch charges
  graduate to their final category when the consumer adopts them; an
  allocator OOM while prefetching sheds the pipeline (the bounded
  queue drains, every prefetch charge releases) and the feed retries
  eagerly — pipelined feeds stay OOM-governed and cost the ladder
  nothing.
* **on-device decode** (``scan_pipeline=device``): instead of decoded
  float32/int64, *compressed* payloads cross the wire — integer/date/
  dictionary-code columns frame-of-reference-packed to the narrowest
  unsigned width, low-NDV float columns as dictionary codes plus a
  tiny value LUT, validity planes bit-packed 8:1 and the valid prefix
  as one row-count per device — and expand on the mesh (XLA
  formulations, which GSPMD partitions on any mesh width).
  `bytes_on_wire` < `bytes_decoded` by the packing ratio.

`scan_pipeline` picks the mode (off | host | device, 'auto' resolves
by backend), `scan_prefetch_depth` bounds the queue.  Overlay-touching
tables (open-transaction visibility) fall back to the eager path.
"""

from __future__ import annotations

import functools
import queue
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..errors import DeviceMemoryExhausted
from ..stats.tracing import stage_scope

# below this many table rows 'auto' keeps the eager path: a producer
# thread + per-column reads cost more than they hide on tiny feeds
AUTO_MIN_ROWS = 4096

# dictionary encoding applies up to this many distinct values (uint16
# codes); the NDV probe samples this many rows before paying a full
# np.unique over the column
_DICT_MAX_NDV = 65536
_NDV_SAMPLE = 65536


class ScanPhaseStats:
    """Per-executor accumulator for the scan pipeline's phase walls and
    wire/decoded byte totals — the bench drivers read (and reset) this
    to stamp per-phase timers into the BENCH artifact."""

    FIELDS = ("prefetch_seconds", "decode_seconds", "transfer_seconds",
              "device_decode_seconds", "bytes_on_wire", "bytes_decoded",
              "prefetch_stalls", "chunks_prefetched", "feeds_pipelined",
              "stream_decode_seconds", "stream_transfer_seconds")

    def __init__(self):
        self._mu = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._mu:
            for f in self.FIELDS:
                setattr(self, f, 0.0 if "seconds" in f else 0)
            # wire bytes placed per mesh-device index (the device-owned
            # slice seam charges each device its own slice) — the
            # multichip bench stamps the hot device's share to prove
            # per-device feed bytes shrink ≈1/N with mesh width
            self.wire_by_device: dict[int, int] = {}

    def add(self, **kw) -> None:
        with self._mu:
            for k, v in kw.items():
                setattr(self, k, getattr(self, k) + v)

    def add_device_bytes(self, per_dev) -> None:
        with self._mu:
            for d, b in enumerate(per_dev):
                self.wire_by_device[d] = \
                    self.wire_by_device.get(d, 0) + int(b)

    def snapshot(self) -> dict:
        with self._mu:
            out = {f: (round(getattr(self, f), 4)
                       if "seconds" in f else int(getattr(self, f)))
                   for f in self.FIELDS}
            n = max(self.wire_by_device, default=-1) + 1
            out["wire_bytes_by_device"] = [
                self.wire_by_device.get(d, 0) for d in range(n)]
            return out

    def merge(self, other: "ScanPhaseStats") -> None:
        """Fold another accumulator in (a completed pipeline's local
        tallies graduate into the executor-wide stats — discarded
        attempts never fold, so the published phase walls describe
        only builds whose feeds were actually used)."""
        with other._mu:
            vals = {f: getattr(other, f) for f in self.FIELDS}
            per_dev_items = list(other.wire_by_device.items())
        self.add(**vals)
        with self._mu:
            for d, b in per_dev_items:
                self.wire_by_device[d] = \
                    self.wire_by_device.get(d, 0) + b


def resolve_scan_mode(settings) -> str:
    """The scan_pipeline mode this session would run: 'off', 'host' or
    'device' ('auto' resolves by backend — device decode pays off when
    a wire separates host and chip, not on a CPU test mesh)."""
    if settings is None:
        return "off"
    raw = settings.get("scan_pipeline")
    if raw != "auto":
        return raw
    import jax

    return "device" if jax.default_backend() != "cpu" else "host"


class _Shed(Exception):
    """Internal: an OOM while prefetching — drain and retry eagerly."""


# ---------------------------------------------------------------------------
# wire encodings (host side)

def _encode_for(buf: np.ndarray):
    """Frame-of-reference pack an integer buffer to the narrowest
    unsigned width; None when no narrower width exists."""
    if buf.size == 0:
        return None
    mn = int(buf.min())
    span = int(buf.max()) - mn
    for limit, wdt in ((1 << 8, np.uint8), (1 << 16, np.uint16),
                       (1 << 32, np.uint32)):
        if span < limit:
            if np.dtype(wdt).itemsize >= buf.dtype.itemsize:
                return None
            wire = (buf.astype(np.int64) - mn).astype(wdt)
            return wire, np.asarray(mn, dtype=buf.dtype)
    return None


def _encode_dict(buf: np.ndarray):
    """Dictionary-code a low-NDV float buffer (codes + LUT); None when
    the column is too distinct (or carries NaN) to pay for itself."""
    if buf.size == 0 or np.isnan(buf).any():
        return None
    flat = buf.reshape(-1)
    if flat.size > 4 * _NDV_SAMPLE:
        step = max(1, flat.size // _NDV_SAMPLE)
        if len(np.unique(flat[::step])) > _DICT_MAX_NDV // 4:
            return None  # sample already too distinct: skip the full sort
    lut = np.unique(flat)
    if len(lut) > _DICT_MAX_NDV:
        return None
    wdt = np.uint8 if len(lut) <= 256 else np.uint16
    codes = np.searchsorted(lut, buf).astype(wdt)
    if codes.nbytes + lut.nbytes >= buf.nbytes:
        return None
    return codes, lut.astype(buf.dtype)


def encode_column(buf: np.ndarray):
    """(kind, wire, extra) for one assembled feed buffer: 'for' (wire =
    offsets, extra = base scalar), 'dict' (wire = codes, extra = LUT)
    or 'plain' (wire = buf)."""
    if np.issubdtype(buf.dtype, np.integer) and \
            buf.dtype.itemsize > 1:
        packed = _encode_for(buf)
        if packed is not None:
            return "for", packed[0], packed[1]
    if np.issubdtype(buf.dtype, np.floating):
        packed = _encode_dict(buf)
        if packed is not None:
            return "dict", packed[0], packed[1]
    return "plain", buf, None


# ---------------------------------------------------------------------------
# on-device decode, XLA formulations on every mesh

@jax.jit
def _for_expand(wire, base):
    with stage_scope("decode"), stage_scope("for"):
        return wire.astype(base.dtype) + base


@jax.jit
def _dict_expand(codes, lut):
    # the decoded column keeps the codes' sharding; said outright,
    # because a gather from the replicated LUT by mesh-sharded indices
    # is one jax will not resolve by itself on an explicit-axis mesh
    with stage_scope("decode"), stage_scope("dict"):
        return lut.at[codes.astype(jnp.int32)].get(
            out_sharding=jax.typeof(codes).sharding)


@functools.partial(jax.jit, static_argnames=("cap",))
def _bits_expand(packed, cap):
    with stage_scope("decode"), stage_scope("bits"):
        shifts = jnp.arange(7, -1, -1, dtype=jnp.uint8)
        bits = (packed[..., None] >> shifts) & jnp.uint8(1)
        return bits.reshape(packed.shape[:-1] + (cap,)).astype(bool)


@functools.partial(jax.jit, static_argnames=("cap",))
def _valid_expand(rows, cap):
    with stage_scope("decode"), stage_scope("valid"):
        return jnp.arange(cap, dtype=jnp.int32)[None, :] < rows


# ---------------------------------------------------------------------------
# the pipeline

def maybe_pipelined_feed(node, catalog, store, mesh, n_dev: int,
                         compute_dtype, counters=None, accountant=None,
                         category: str = "feed", stats=None):
    """Build `node`'s feed through the pipelined path, or return None
    (caller proceeds on the eager path): scan_pipeline off / too small
    under 'auto' / open-transaction overlay on the table / the
    pipeline shed itself after a prefetch OOM."""
    from .feed import _overlay_touches

    settings = store.settings
    mode = resolve_scan_mode(settings)
    if mode == "off":
        return None
    table = node.rel.table
    if _overlay_touches(store, table):
        return None  # session-private visibility: eager reads it exactly
    if settings.get("scan_pipeline") == "auto" and \
            store.table_row_count(table) < AUTO_MIN_ROWS:
        return None
    from .hbm import accountant_for

    acc = accountant_for(store.data_dir) if accountant is None \
        else accountant
    pipe = _ScanPipeline(node, catalog, store, mesh, n_dev,
                         compute_dtype, mode, counters, acc, category,
                         stats, settings.get("scan_prefetch_depth"))
    try:
        return pipe.run()
    except _Shed:
        # prefetch OOM: the pipeline drained (every prefetch charge
        # released) — the eager retry is the cheapest rung of all
        return None


class _ScanPipeline:
    def __init__(self, node, catalog, store, mesh, n_dev, compute_dtype,
                 mode, counters, accountant, category, stats, depth):
        from ..catalog import DistributionMethod
        from .feed import make_chunk_filter

        self.node = node
        self.store = store
        self.mesh = mesh
        self.n_dev = n_dev
        self.mode = mode
        self.counters = counters
        self.acc = accountant
        self.category = category
        # tallies accumulate LOCALLY and fold into the executor-wide
        # accumulator only when the pipeline completes — a shed/failed
        # build's phase walls must not skew the published stats
        self.stats_out = stats
        self.stats = ScanPhaseStats() if stats is not None else None
        # producer-side tallies, folded into `counters` on the
        # STATEMENT thread when the pipeline finishes: incrementing
        # StatCounters from the short-lived producer thread would
        # append one never-reclaimed thread-local slot per feed build
        # (the same reason StreamBatcher passes its chunk filter no
        # counters)
        self.chunks_prefetched = 0
        self.chunks_skipped = 0
        self.table = node.rel.table
        meta = catalog.table(self.table)
        self.sharded = meta.method == DistributionMethod.HASH
        self.colnames = [cid.split(".", 1)[1] for cid in node.columns]
        self.dtypes = []
        for cname in self.colnames:
            dt = meta.schema.column(cname).dtype.numpy_dtype
            if dt == np.float64 and compute_dtype is not None:
                dt = np.dtype(compute_dtype)
            self.dtypes.append(np.dtype(dt))
        self.storage_of = {c: store.storage_column_name(self.table, c)
                           for c in self.colnames}
        name_map = {c.name: store.storage_column_name(self.table, c.name)
                    for c in meta.schema.columns}
        # counters=None: the filter runs on the producer thread; skips
        # are tallied from the selection result and folded later
        self.chunk_filter = (make_chunk_filter(node.filter, None,
                                               name_map)
                             if node.filter is not None else None)
        # read units: (dev, shard_id, record) in shard order — the same
        # order the eager path concatenates, so rows land identically
        self.tasks: list[list] = []
        shards = catalog.table_shards(self.table)
        if self.sharded:
            from ..planner.plan import table_placement

            placement = table_placement(catalog, self.table, n_dev)
            for s, dev in zip(shards, placement):
                if node.pruned_shards is not None and \
                        s.shard_index not in node.pruned_shards:
                    continue
                for rec in store.shard_stripe_records(self.table,
                                                      s.shard_id):
                    self.tasks.append([dev, s.shard_id, rec])
        else:
            if len(shards) != 1:
                from ..errors import ExecutionError

                raise ExecutionError(
                    f"table {self.table}: expected single shard")
            for rec in store.shard_stripe_records(self.table,
                                                  shards[0].shard_id):
                self.tasks.append([0, shards[0].shard_id, rec])
        # per-task layout, filled by the first column pass:
        # [dest_offset, n_rows, selected_chunks|None, keep_mask|None,
        #  n_chunks]
        self.layout: list[list] = [[0, 0, None, None, 0]
                                   for _ in self.tasks]
        self.dev_rows = [0] * (n_dev if self.sharded else 1)
        self.cap = 0
        self._readers: dict[str, object] = {}
        self.q: queue.Queue = queue.Queue(maxsize=max(1, int(depth)))
        self.stop_evt = threading.Event()

    # -- producer ----------------------------------------------------------
    def _verified(self, sid: int, fname: str, fn):
        """verified_read with the eager path's failover contract: the
        `store.read_shard` seam fires per stripe read, and a failed
        read carries (table, shard_id) so the statement retry loop can
        mark the placement suspect and route the next attempt to a
        surviving replica (read_shard tags eager reads the same way —
        without this, a dead copy would fail every retry while a
        healthy replica sat idle)."""
        from ..errors import StorageError
        from ..utils.faultinjection import fault_point

        try:
            fault_point("store.read_shard")
            return self.store.verified_read(self.table, sid, fname, fn)
        except Exception as e:
            if isinstance(e, (StorageError, OSError)) or \
                    getattr(e, "injected_fault", False):
                e.table = self.table
                e.shard_id = sid
            raise

    def _reader(self, path: str):
        r = self._readers.get(path)
        if r is None:
            from ..storage.format import StripeReader

            r = StripeReader(path, verify=self.store._verify_enabled())
            self._readers[path] = r
        return r

    def _read_stripe_column(self, ti: int, cname: str, first: bool):
        """One (stripe, column) read through the replica-failover seam.
        Returns (values, validity, n) AFTER delete-mask filtering; the
        first column's pass records the chunk selection + keep mask the
        later columns are pinned to."""
        dev, sid, rec = self.tasks[ti]
        lay = self.layout[ti]
        storage = self.storage_of[cname]
        dmask = (self.store.effective_delete_mask(self.table, sid, rec)
                 if first else None)

        def read_one(path):
            reader = self._reader(path)
            present_all = [self.storage_of[c] for c in self.colnames
                           if self.storage_of[c] in reader._by_name]
            if first:
                # chunk selection over the FULL projection's stats,
                # computed once and pinned for every column; stripes
                # with deletions read whole (positions must align with
                # the bitmap), trading chunk skipping for correctness
                if dmask is None and self.chunk_filter is not None \
                        and present_all:
                    lay[2] = reader.selected_chunks(present_all,
                                                    self.chunk_filter)
                lay[3] = None if dmask is None or not dmask.any() \
                    else ~dmask
                # stash the total only: the tally happens once per
                # stripe AFTER verified_read returns — this closure
                # re-runs on a replica-failover retry and would
                # double-count (idempotent slot write, not an append)
                lay[4] = reader.n_chunks
            sel = lay[2]
            n_sel = (reader.row_count if sel is None
                     else sum(reader.footer["chunk_rows"][i]
                              for i in sel))
            if storage not in reader._by_name:
                # column added by ALTER TABLE after this stripe was
                # written: reads as all-NULL (eager-path contract)
                dt = self.dtypes[self.colnames.index(cname)]
                return (np.zeros(n_sel, dtype=dt),
                        np.zeros(n_sel, dtype=np.bool_), n_sel)
            rv, rm, rn = reader.read([storage], chunks=sel)
            return rv[storage], rm[storage], rn

        v, m, n = self._verified(sid, rec["file"], read_one)
        if first:
            n_ch = len(lay[2]) if lay[2] is not None else lay[4]
            self.chunks_prefetched += n_ch
            self.chunks_skipped += lay[4] - n_ch
            self._stat(chunks_prefetched=n_ch)
        keep = lay[3]
        if keep is not None:
            v, m = v[keep], m[keep]
            n = int(keep.sum())
        return dev if self.sharded else 0, v, m, n

    def _assemble(self, ci: int, pieces=None):
        """[n_dev, cap] (or [cap]) buffer + nulls plane for column ci —
        from the first pass's saved pieces, or by re-reading at the
        recorded offsets."""
        from ..utils.faultinjection import fault_point

        cname = self.colnames[ci]
        dtype = self.dtypes[ci]
        shape = ((len(self.dev_rows), self.cap) if self.sharded
                 else (self.cap,))
        buf = np.zeros(shape, dtype=dtype)
        nbuf = None
        for ti in range(len(self.tasks)):
            if pieces is not None:
                dev, v, m, n = pieces[ti]
            else:
                fault_point("executor.scan_prefetch")
                dev, v, m, n = self._read_stripe_column(ti, cname,
                                                        first=False)
            off = self.layout[ti][0]
            if n == 0:
                continue
            dst = buf[dev] if self.sharded else buf
            dst[off:off + n] = v.astype(dtype)
            if not m.all():
                if nbuf is None:
                    nbuf = np.zeros(shape, dtype=bool)
                ndst = nbuf[dev] if self.sharded else nbuf
                ndst[off:off + n] = ~m
        return buf, nbuf

    def _first_pass(self):
        """Read column 0 across every stripe, recording the layout
        (offsets, chunk selections, keep masks) every later column is
        pinned to.  A zero-column projection (bare count(*)) needs only
        row counts: footers + delete masks, no chunk decode at all —
        cheaper than the eager path, which reads every column to count
        rows."""
        from ..utils.faultinjection import fault_point

        pieces = []
        for ti in range(len(self.tasks)):
            # named seam: a prefetch death must drain the pipeline into
            # a clean statement error, never a hang or a leaked charge
            fault_point("executor.scan_prefetch")
            if self.colnames:
                dev, v, m, n = self._read_stripe_column(
                    ti, self.colnames[0], first=True)
                pieces.append((dev, v, m, n))
            else:
                dev, sid, rec = self.tasks[ti]
                dev = dev if self.sharded else 0
                dmask = self.store.effective_delete_mask(
                    self.table, sid, rec)
                n = self._verified(
                    sid, rec["file"],
                    lambda p: self._reader(p).row_count)
                if dmask is not None and dmask.any():
                    n = int((~dmask).sum())
            lay = self.layout[ti]
            lay[0] = self.dev_rows[dev]
            lay[1] = n
            self.dev_rows[dev] += n
        from .compiler import _round_cap

        self.cap = _round_cap(max(self.dev_rows)
                              if any(self.dev_rows) else 1)
        return pieces

    def _place(self, arr, category=None):
        """Accounted placement from the producer thread — the transfer
        is in flight while the next column decodes.  Sharded buffers go
        through the device-owned slice seam: each device's row slice
        (built from only the shards it owns) dispatches as its own
        transfer and charges its own per-device bytes."""
        cat = self.category if category is None else category
        if self.sharded:
            slices = [arr[d] for d in range(arr.shape[0])]
            out = self.acc.place_sharded_slices_tracked(
                self.mesh, slices, cat)
            if self.stats is not None:
                self.stats.add_device_bytes([s.nbytes for s in slices])
            return out
        return self.acc.place_tracked(self.mesh, arr, False, cat)

    def _encode_and_place(self, ci: int, buf, nbuf):
        """Wire-encode (device mode) + place one column; returns the
        queue payload the consumer finishes."""
        from ..stats.tracing import trace_span

        t0 = time.perf_counter()
        if self.mode != "device":
            with trace_span("scan.transfer"):
                arr, h = self._place(buf, "prefetch")
                payload = {"kind": "plain", "arr": arr, "handle": h,
                           "wire": buf.nbytes, "decoded": buf.nbytes}
                if nbuf is not None:
                    narr, nh = self._place(nbuf, "prefetch")
                    payload.update(
                        nulls=narr, nulls_handle=nh,
                        wire=payload["wire"] + nbuf.nbytes,
                        decoded=payload["decoded"] + nbuf.nbytes)
            self._stat(transfer_seconds=time.perf_counter() - t0)
            return payload
        with trace_span("scan.wire_encode"):
            kind, wire, extra = encode_column(buf)
        t1 = time.perf_counter()
        with trace_span("scan.transfer"):
            arr, h = self._place(wire, "prefetch")
            payload = {"kind": kind, "arr": arr, "handle": h,
                       "dtype": buf.dtype, "wire": wire.nbytes,
                       "decoded": buf.nbytes}
            if kind == "for":
                payload["base"] = extra
            elif kind == "dict":
                lut, lh = self.acc.place_tracked(self.mesh, extra,
                                                 False, "prefetch")
                payload.update(lut=lut, lut_handle=lh,
                               wire=payload["wire"] + extra.nbytes)
            if nbuf is not None:
                packed = np.packbits(nbuf, axis=-1)
                narr, nh = self._place(packed, "prefetch")
                payload.update(nulls=narr, nulls_handle=nh,
                               nulls_packed=True,
                               wire=payload["wire"] + packed.nbytes,
                               decoded=payload["decoded"] + nbuf.nbytes)
        self._stat(decode_seconds=t1 - t0,
                   transfer_seconds=time.perf_counter() - t1)
        return payload

    def _valid_payload(self):
        from ..stats.tracing import trace_span

        t0 = time.perf_counter()
        with trace_span("scan.transfer"):
            if self.mode == "device" and self.sharded:
                rows = np.asarray(self.dev_rows,
                                  dtype=np.int32).reshape(-1, 1)
                arr, h = self._place(rows, "prefetch")
                payload = {"kind": "rows", "arr": arr, "handle": h,
                           "wire": rows.nbytes,
                           "decoded": len(self.dev_rows) * self.cap}
            else:
                if self.sharded:
                    valid = np.zeros((len(self.dev_rows), self.cap),
                                     dtype=bool)
                    for d, r in enumerate(self.dev_rows):
                        valid[d, :r] = True
                else:
                    valid = np.zeros(self.cap, dtype=bool)
                    valid[:self.dev_rows[0]] = True
                arr, h = self._place(valid, "prefetch")
                payload = {"kind": "plain", "arr": arr, "handle": h,
                           "wire": valid.nbytes,
                           "decoded": valid.nbytes}
        self._stat(transfer_seconds=time.perf_counter() - t0)
        return payload

    def _stat(self, **kw):
        if self.stats is not None:
            self.stats.add(**kw)

    def _put(self, item) -> bool:
        while not self.stop_evt.is_set():
            try:
                self.q.put(item, timeout=0.2)
                return True
            except queue.Full:
                continue
        return False

    def _produce(self):
        from ..stats.tracing import adopt_context, trace_span
        from ..utils.faultinjection import fault_point

        # the producer adopts the statement's trace context: its
        # prefetch/encode/transfer spans nest under the span that was
        # open when run() captured the token (the feed build), on the
        # producer's own track — any span this thread leaves open is
        # force-closed and counted by adopt_context on the way out
        with adopt_context(self._trace_ctx):
            try:
                t0 = time.perf_counter()
                # classification parity with the eager path: the
                # feed-level placement seam fires here too, before any
                # transfer starts
                fault_point("executor.device_put")
                with trace_span("scan.prefetch"):
                    pieces = self._first_pass()
                self._stat(prefetch_seconds=time.perf_counter() - t0)
                if self.colnames:
                    buf, nbuf = self._assemble(0, pieces)
                    del pieces
                    if not self._put(("col", self.node.columns[0],
                                      self._encode_and_place(0, buf,
                                                             nbuf))):
                        return
                    del buf, nbuf
                for ci in range(1, len(self.colnames)):
                    t0 = time.perf_counter()
                    with trace_span("scan.prefetch"):
                        buf, nbuf = self._assemble(ci)
                    self._stat(
                        prefetch_seconds=time.perf_counter() - t0)
                    if not self._put(("col", self.node.columns[ci],
                                      self._encode_and_place(ci, buf,
                                                             nbuf))):
                        return
                    del buf, nbuf
                if not self._put(("valid", None,
                                  self._valid_payload())):
                    return
                self._put(("done", None, None))
            except DeviceMemoryExhausted as e:
                self._put(("shed", None, e))
            except BaseException as e:  # graftlint: ignore[swallowed-base-exception] — not swallowed: forwarded over the queue and re-raised on the consumer thread
                self._put(("err", None, e))

    # -- consumer ----------------------------------------------------------
    def _finish_col(self, payload, category=None):
        """Adopt one placed column on the statement thread: recharge a
        plain placement to its final category, or expand a wire payload
        on-device and adopt the decoded output."""
        from ..utils.faultinjection import fault_point

        cat = self.category if category is None else category
        self._stat(bytes_on_wire=payload["wire"],
                   bytes_decoded=payload["decoded"])
        kind = payload["kind"]
        decoded_nulls = None
        from ..stats.tracing import trace_span

        if payload.get("nulls") is not None:
            if payload.get("nulls_packed"):
                fault_point("executor.device_decode")
                t0 = time.perf_counter()
                with trace_span("scan.device_decode"):
                    decoded_nulls = _bits_expand(payload["nulls"],
                                                 self.cap)
                    self.acc.adopt(decoded_nulls, self.sharded,
                                   self.n_dev, cat)
                self._stat(
                    device_decode_seconds=time.perf_counter() - t0)
                self._count_decoded(decoded_nulls)
            else:
                self.acc.recharge(payload["nulls_handle"], cat)
                decoded_nulls = payload["nulls"]
        if kind == "plain":
            self.acc.recharge(payload["handle"], cat)
            return payload["arr"], decoded_nulls
        # named seam: a failure while expanding a wire payload must
        # surface as a clean statement error with the charge released
        fault_point("executor.device_decode")
        t0 = time.perf_counter()
        with trace_span("scan.device_decode"):
            if kind == "for":
                decoded = _for_expand(payload["arr"], payload["base"])
            elif kind == "dict":
                decoded = _dict_expand(payload["arr"], payload["lut"])
            else:  # rows → valid prefix
                decoded = _valid_expand(payload["arr"], self.cap)
            self.acc.adopt(decoded, self.sharded, self.n_dev, cat)
        self._stat(device_decode_seconds=time.perf_counter() - t0)
        self._count_decoded(decoded)
        return decoded, decoded_nulls

    def _count_decoded(self, arr) -> None:
        if self.counters is not None:
            from ..stats.counters import DEVICE_DECODED_BYTES_TOTAL

            self.counters.increment(DEVICE_DECODED_BYTES_TOTAL,
                                    int(arr.nbytes))

    def run(self):
        from ..stats.tracing import capture_context
        from ..utils.cancellation import check_cancel
        from .compiler import FeedSpec

        # hand the statement's trace context to the producer thread
        # (None when nothing is being traced — adoption then no-ops)
        self._trace_ctx = capture_context()
        t = threading.Thread(target=self._produce, daemon=True,
                             name="scan-prefetch")
        t.start()
        arrays: dict = {}
        nulls: dict = {}
        valid = None
        waiting = False
        got_first = False
        try:
            while True:
                # queue pops are the consumer's cancellation seams (the
                # finally below unwinds the producer cleanly)
                check_cancel()
                try:
                    kind, cid, payload = self.q.get(timeout=0.25)
                except queue.Empty:
                    # the initial fill is not an underrun: the first
                    # column's full read can never be hidden behind a
                    # previous one, so counting it would stamp one
                    # noise stall on every feed regardless of depth
                    if not waiting and got_first:
                        waiting = True
                        self._stat(prefetch_stalls=1)
                        if self.counters is not None:
                            from ..stats.counters import (
                                PREFETCH_STALLS_TOTAL,
                            )

                            self.counters.increment(
                                PREFETCH_STALLS_TOTAL)
                    continue
                waiting = False
                got_first = True
                if kind == "err":
                    raise payload
                if kind == "shed":
                    # the SAME statement attempt redoes this feed
                    # eagerly (its chunk filter counts skips afresh):
                    # folding the discarded build's tallies too would
                    # double-report the statement's chunk accounting
                    self.chunks_prefetched = self.chunks_skipped = 0
                    raise _Shed()
                if kind == "done":
                    break
                if kind == "col":
                    a, nb = self._finish_col(payload)
                    arrays[cid] = a
                    if nb is not None:
                        nulls[cid] = nb
                else:  # valid
                    valid, _ = self._finish_col(payload)
        finally:
            self.stop_evt.set()
            while True:  # drain so a blocked put wakes immediately
                try:
                    self.q.get_nowait()
                except queue.Empty:
                    break
            t.join(timeout=5.0)
            # fold producer tallies on THIS (statement) thread — a
            # per-producer-thread increment would leak counter slots
            if self.counters is not None:
                from ..stats.counters import (
                    CHUNKS_PREFETCHED_TOTAL,
                    CHUNKS_SKIPPED,
                )

                if self.chunks_prefetched:
                    self.counters.increment(CHUNKS_PREFETCHED_TOTAL,
                                            self.chunks_prefetched)
                if self.chunks_skipped:
                    self.counters.increment(CHUNKS_SKIPPED,
                                            self.chunks_skipped)
        self._stat(feeds_pipelined=1)
        if self.stats_out is not None:
            self.stats_out.merge(self.stats)
        return FeedSpec(node=self.node, sharded=self.sharded,
                        arrays=arrays, nulls=nulls, valid=valid,
                        capacity=self.cap,
                        dev_rows=(list(self.dev_rows) if self.sharded
                                  else None))
