"""Persistent compiled-executable cache + single-flight compile dedup.

The serving layer made the steady state fast, but every fresh process
re-paid parse → plan → XLA compile per plan shape: a deploy/restart
under live traffic was a compile storm.  The inference-serving move
(PystachIO, PAPERS.md) treats compiled artifacts as durable, versioned
state that is *loaded* — not recomputed — on startup:

* **ExecutableCache** — one per data_dir (the lock_manager_for /
  workload_manager_for pattern): serialized AOT executables
  (``jax.experimental.serialize_executable``) written through the PR-7
  durable-io seam into ``<data_dir>/exec_cache/``.  Each entry is a
  checksummed meta JSON (``atomic_write_json_checked`` — version, env
  stamp, the full plan-cache key, unpack metadata, payload CRC) plus a
  framed binary payload; the payload write lands FIRST, the meta write
  is the commit point, so a power cut between the two leaves an
  invisible orphan, never a torn entry.  Corrupt, torn, truncated or
  version/backend-skewed entries are *detected* (CRC + stamp check) and
  fall back to a clean recompile — never a crash, never a wrong or
  stale executable.

* **CompileGate** — single-flight compile dedup: one in-flight compile
  per cache key per data_dir.  N sessions hitting a cold shape produce
  ONE compile; followers wait in cancellation-aware slices under their
  own ``statement_timeout_ms`` budget.  The serving batcher's ledger
  invariant holds: every follower resolves answered XOR cleanly
  errored XOR promoted (a leader dying on a BaseException or its own
  cancel hands leadership to a waiting follower — no stranded
  waiters).

Trust model: the executable payload is deserialized via jax's pjrt
unpickler (there is no JSON encoding of a compiled binary), so the
cache directory sits in the same trust domain as the data files beside
it — the CRC/stamp checks defend against *rot and skew*, not a
malicious writer with filesystem access (who could corrupt the stripes
directly).  Everything else persisted here stays JSON.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import zlib

from ..errors import StorageError

# 2 (PR 32): an entry carries the program's deferred-column counts, and
# the program of an unchanged key changed (columns cross a compaction
# as a row index) — the key holds the plan, the capacities and the
# environment, not the engine's code, so a data directory written
# before the upgrade must not keep serving the old program
# 3 (PR 34): beside them the probe slots of its fused lookups
# 4 (PR 35): a program with a recorded exchange returns the exchange's
# fullest bucket and rows sent after its stage actuals
# 5 (PR 36): a fourth tally, the packed slots of its bucketed group-bys
# 6 (PR 40): a fifth, the slots of its window operators
# 7: three more, the index slots of its deferred gathers and its index
# chains composed from the top, with their middles gathered twice
# 8: three more, its windows' lexsorts and ORDER BY specs read in the
# groups' order, and the operands of its multi-key joins' sorts; a
# window over groups with an ORDER BY or a multi-key join with known
# key ranges compiles another program
# 9: a twelfth, the index slots of its sort-path group-bys' gathers; a
# sort-path group-by under a capacity compiles another program
# 10: a thirteenth, its fused lookups whose probe side compacts to the
# build keys' range; every fused inner lookup records that range's rows
EXEC_CACHE_VERSION = 10
EXEC_CACHE_DIR = "exec_cache"
# on-disk entry bound per data_dir: retry/tightening intermediates and
# dead shapes age out coldest-first (hits, then insertion sequence)
EXEC_CACHE_MAX_ENTRIES = 512
# coalesce index rewrites: the hit/seq index is advisory (warmup
# ordering) — rebuildable from entry mtimes — so it flushes debounced
INDEX_FLUSH_EVERY = 16

_MAGIC = b"CTEX1\n"


# -- key / metadata serialization -------------------------------------------
# The plan-cache key is a nested tuple of strings, ints, floats, bools
# and Nones (plan fingerprint, n_devices, dtype, feed signature, caps
# signature, probe kernel) — the same JSON-safe shape as the caps memo,
# encoded the same way (tuples tagged so they round-trip).
def key_to_json(obj):
    if isinstance(obj, tuple):
        return {"t": [key_to_json(x) for x in obj]}
    if isinstance(obj, dict):
        return {"d": [[key_to_json(k), key_to_json(v)]
                      for k, v in obj.items()]}
    # numpy scalars ride in some fingerprints (key extents, repart
    # caps): coerce to python scalars — hash/equality agree, so a key
    # reconstructed from JSON still hits the in-memory plan cache
    if isinstance(obj, bool) or obj is None or \
            isinstance(obj, (int, float, str)):
        return obj
    import numpy as _np

    if isinstance(obj, _np.bool_):
        return bool(obj)
    if isinstance(obj, _np.integer):
        return int(obj)
    if isinstance(obj, _np.floating):
        return float(obj)
    return obj


def key_from_json(obj):
    if isinstance(obj, dict) and "t" in obj:
        return tuple(key_from_json(x) for x in obj["t"])
    if isinstance(obj, dict) and "d" in obj:
        return {key_from_json(k): key_from_json(v) for k, v in obj["d"]}
    return obj


def env_stamp(mesh) -> dict:
    """The environment a serialized executable is only valid in: cache
    format version, jax version, backend platform + device kind, and
    the exact mesh device ids (a shrunken post-failover mesh compiles
    different programs than the full one).  Part of the entry hash —
    a skewed entry is never even looked up — AND re-verified from the
    meta on load (defense in depth against hand-moved files)."""
    import jax

    devs = list(mesh.devices.flat)
    return {
        "cache_version": EXEC_CACHE_VERSION,
        "jax": jax.__version__,
        "platform": devs[0].platform,
        "device_kind": getattr(devs[0], "device_kind", ""),
        "devices": [d.id for d in devs],
    }


def _canonical(obj) -> bytes:
    return json.dumps(obj, sort_keys=True, separators=(",", ":")).encode()


def entry_hash(key, stamp: dict) -> str:
    h = hashlib.sha256()
    h.update(_canonical(key_to_json(key)))
    h.update(b"\0")
    h.update(_canonical(stamp))
    return h.hexdigest()[:40]


def _frame(blobs: list[bytes]) -> bytes:
    out = [_MAGIC]
    for b in blobs:
        out.append(len(b).to_bytes(8, "little"))
        out.append(b)
    return b"".join(out)


def _unframe(data: bytes, n: int) -> list[bytes]:
    if not data.startswith(_MAGIC):
        raise ValueError("exec-cache payload: bad magic")
    off = len(_MAGIC)
    blobs = []
    for _ in range(n):
        if off + 8 > len(data):
            raise ValueError("exec-cache payload: truncated length")
        ln = int.from_bytes(data[off:off + 8], "little")
        off += 8
        if off + ln > len(data):
            raise ValueError("exec-cache payload: truncated blob")
        blobs.append(data[off:off + ln])
        off += ln
    return blobs


def _clone_error(e: Exception) -> Exception:
    """Per-follower copy of a leader's compile failure (sharing one
    exception object across raising threads would share tracebacks);
    classifier markers ride along so each session's retry envelope
    treats it exactly like a solo failure (the serving batcher's
    pattern)."""
    try:
        clone = type(e)(*e.args)
    except Exception:
        clone = StorageError(f"deduped compile failed: {e}")
    for attr in ("injected_fault", "fault_point", "post_visibility"):
        if hasattr(e, attr):
            try:
                setattr(clone, attr, getattr(e, attr))
            except Exception:  # graftlint: ignore[silent-exception] — best-effort marker copy: a clone type refusing ONE attr must not drop the remaining markers or the error itself
                continue
    return clone


class _Flight:
    __slots__ = ("evt", "entry", "error", "promote")

    def __init__(self):
        self.evt = threading.Event()
        self.entry = None
        self.error: Exception | None = None
        self.promote = False


class CompileGate:
    """Single-flight compile dedup: one in-flight compile per key.

    ``run(key, compile_fn)`` either leads (runs ``compile_fn`` and
    publishes the entry to every waiter) or follows (waits, in
    cancellation-aware slices, for the leader's entry).  Ledger: every
    caller resolves answered XOR cleanly errored XOR promoted —
    a leader that dies on a BaseException (power cut, interpreter
    teardown) or on its own cancel/timeout hands leadership to a
    self-promoting follower instead of erroring innocents."""

    def __init__(self):
        self._mu = threading.Lock()
        self._flights: dict = {}
        # shared-layer totals (bench cold_start + the fan-in test read
        # these; per-session counters fold requester-side).  A flight
        # is one gated RESOLVE (disk load or compile — the owning
        # ExecutableCache counts actual compiles separately)
        self.flights_led_total = 0
        self.deduped_total = 0
        self.promoted_total = 0
        self.errored_followers_total = 0

    def run(self, key, compile_fn):
        """Returns ``(entry, deduped)``; raises the compile failure
        (leaders raise their own, followers a per-waiter clone)."""
        from ..errors import QueryCanceled, StatementTimeout
        from ..utils.cancellation import check_cancel

        while True:
            with self._mu:
                fl = self._flights.get(key)
                lead = fl is None
                if lead:
                    fl = self._flights[key] = _Flight()
            if lead:
                try:
                    entry = compile_fn()
                except BaseException as e:
                    with self._mu:
                        self._flights.pop(key, None)
                        if isinstance(e, Exception) and \
                                not isinstance(e, (QueryCanceled,
                                                   StatementTimeout)):
                            # a real compile failure: followers raise a
                            # clone and their own envelopes classify it
                            fl.error = e
                        else:
                            # leader death / leader-local cancel:
                            # innocent followers self-promote instead
                            # of inheriting a failure they never caused
                            fl.promote = True
                    fl.evt.set()
                    raise
                with self._mu:
                    fl.entry = entry
                    self._flights.pop(key, None)
                    self.flights_led_total += 1
                fl.evt.set()
                return entry, False
            from ..stats.tracing import trace_span

            with trace_span("compile.single_flight_wait"):
                while not fl.evt.wait(0.005):
                    check_cancel()  # deadline / Session.cancel() seam
            if fl.promote:
                with self._mu:
                    self.promoted_total += 1
                continue  # self-promote: next loop may lead
            if fl.error is not None:
                with self._mu:
                    self.errored_followers_total += 1
                raise _clone_error(fl.error)
            with self._mu:
                self.deduped_total += 1
            return fl.entry, True

    def snapshot(self) -> dict:
        with self._mu:
            return {
                "in_flight": len(self._flights),
                "flights_led_total": self.flights_led_total,
                "deduped_total": self.deduped_total,
                "promoted_total": self.promoted_total,
                "errored_followers_total": self.errored_followers_total,
            }


class ExecutableCache:
    """Per-data_dir on-disk cache of serialized compiled executables."""

    def __init__(self, data_dir: str):
        self.dir = os.path.join(data_dir, EXEC_CACHE_DIR)
        self.gate = CompileGate()
        self._mu = threading.Lock()
        # hash → {"hits": n, "seq": m}: the warmup ordering source.
        # Advisory — corrupt/absent index rebuilds from entry mtimes
        self._index: dict[str, dict] = {}
        self._seq = 0
        self._index_loaded = False
        self._index_dirty = 0
        # shared-layer totals (citus_stat-style; per-session counters
        # fold requester-side in the runner).  compiles_total counts
        # ACTUAL PlanCompiler builds (the runner bumps it inside its
        # gated compile_fn) — the fan-in/storm "zero redundant
        # compiles" assertions read this, not flight counts
        self.hits_total = 0
        self.misses_total = 0
        self.rejects_total = 0
        self.stores_total = 0
        self.compiles_total = 0

    def note_compile(self) -> None:
        with self._mu:
            self.compiles_total += 1

    # -- paths ---------------------------------------------------------------
    def _meta_path(self, h: str) -> str:
        return os.path.join(self.dir, f"{h}.meta.json")

    def _bin_path(self, h: str) -> str:
        return os.path.join(self.dir, f"{h}.bin")

    def _index_path(self) -> str:
        return os.path.join(self.dir, "index.json")

    def has_entries(self) -> bool:
        try:
            return any(f.endswith(".meta.json")
                       for f in os.listdir(self.dir))
        except OSError:
            return False

    # -- load ----------------------------------------------------------------
    def load(self, key, mesh):
        """Resolve `key` from disk.  Returns ``(entry, status)`` where
        entry is the plan-cache tuple ``(compiled_fn, out_meta,
        stage_keys, shuffle_bytes, tallies)`` or None, and status is
        ``'hit' | 'miss' | 'reject'``.  Every failure mode — torn or
        bit-flipped payload, corrupt meta, version/backend/mesh skew,
        an unloadable executable — is *detected* and reported as a
        reject so the caller compiles cleanly; nothing here raises
        except an armed fault/cancel (cooperative seams)."""
        stamp = env_stamp(mesh)
        h = entry_hash(key, stamp)
        meta_path = self._meta_path(h)
        if not os.path.exists(meta_path):
            with self._mu:
                self.misses_total += 1
            return None, "miss"
        from ..utils.faultinjection import fault_point

        from ..errors import QueryCanceled, StatementTimeout

        try:
            # named seam INSIDE the guard: injected rot/IO failure
            # while adopting a persisted executable must end in a
            # counted reject + clean recompile, exactly like real rot
            fault_point("executor.exec_cache_load")
            entry = self._load_verified(h, meta_path, stamp, mesh)
        except (QueryCanceled, StatementTimeout):
            raise  # the statement's own deadline/cancel, not rot
        except Exception as e:  # graftlint: ignore[swallowed-fault-seam] — not swallowed into silence: THE contract of this seam is that rot (injected or real) downgrades to a counted reject + clean recompile, never a crash or a stale executable
            with self._mu:
                self.rejects_total += 1
            if self._is_verified_rot(e):
                # only VERIFIED rot (CRC/magic/skew/torn commit)
                # deletes the entry; a transient EMFILE/EIO must not
                # destroy a payload that is actually intact
                self._drop_entry(h)
            return None, "reject"
        self._touch(h)
        with self._mu:
            self.hits_total += 1
        return entry, "hit"

    def load_hash(self, h: str, mesh):
        """Warmup path: adopt entry `h` by its hash, returning
        ``(key, entry)`` — or ``(None, None)`` when the entry is
        missing, skewed or corrupt (warmup skips it; the lazy path
        would reject it the same way)."""
        stamp = env_stamp(mesh)
        meta_path = self._meta_path(h)
        if not os.path.exists(meta_path):
            # pruned/dropped since top_hashes ranked it: not rot — the
            # rejects counter must only ever report DETECTED corruption
            return None, None
        try:
            meta = self._read_meta(meta_path, stamp)
            key = key_from_json(meta["key"])
            if entry_hash(key, stamp) != h:
                raise ValueError("exec-cache entry hash mismatch")
            entry = self._load_verified(h, meta_path, stamp, mesh,
                                        meta=meta)
        except Exception:
            with self._mu:
                self.rejects_total += 1
            return None, None
        self._touch(h)
        with self._mu:
            self.hits_total += 1
        return key, entry

    @staticmethod
    def _is_verified_rot(e: Exception) -> bool:
        """True when the load failure PROVES the entry is bad (corrupt
        meta/payload, version or environment skew, a bin file missing
        under a present meta = torn commit, malformed fields) rather
        than a transient IO condition."""
        from ..errors import CorruptStripe

        return isinstance(e, (CorruptStripe, ValueError, KeyError,
                              TypeError, FileNotFoundError,
                              EOFError))

    def _read_meta(self, meta_path: str, stamp: dict) -> dict:
        from ..utils.io import read_json_checked

        meta = read_json_checked(meta_path)  # raises CorruptStripe on rot
        if meta.get("version") != EXEC_CACHE_VERSION:
            raise ValueError("exec-cache entry version skew")
        if meta.get("stamp") != stamp:
            # backend / jax-version / mesh-shape skew: a stale
            # executable must never be served across an upgrade
            raise ValueError("exec-cache entry environment skew")
        return meta

    def _load_verified(self, h: str, meta_path: str, stamp: dict, mesh,
                       meta: dict | None = None):
        import pickle

        import numpy as np
        from jax.experimental import serialize_executable as _se

        if meta is None:
            meta = self._read_meta(meta_path, stamp)
        with open(self._bin_path(h), "rb") as f:
            data = f.read()
        if zlib.crc32(data) != meta["payload_crc32"]:
            raise ValueError("exec-cache payload checksum mismatch")
        exe, it, ot = _unframe(data, 3)
        # without execution_devices the executable loads onto EVERY
        # device of the backend, and a mesh narrower than the backend
        # then fails at dispatch with a shard-count mismatch
        compiled = _se.deserialize_and_load(
            exe, pickle.loads(it), pickle.loads(ot),
            execution_devices=list(mesh.devices.flat))
        out_meta = [(kind, cid, np.dtype(dt))
                    for kind, cid, dt in meta["out_meta"]]
        stage_keys = [tuple(sk) for sk in meta["stage_keys"]]
        return (compiled, out_meta, stage_keys,
                int(meta["shuffle_bytes"]),
                tuple(int(n) for n in meta["tallies"]))

    # -- store ---------------------------------------------------------------
    def store(self, key, mesh, compiled, out_meta, stage_keys,
              shuffle_bytes: int,
              tallies: tuple[int, ...] | None = None) -> bool:
        """Persist one compiled entry.  Best-effort for REAL IO errors
        (the in-memory entry still answers the statement; persistence
        is a warm-start optimization, like the caps memo) — but the
        named fault seam fires before the catch, so an injected fault
        propagates and the session retry envelope exercises the
        recompile path.  Returns True when the entry landed."""
        import pickle

        from ..utils.faultinjection import fault_point
        from ..utils.io import (
            atomic_write_bytes,
            atomic_write_json_checked,
        )
        from .compiler import NO_TALLIES

        fault_point("executor.exec_cache_store")
        stamp = env_stamp(mesh)
        h = entry_hash(key, stamp)
        try:
            from jax.experimental import serialize_executable as _se

            exe, in_tree, out_tree = _se.serialize(compiled)
            data = _frame([bytes(exe), pickle.dumps(in_tree),
                           pickle.dumps(out_tree)])
            os.makedirs(self.dir, exist_ok=True)
            # payload first, checksummed meta LAST (the commit point):
            # a power cut between the two leaves an invisible orphan
            # the next store simply overwrites
            atomic_write_bytes(self._bin_path(h), data)
            atomic_write_json_checked(self._meta_path(h), {
                "version": EXEC_CACHE_VERSION,
                "stamp": stamp,
                "key": key_to_json(key),
                "out_meta": [[kind, cid, str(dt)]
                             for kind, cid, dt in out_meta],
                "stage_keys": [list(sk) for sk in stage_keys],
                "shuffle_bytes": int(shuffle_bytes),
                "tallies": [int(n) for n in (tallies or NO_TALLIES)],
                "payload_crc32": zlib.crc32(data),
                "payload_bytes": len(data),
            })
        except Exception:  # graftlint: ignore[silent-exception] — best-effort by contract: a backend whose executables don't serialize (XlaRuntimeError UNIMPLEMENTED), unpicklable treedefs, or a full/read-only disk must NOT fail the statement — it already holds its in-memory executable; warm restarts just stay cold.  The named fault seam fired BEFORE this try, so injected faults still propagate.
            return False
        with self._mu:
            self.stores_total += 1
        self._touch(h)
        self._prune()
        return True

    # -- hotness index / warmup ordering -------------------------------------
    def _load_index_locked(self) -> None:
        if self._index_loaded:
            return
        self._index_loaded = True
        from ..utils.io import read_json_checked

        try:
            obj = read_json_checked(self._index_path())
            idx = {h: {"hits": int(v["hits"]), "seq": int(v["seq"])}
                   for h, v in obj["entries"].items()}
        except Exception:
            # absent/corrupt index: rebuild advisory ordering from
            # entry mtimes (the entries themselves stay verified)
            idx = {}
            try:
                metas = [f for f in os.listdir(self.dir)
                         if f.endswith(".meta.json")]
            except OSError:
                metas = []
            stats = []
            for f in metas:
                try:
                    stats.append((os.stat(
                        os.path.join(self.dir, f)).st_mtime, f))
                except OSError:
                    continue
            for i, (_, f) in enumerate(sorted(stats)):
                idx[f[:-len(".meta.json")]] = {"hits": 0, "seq": i}
        self._index = idx
        self._seq = max((v["seq"] for v in idx.values()), default=-1) + 1

    def _touch(self, h: str) -> None:
        flush = False
        with self._mu:
            self._load_index_locked()
            ent = self._index.get(h)
            if ent is None:
                ent = self._index[h] = {"hits": 0, "seq": 0}
            ent["hits"] += 1
            ent["seq"] = self._seq
            self._seq += 1
            self._index_dirty += 1
            if self._index_dirty >= INDEX_FLUSH_EVERY:
                self._index_dirty = 0
                flush = True
        if flush:
            self.flush_index()

    def flush_index(self) -> None:
        from ..utils.io import atomic_write_json_checked

        with self._mu:
            self._load_index_locked()
            payload = {"entries": dict(self._index)}
            self._index_dirty = 0
        try:
            os.makedirs(self.dir, exist_ok=True)
            atomic_write_json_checked(self._index_path(), payload)
        except OSError:
            pass  # advisory: warmup ordering degrades to mtimes

    def top_hashes(self, limit: int) -> list[str]:
        """Entry hashes hottest-first (hits desc, then recency desc) —
        the warmup phase's work list."""
        with self._mu:
            self._load_index_locked()
            ranked = sorted(self._index.items(),
                            key=lambda kv: (-kv[1]["hits"],
                                            -kv[1]["seq"]))
        out = []
        for h, _ in ranked:
            if os.path.exists(self._meta_path(h)):
                out.append(h)
            if len(out) >= max(0, limit):
                break
        return out

    # -- hygiene -------------------------------------------------------------
    def _drop_entry(self, h: str) -> None:
        for p in (self._meta_path(h), self._bin_path(h)):
            try:
                os.unlink(p)
            except OSError:
                pass
        with self._mu:
            self._load_index_locked()
            self._index.pop(h, None)

    def _prune(self) -> None:
        """Age out coldest entries beyond EXEC_CACHE_MAX_ENTRIES."""
        with self._mu:
            self._load_index_locked()
            if len(self._index) <= EXEC_CACHE_MAX_ENTRIES:
                return
            ranked = sorted(self._index.items(),
                            key=lambda kv: (kv[1]["hits"], kv[1]["seq"]))
            doomed = [h for h, _ in
                      ranked[:len(self._index) - EXEC_CACHE_MAX_ENTRIES]]
        for h in doomed:
            self._drop_entry(h)

    def snapshot(self) -> dict:
        with self._mu:
            return {
                "hits_total": self.hits_total,
                "misses_total": self.misses_total,
                "rejects_total": self.rejects_total,
                "stores_total": self.stores_total,
                "compiles_total": self.compiles_total,
                "entries": len(self._index) if self._index_loaded
                else None,
                **{f"gate_{k}": v for k, v in
                   self.gate.snapshot().items()},
            }


# process-wide registry: sessions sharing a data_dir share the cache
# AND the compile gate (the lock_manager_for pattern)
_registry: dict[str, ExecutableCache] = {}
_registry_mu = threading.Lock()


def exec_cache_for(data_dir: str) -> ExecutableCache:
    key = os.path.realpath(data_dir)
    with _registry_mu:
        if key not in _registry:
            _registry[key] = ExecutableCache(key)
        return _registry[key]
