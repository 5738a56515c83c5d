"""Per-shard stripe management: manifests, dictionaries, append/scan.

The reference keeps stripe metadata in catalog tables
(/root/reference/src/backend/columnar/columnar_metadata.c:171-181
columnar.stripe / chunk_group / chunk) with transactional visibility; here
each table has a MANIFEST.json updated by atomic rename, and the transaction
layer (citus_tpu.transaction) stages manifests for multi-table atomic ingest
(the 2PC analogue).

Directory layout::

    <data_dir>/
      catalog.json
      tables/<table>/
        MANIFEST.json
        dict_<column>.json
        shard_<shard_id>/stripe_<n>.ctps

An intermediate result (`catalog.is_intermediate`: a subplan's rows, held
for the one statement that scans them) has none of these: `hold_resident`
keeps its typed arrays and its manifest in memory, the readers below
answer from them, and nothing under its name on disk is ever read.
"""

from __future__ import annotations

import os
import threading

import numpy as np

from ..catalog import Catalog
from ..catalog.catalog import is_intermediate
from ..errors import CorruptStripe, StorageError
from ..utils import io as dio
from . import integrity
from .dictionary import Dictionary
from .format import StripeReader, write_stripe


def _column_stats(columns: dict[str, np.ndarray],
                  validity: dict[str, np.ndarray] | None) -> dict:
    """Per-column [min, max, null_count] over non-NULL values (JSON-safe
    scalars).  Pre-null-count manifests hold 2-element entries — readers
    must treat a missing third element as "may contain NULLs"."""
    out = {}
    for name, arr in columns.items():
        nulls = 0
        v = arr
        if validity is not None and name in validity:
            val = validity[name]
            nulls = int(len(val) - val.sum())
            v = arr[val]
        if arr.dtype == object or v.size == 0:
            out[name] = [None, None, nulls]
        elif np.issubdtype(v.dtype, np.floating):
            out[name] = [float(v.min()), float(v.max()), nulls]
        else:
            out[name] = [int(v.min()), int(v.max()), nulls]
    return out


# `file` of the one stripe record of an intermediate result held in
# memory (TableStore.hold_resident): no file of a table's directory
RESIDENT_STRIPE = "(resident)"

# Process-wide per-(data_dir, table) manifest write locks: sessions sharing
# a data_dir each cache manifests, so every manifest read-modify-write must
# serialize AND re-read disk state first, or one session's save can clobber
# another's committed records (the lost-update the reference prevents with
# catalog-table row locking).
_manifest_write_locks: dict[tuple[str, str], threading.Lock] = {}
_mwl_mu = threading.Lock()


class TableStore:
    """Host-side storage manager for all tables under one data directory."""

    def __init__(self, data_dir: str, catalog: Catalog, settings=None):
        self.data_dir = data_dir
        self.catalog = catalog
        self.settings = settings
        self._lock = threading.RLock()
        self._manifests: dict[str, dict] = {}
        self._dicts: dict[tuple[str, str], Dictionary] = {}
        # intermediate results' rows, from hold_resident to
        # drop_table_storage: table → (values, validity, rows)
        self._resident: dict[str, tuple[dict, dict, int]] = {}
        # per-table data version: bumped on every visible mutation; the
        # executor's device-feed cache keys on it (the metadata-cache
        # invalidation analogue, metadata/metadata_cache.c:287)
        self._data_versions: dict[str, int] = {}
        # table → (mtime_ns, size) of the manifest file the cached
        # manifest was loaded from (cross-session staleness detection)
        self._manifest_stats: dict[str, tuple] = {}
        # read-your-writes overlay, set by an open transaction
        # (transaction.manager.Transaction): staged-but-uncommitted stripe
        # records and deletion masks folded into every read
        self.overlay = None
        os.makedirs(os.path.join(data_dir, "tables"), exist_ok=True)
        # change feed journal (cdc_decoder.c analogue): written at the
        # same manifest-flip points that make changes visible; internal
        # shard movement suppresses itself via change_log.suppress()
        from ..cdc import ChangeLog

        self.change_log = ChangeLog(data_dir)

    # -- paths -------------------------------------------------------------
    def table_dir(self, table: str) -> str:
        return os.path.join(self.data_dir, "tables", table)

    def shard_dir(self, table: str, shard_id: int) -> str:
        return os.path.join(self.table_dir(table), f"shard_{shard_id}")

    def replica_dir(self, table: str, shard_id: int,
                    node_id: int) -> str:
        """Physical home of a non-primary placement's stripe copies.
        A flat sibling of the shard dirs (restore points / cleanup
        treat any table_dir subdirectory as a bag of data files)."""
        return os.path.join(self.table_dir(table),
                            f"replica_{node_id}__shard_{shard_id}")

    def _manifest_path(self, table: str) -> str:
        return os.path.join(self.table_dir(table), "MANIFEST.json")

    @staticmethod
    def _stat_identity(path: str) -> tuple | None:
        """The manifest's on-disk identity (mtime_ns, size, inode) —
        THE cross-session staleness fact every comparison below keys
        on; one helper so the fields can never drift between the
        record, refresh and serving-backstop sites.  None when the
        file is missing/unreadable."""
        try:
            st = os.stat(path)
            return (st.st_mtime_ns, st.st_size, st.st_ino)
        except OSError:
            return None

    def _verify_enabled(self) -> bool:
        if self.settings is None:
            return True
        return bool(self.settings.get("storage_verify_checksums"))

    # -- manifest ----------------------------------------------------------
    def manifest(self, table: str) -> dict:
        with self._lock:
            if table not in self._manifests:
                path = self._manifest_path(table)
                # an intermediate result's manifest lives here alone: a
                # file under its name is a dead process's leftover
                if not is_intermediate(table) and os.path.exists(path):
                    # identity BEFORE content: another session's commit
                    # can rename a new manifest between our read and a
                    # stat.  Stat-first pairs the cached identity with
                    # content AT LEAST as new, so the worst case is one
                    # redundant refresh_if_stale reload.  The old
                    # read-then-stat order could pair a NEW identity
                    # with OLD content — every later staleness check
                    # then compared new == new and the reader served
                    # old rows forever (and poisoned the shared serving
                    # result cache with a fresh-token stale fill; found
                    # by the serving invalidation hammer once PR 13's
                    # mesh seams shifted thread timing).
                    ident = self._stat_identity(path)
                    # CRC-verified load: a flipped bit in the manifest
                    # must fail loudly, never route reads at garbage
                    self._manifests[table] = dio.read_json_checked(path)
                    if ident is not None:
                        self._manifest_stats[table] = ident
                    else:
                        self._manifest_stats.pop(table, None)
                else:
                    self._manifests[table] = {"next_stripe": 1, "shards": {}}
                    self._manifest_stats.pop(table, None)
            return self._manifests[table]

    def _save_manifest(self, table: str) -> None:
        from ..utils.faultinjection import fault_point

        # named seam: a kill here dies BEFORE the visibility flip — the
        # stripe/mask files exist but stay invisible (clean retry)
        fault_point("storage.manifest_flip")
        os.makedirs(self.table_dir(table), exist_ok=True)
        path = self._manifest_path(table)
        try:
            prev_mtime = os.stat(path).st_mtime_ns
        except OSError:
            prev_mtime = None
        dio.atomic_write_json_checked(path, self._manifests[table])
        if prev_mtime is not None:
            # identity must change on EVERY commit: two same-size
            # commits inside one filesystem timestamp tick (easy once
            # warm DML lands back-to-back) plus inode reuse would give
            # the new manifest the exact (mtime_ns, size, inode) a
            # reader session already cached — refresh_if_stale (and the
            # serving cache's manifest-identity backstop) would serve
            # the OLD rows.  Forcing mtime_ns strictly monotone along
            # the commit chain makes the stat identity injective; we
            # hold the table write lock, so the bump cannot race
            # another writer.
            try:
                if os.stat(path).st_mtime_ns <= prev_mtime:
                    os.utime(path, ns=(prev_mtime + 1, prev_mtime + 1))
            except OSError:
                pass
        with self._lock:
            self._record_manifest_stat(table)

    def _record_manifest_stat(self, table: str) -> None:
        """Remember the on-disk manifest's identity (caller holds lock
        AND the table write lock — only the writer may stat AFTER its
        own commit; readers record a PRE-read stat via manifest()).
        Inode included: atomic_write_json renames a fresh file per
        commit, so two same-size commits inside one mtime tick still
        change identity (review: lost-visibility hole)."""
        ident = self._stat_identity(self._manifest_path(table))
        if ident is not None:
            self._manifest_stats[table] = ident
        else:
            self._manifest_stats.pop(table, None)

    def refresh_if_stale(self, table: str) -> bool:
        """Reload the cached manifest iff ANOTHER session committed a
        newer one to disk (one stat() per check).  The read-path
        counterpart of `refresh`: writers refresh under the DML lock,
        readers call this before building feeds so cross-session
        read-committed visibility holds without invalidating warm feed
        caches on every query.  Returns True when a reload happened."""
        with self._lock:
            if table not in self._manifests or is_intermediate(table):
                # next read loads from disk anyway; an intermediate
                # result has no disk state to be stale against
                return False
            disk = self._stat_identity(self._manifest_path(table))
            if self._manifest_stats.get(table) == disk:
                return False
            self._manifests.pop(table, None)
            self.bump_data_version(table)
            return True

    def _write_lock(self, table: str) -> threading.Lock:
        key = (os.path.abspath(self.data_dir), table)
        with _mwl_mu:
            if key not in _manifest_write_locks:
                _manifest_write_locks[key] = threading.Lock()
            return _manifest_write_locks[key]

    def _reload_manifest_locked(self, table: str) -> dict:
        """Drop the cached manifest and re-read disk (caller holds
        self._lock AND the table write lock)."""
        if not is_intermediate(table):
            self._manifests.pop(table, None)
        return self.manifest(table)

    def data_version(self, table: str) -> int:
        with self._lock:
            return self._data_versions.get(table, 0)

    def manifest_stat_sig(self, table: str) -> tuple | None:
        """The on-disk manifest's identity (mtime_ns, size, inode), or
        None when the table has no manifest yet.  Cross-session
        comparable (unlike the per-store data_version counter): the
        serving result cache records it at fill time and re-checks on
        every hit — the backstop for mutations the CDC journal missed
        (a crash in the post-visibility cdc.append window, out-of-band
        restore surgery)."""
        return self._stat_identity(self._manifest_path(table))

    def refresh(self, table: str) -> None:
        """Drop the cached manifest so the next read reloads from disk —
        used after lock acquisition so a session sharing this data_dir
        sees the lock winner's committed state."""
        with self._lock:
            self._manifests.pop(table, None)
            self.bump_data_version(table)

    def bump_data_version(self, table: str) -> None:
        with self._lock:
            self._data_versions[table] = self._data_versions.get(table, 0) + 1

    def drop_table_storage(self, table: str) -> None:
        import shutil

        with self._lock:
            self._manifests.pop(table, None)
            self._dicts = {k: v for k, v in self._dicts.items() if k[0] != table}
            self.bump_data_version(table)
            if is_intermediate(table):
                self._resident.pop(table, None)
            elif os.path.exists(self.table_dir(table)):
                shutil.rmtree(self.table_dir(table))

    # -- dictionaries ------------------------------------------------------
    def storage_column_name(self, table: str, column: str) -> str:
        """Current column name → on-disk stripe/dictionary name (identity
        unless ALTER TABLE RENAME COLUMN recorded a mapping)."""
        return self.manifest(table).get("renames", {}).get(column, column)

    def rename_column(self, table: str, old: str, new: str) -> None:
        with self._write_lock(table), self._lock:
            man = self.manifest(table)
            renames = man.setdefault("renames", {})
            storage = renames.pop(old, old)
            renames[new] = storage
            self._save_manifest(table)

    def retire_column(self, table: str, column: str) -> None:
        """DROP COLUMN bookkeeping: remember the on-disk name as dead so
        a later ADD COLUMN with the same name can never resurrect the
        dropped column's stripe data."""
        with self._write_lock(table), self._lock:
            man = self.manifest(table)
            storage = man.setdefault("renames", {}).pop(column, column)
            retired = man.setdefault("retired", [])
            if storage not in retired:
                retired.append(storage)
            self._save_manifest(table)

    def register_column(self, table: str, column: str) -> None:
        """ADD COLUMN bookkeeping: if the name collides with a retired
        storage name or another column's storage target (rename left the
        old on-disk name in place), map the new column to a fresh
        storage name instead."""
        with self._write_lock(table), self._lock:
            man = self.manifest(table)
            renames = man.setdefault("renames", {})
            used = set(man.get("retired", [])) | set(renames.values())
            if column in used:
                i = 2
                while f"{column}__{i}" in used or \
                        f"{column}__{i}" in renames.values():
                    i += 1
                renames[column] = f"{column}__{i}"
                self._save_manifest(table)

    def dictionary(self, table: str, column: str) -> Dictionary:
        column = self.storage_column_name(table, column)
        with self._lock:
            key = (table, column)
            if key not in self._dicts:
                path = os.path.join(self.table_dir(table), f"dict_{column}.json")
                self._dicts[key] = (
                    Dictionary.load(path)
                    if not is_intermediate(table) and os.path.exists(path)
                    else Dictionary())
            return self._dicts[key]

    def save_dictionaries(self, table: str) -> None:
        if is_intermediate(table):
            return  # its dictionaries live and die in self._dicts
        with self._lock:
            os.makedirs(self.table_dir(table), exist_ok=True)
            for (t, col), d in self._dicts.items():
                if t == table:
                    d.save(os.path.join(self.table_dir(table), f"dict_{col}.json"))

    # -- write path --------------------------------------------------------
    def append_stripe(self, table: str, shard_id: int,
                      columns: dict[str, np.ndarray],
                      validity: dict[str, np.ndarray] | None = None,
                      codec: str = "zstd", level: int = 3,
                      chunk_rows: int = 10_000,
                      commit: bool = True) -> dict:
        """Write one stripe for a shard.  With commit=False the stripe file
        exists on disk but is invisible until `commit_pending` flips the
        manifest — the write/visibility split the transaction layer uses.
        Returns the pending-stripe record."""
        from ..utils.faultinjection import fault_point

        fault_point("store.append_stripe")
        meta = self.catalog.table(table)
        # new stripes write under STORAGE names so renamed columns stay
        # consistent with pre-rename stripes
        ren = self.manifest(table).get("renames", {})
        if ren:
            columns = {ren.get(c, c): a for c, a in columns.items()}
            if validity is not None:
                validity = {ren.get(c, c): a
                            for c, a in validity.items()}
        schema_cols = [(ren.get(c.name, c.name), c.dtype)
                       for c in meta.schema.columns]
        with self._write_lock(table), self._lock:
            # Persist the bumped counter BEFORE writing the file so a crash +
            # reopen can never re-allocate (and overwrite) this stripe
            # number; reload first so two sessions can't allocate the same.
            man = self._reload_manifest_locked(table)
            stripe_no = man["next_stripe"]
            man["next_stripe"] = stripe_no + 1
            self._save_manifest(table)
            os.makedirs(self.shard_dir(table, shard_id), exist_ok=True)
            fname = f"stripe_{stripe_no:06d}.ctps"
            path = os.path.join(self.shard_dir(table, shard_id), fname)
        # stripe write (compression + fsync) happens outside the store lock
        footer = write_stripe(path, schema_cols, columns, validity,
                              codec=codec, level=level, chunk_rows=chunk_rows)
        record = {"file": fname, "rows": footer["row_count"],
                  "bytes": os.path.getsize(path),
                  "stats": _column_stats(columns, validity)}
        if commit:
            self.commit_pending(table, [(shard_id, record)])
        return record

    def hold_resident(self, table: str, shard_id: int,
                      columns: dict[str, np.ndarray],
                      validity: dict[str, np.ndarray]) -> dict:
        """Keep an intermediate result's rows in memory as its shard's one
        stripe, until `drop_table_storage`: what `append_stripe` is to a
        user table, without the file, the manifest file, the dictionary
        files and the change feed.  The record carries a stripe record's
        rows, bytes and statistics, so row counts, planning statistics
        and `read_shard` answer as they would over the stripe.  Returns
        the record."""
        if not is_intermediate(table):
            raise StorageError(
                f"table {table}: only an intermediate result is held "
                "in memory")
        n_rows = len(next(iter(columns.values()))) if columns else 0
        record = {"file": RESIDENT_STRIPE, "rows": n_rows,
                  "bytes": sum(a.nbytes for a in columns.values())
                  + sum(v.nbytes for v in validity.values()),
                  "stats": _column_stats(columns, validity)}
        with self._lock:
            self._resident[table] = (columns, validity, n_rows)
            self._manifests[table] = {"next_stripe": 2,
                                      "shards": {str(shard_id): [record]}}
            self.bump_data_version(table)
        return record

    # -- placement copies (replication-factor ≥ 2 physical replicas) -------
    def _primary_owner(self, shard_id: int):
        """Placement whose physical copy is the plain shard dir: the
        lowest placement_id ever allocated for the shard (stable across
        quarantine/moves — attribution, not routing)."""
        ps = self.catalog.all_shard_placements(shard_id)
        return ps[0] if ps else None

    def _mirror_records(self, table: str,
                        pending: list[tuple[int, dict]]) -> None:
        """Copy freshly committed stripe files to every other active
        placement's replica dir — the physical half of
        shard_replication_factor (the reference ships the same rows to
        each placement over COPY; immutable stripes just duplicate the
        file).  Runs BEFORE the manifest flip: a committed stripe always
        has its replica copies on disk.

        Hash-distributed tables only: reference/local tables place on
        EVERY node by construction (8 mirror copies per intermediate-
        result stripe on an 8-device mesh would tax every recursive-
        planning materialization), so they keep single-copy
        shared-storage semantics — corruption there surfaces as a clean
        CorruptStripe, like factor-1 hash tables."""
        from ..catalog import DistributionMethod

        meta = self.catalog.tables.get(table)
        if meta is None or meta.method != DistributionMethod.HASH:
            return
        for shard_id, rec in pending:
            ps = self.catalog.shard_placements(shard_id)
            if len(ps) < 2:
                continue
            owner = self._primary_owner(shard_id)
            src = os.path.join(self.shard_dir(table, shard_id),
                               rec["file"])
            if not os.path.exists(src):
                continue  # recovery replay after a post-flip crash
            for p in ps:
                if owner is not None and \
                        p.placement_id == owner.placement_id:
                    continue
                d = self.replica_dir(table, shard_id, p.node_id)
                dst = os.path.join(d, rec["file"])
                if os.path.exists(dst):
                    continue  # idempotent replay
                os.makedirs(d, exist_ok=True)
                dio.copy_file_durable(src, dst)

    def _copy_paths(self, table: str, shard_id: int,
                    fname: str) -> list[str]:
        """Every on-disk copy of one stripe file, primary first."""
        out = [os.path.join(self.shard_dir(table, shard_id), fname)]
        tdir = self.table_dir(table)
        suffix = f"__shard_{shard_id}"
        try:
            entries = sorted(os.listdir(tdir))
        except OSError:
            return out
        for e in entries:
            if e.startswith("replica_") and e.endswith(suffix):
                p = os.path.join(tdir, e, fname)
                if os.path.exists(p):
                    out.append(p)
        return out

    def stripe_read_path(self, table: str, shard_id: int,
                         fname: str) -> str:
        """Physical path the CURRENT routing placement reads: primary
        copy for the owner placement, the replica-dir copy otherwise
        (falling back to primary when no mirror was ever written —
        shared-storage semantics).  Suspect placements re-route here:
        marking the primary's placement suspect makes the next read
        resolve to a surviving replica copy."""
        primary = os.path.join(self.shard_dir(table, shard_id), fname)
        try:
            p = self.catalog.active_placement(shard_id, probe=False)
        except Exception:
            return primary
        owner = self._primary_owner(shard_id)
        if owner is None or p.placement_id == owner.placement_id:
            return primary
        alt = os.path.join(self.replica_dir(table, shard_id, p.node_id),
                           fname)
        return alt if os.path.exists(alt) else primary

    def _placement_of_copy(self, shard_id: int, path: str):
        """The placement whose physical copy `path` is (suspect-marking
        attribution for corrupt copies)."""
        base = os.path.basename(os.path.dirname(path))
        if base.startswith("replica_"):
            node_id = int(base[len("replica_"):].split("__", 1)[0])
            for p in self.catalog.all_shard_placements(shard_id):
                if p.node_id == node_id:
                    return p
            return None
        return self._primary_owner(shard_id)

    def _maybe_bitflip(self, path: str) -> None:
        """`storage.stripe_bitflip` seam: an armed injection corrupts
        one byte of the file about to be read and lets the read proceed
        — silent bit rot the CRC path must catch (detect + repair or
        clean CorruptStripe, never wrong rows)."""
        from ..utils.faultinjection import InjectedFault, fault_point

        try:
            fault_point("storage.stripe_bitflip")
        except InjectedFault:
            try:
                integrity.flip_one_bit(path)
            except (OSError, CorruptStripe):
                pass  # file too small/unwritable: nothing to corrupt

    def verified_read(self, table: str, shard_id: int, fname: str,
                      reader_fn):
        """Run `reader_fn(path)` against the routing placement's copy
        with end-to-end corruption handling: a CorruptStripe from one
        copy marks its placement suspect (the PR-3 placement-failure
        re-route), the read transparently answers from another copy
        that fully verifies, and the damaged copy is healed in place
        from the verified bytes (best-effort — a failed heal leaves the
        placement suspect for the scrubber).  Only when EVERY copy is
        damaged does CorruptStripe propagate — a clean error, never
        wrong rows.  In-place healing matters beyond latency: without
        it a corrupt copy lingers until the next scrub, and a second
        bit flip on the surviving copy in that window is permanent data
        loss (replication factor 2 tolerates ONE dead copy at a time).
        """
        path = self.stripe_read_path(table, shard_id, fname)
        self._maybe_bitflip(path)
        verify = self._verify_enabled()
        try:
            result = reader_fn(path)
            if verify:
                integrity.note("stripes_verified")
            return result
        except CorruptStripe as first:
            integrity.note("corruption_detected")
            bad = self._placement_of_copy(shard_id, path)
            if bad is not None:
                self.catalog.mark_placement_suspect(bad.placement_id)
            for alt in self._copy_paths(table, shard_id, fname):
                if alt == path:
                    continue
                try:
                    integrity.verify_stripe_file(alt)
                    result = reader_fn(alt)
                except CorruptStripe:
                    integrity.note("corruption_detected")
                    p = self._placement_of_copy(shard_id, alt)
                    if p is not None:
                        self.catalog.mark_placement_suspect(
                            p.placement_id)
                    continue
                integrity.note("read_repairs")
                self._heal_copy(path, alt, bad)
                return result
            raise first

    def _heal_copy(self, dst: str, src: str, bad_placement) -> None:
        """Rewrite a corrupt copy from verified bytes at read time; on
        success the placement is trusted again.  Failures leave it
        suspect — the scrubber's quarantine + re-replication pass is
        the heavier fallback for corruption found at rest."""
        try:
            dio.copy_file_durable(src, dst)
            integrity.verify_stripe_file(dst)
        except (OSError, CorruptStripe):
            return
        if bad_placement is not None:
            self.catalog.clear_placement_suspect(
                bad_placement.placement_id)

    def commit_pending(self, table: str,
                       pending: list[tuple[int, dict]]) -> None:
        """Atomically make a batch of stripes visible: one manifest write.

        Dictionaries are persisted first so a committed STRING stripe can
        never reference codes missing from the on-disk dictionary (the
        dictionary is append-only, so over-persisting is harmless)."""
        # replica copies touch only immutable, uniquely-named stripe
        # files plus the catalog — made before the locks so mirroring a
        # large stripe cannot stall every other table's readers, yet
        # still BEFORE the manifest flip: a committed stripe always has
        # its replica copies on disk
        self._mirror_records(table, pending)
        with self._write_lock(table), self._lock:
            self.save_dictionaries(table)
            man = self._reload_manifest_locked(table)
            for shard_id, record in pending:
                man["shards"].setdefault(str(shard_id), []).append(record)
                stripe_no = int(record["file"].split("_")[1].split(".")[0])
                man["next_stripe"] = max(man["next_stripe"], stripe_no + 1)
            self._save_manifest(table)
            self.bump_data_version(table)
            # change feed AFTER the durable flip: a crash in between
            # loses the event (at-most-once) but never emits a phantom
            self.change_log.emit([
                self.change_log.insert_event(table, sid, rec)
                for sid, rec in pending])

    # -- DML (deletion bitmaps) -------------------------------------------
    # The reference's columnar engine is append-only (columnar/README.md:
    # 40-62: no UPDATE/DELETE); distributed DML there routes to row-store
    # shards (multi_router_planner.c CreateModifyPlan).  Here every table is
    # columnar, so DML uses per-stripe deletion bitmaps: DELETE marks rows,
    # UPDATE = delete + append, both made visible by ONE manifest write.

    def _delete_mask_path(self, table: str, shard_id: int, fname: str) -> str:
        return os.path.join(self.shard_dir(table, shard_id), fname)

    def load_delete_mask(self, table: str, shard_id: int,
                         record: dict) -> np.ndarray | None:
        fname = record.get("deletes")
        if not fname:
            return None
        return integrity.read_mask(
            self._delete_mask_path(table, shard_id, fname))

    # -- transaction overlay ----------------------------------------------
    def _overlay_records(self, table: str, shard_id: int) -> list[dict]:
        if self.overlay is None:
            return []
        return self.overlay.records.get((table, shard_id), [])

    def _overlay_mask(self, table: str, shard_id: int,
                      fname: str) -> np.ndarray | None:
        if self.overlay is None:
            return None
        return self.overlay.deletes.get((table, shard_id, fname))

    def effective_delete_mask(self, table: str, shard_id: int,
                              record: dict) -> np.ndarray | None:
        """On-disk deletion bitmap OR the open transaction's staged one."""
        disk = self.load_delete_mask(table, shard_id, record)
        staged = self._overlay_mask(table, shard_id, record["file"])
        if staged is None:
            return disk
        return staged if disk is None else (disk | staged)

    def apply_dml(self, table: str,
                  deletes: dict[int, dict[str, np.ndarray]],
                  pending: list[tuple[int, dict]] = ()) -> None:
        """Atomically apply a DML effect: per-stripe delete masks (True =
        row now dead) plus newly written (commit=False) stripes, all made
        visible by a single manifest write.  Delete-mask files are
        versioned, never overwritten in place, so a crash before the
        manifest flip leaves only orphan files."""
        from ..utils.faultinjection import fault_point

        fault_point("store.apply_dml")
        events: list[dict] = []
        # before the locks, like commit_pending: immutable-file copies
        # must not serialize against the store-wide lock
        self._mirror_records(table, list(pending))
        with self._write_lock(table), self._lock:
            self.save_dictionaries(table)
            man = self._reload_manifest_locked(table)
            stale: list[str] = []
            # pending stripes first so a staged delete may target a stripe
            # committed by this very call (transactional UPDATE-after-INSERT)
            for shard_id, record in pending:
                recs = man["shards"].setdefault(str(shard_id), [])
                if any(r["file"] == record["file"] for r in recs):
                    continue  # crash-recovery replay: already applied
                recs.append(record)
                stripe_no = int(record["file"].split("_")[1].split(".")[0])
                man["next_stripe"] = max(man["next_stripe"], stripe_no + 1)
                events.append(self.change_log.insert_event(
                    table, shard_id, record))
            for shard_id, per_stripe in deletes.items():
                records = man["shards"].get(str(shard_id), [])
                by_file = {r["file"]: r for r in records}
                for fname, mask in per_stripe.items():
                    if not mask.any():
                        continue
                    rec = by_file[fname]
                    if len(mask) != rec["rows"]:
                        raise ValueError(
                            f"{table}/{fname}: delete mask length "
                            f"{len(mask)} != stripe rows {rec['rows']}")
                    old = self.load_delete_mask(table, shard_id, rec)
                    newly = mask if old is None else (mask & ~old)
                    if newly.any():
                        events.append(self.change_log.delete_event(
                            table, shard_id, fname, newly))
                    combined = mask if old is None else (old | mask)
                    version = rec.get("del_version", 0) + 1
                    delname = f"{fname}.del{version:04d}.npy"
                    path = self._delete_mask_path(table, shard_id, delname)
                    integrity.write_mask(path, combined)
                    if rec.get("deletes"):
                        stale.append(self._delete_mask_path(
                            table, shard_id, rec["deletes"]))
                    rec["deletes"] = delname
                    rec["del_version"] = version
                    rec["live_rows"] = int((~combined).sum())
            self._save_manifest(table)
            self.bump_data_version(table)
            self.change_log.emit(events)
            for path in stale:
                try:
                    os.unlink(path)
                except OSError:
                    pass

    def remove_shard_records(self, table: str, shard_id: int) -> None:
        """Drop a shard's manifest entries (split/cleanup: the shard's
        rows now live in successor shards)."""
        with self._write_lock(table), self._lock:
            man = self._reload_manifest_locked(table)
            if str(shard_id) in man["shards"]:
                del man["shards"][str(shard_id)]
                self._save_manifest(table)
                self.bump_data_version(table)

    def shard_stripe_records(self, table: str, shard_id: int) -> list[dict]:
        man = self.manifest(table)
        return ([dict(r) for r in man["shards"].get(str(shard_id), [])]
                + [dict(r) for r in self._overlay_records(table, shard_id)])

    def read_stripe_raw(self, table: str, shard_id: int, fname: str,
                        columns: list[str] | None = None,
                        record: dict | None = None,
                        ) -> tuple[dict, dict, int, np.ndarray | None]:
        """Read one stripe WITHOUT applying its deletion bitmap; returns
        (values, validity, rows, delete_mask|None) so DML sees physical
        row positions.  Pass the manifest `record` (from
        shard_stripe_records) to skip the manifest rescan."""
        if record is None:
            record = next(r for r in self.shard_stripe_records(table,
                                                               shard_id)
                          if r["file"] == fname)
        verify = self._verify_enabled()
        vals, mask, n = self.verified_read(
            table, shard_id, fname,
            lambda p: StripeReader(p, verify=verify).read(columns))
        return vals, mask, n, self.effective_delete_mask(table, shard_id,
                                                         record)

    def discard_pending(self, table: str,
                        pending: list[tuple[int, dict]]) -> None:
        with self._lock:
            for shard_id, record in pending:
                path = os.path.join(self.shard_dir(table, shard_id),
                                    record["file"])
                if os.path.exists(path):
                    os.unlink(path)

    # -- read path ---------------------------------------------------------
    def shard_stripe_paths(self, table: str, shard_id: int) -> list[str]:
        man = self.manifest(table)
        records = man["shards"].get(str(shard_id), [])
        return [os.path.join(self.shard_dir(table, shard_id), r["file"])
                for r in records]

    def shard_row_count(self, table: str, shard_id: int) -> int:
        man = self.manifest(table)
        total = 0
        for r in man["shards"].get(str(shard_id), []):
            total += r.get("live_rows", r["rows"])
            staged = self._overlay_mask(table, shard_id, r["file"])
            if staged is not None:
                disk = self.load_delete_mask(table, shard_id, r)
                newly = staged if disk is None else (staged & ~disk)
                total -= int(newly.sum())
        for r in self._overlay_records(table, shard_id):
            staged = self._overlay_mask(table, shard_id, r["file"])
            total += (r["rows"] if staged is None
                      else int((~staged).sum()))
        return total

    def shard_size_bytes(self, table: str, shard_id: int) -> int:
        man = self.manifest(table)
        return sum(r["bytes"] for r in man["shards"].get(str(shard_id), []))

    def column_has_nulls(self, table: str, column: str) -> bool | None:
        """Whether any committed/staged stripe holds a NULL in `column`
        (manifest null-count rollup; None = unknown — pre-null-count
        manifests or no stats).  Conservative under deletes: a deleted
        NULL still counts."""
        column = self.storage_column_name(table, column)
        man = self.manifest(table)
        rec_lists = list(man["shards"].values())
        if self.overlay is not None:
            rec_lists.extend(recs for (t, _sid), recs
                             in self.overlay.records.items() if t == table)
        for recs in rec_lists:
            for r in recs:
                s = (r.get("stats") or {}).get(column)
                if s is None or len(s) < 3:
                    return None
                if s[2] > 0:
                    return True
        return False

    def column_range(self, table: str,
                     column: str) -> tuple[float, float] | None:
        """Table-wide (min, max) for a numeric/date column from manifest
        stripe stats (the per-stripe skip-node rollup the planner's
        cardinality estimation reads; ref: columnar chunk skip nodes,
        columnar/columnar_metadata.c).  None when no stripe carries stats
        (pre-stats files) or the column is all-NULL."""
        column = self.storage_column_name(table, column)
        man = self.manifest(table)
        rec_lists = list(man["shards"].values())
        if self.overlay is not None:
            # staged-but-uncommitted stripes are visible to this session's
            # scans, so their value ranges must widen the extent too —
            # otherwise dense-grid aggregation clips new keys into the
            # boundary group
            rec_lists.extend(recs for (t, _sid), recs
                             in self.overlay.records.items() if t == table)
        lo = hi = None
        for recs in rec_lists:
            for r in recs:
                s = (r.get("stats") or {}).get(column)
                if s is None:
                    return None
                if s[0] is None:
                    continue
                lo = s[0] if lo is None else min(lo, s[0])
                hi = s[1] if hi is None else max(hi, s[1])
        if lo is None:
            return None
        return lo, hi

    def table_row_count(self, table: str) -> int:
        man = self.manifest(table)
        if self.overlay is None:
            return sum(r.get("live_rows", r["rows"])
                       for recs in man["shards"].values() for r in recs)
        return sum(self.shard_row_count(table, int(sid))
                   for sid in set(man["shards"])
                   | {str(s) for t, s in self.overlay.records if t == table})

    def iter_shard_stripes(self, table: str, shard_id: int,
                           columns: list[str] | None = None,
                           chunk_filter=None):
        """Yield (values, validity, live_rows) per visible stripe of one
        shard — the streaming read path (batched stripe→HBM feeds consume
        this one stripe at a time instead of materializing the shard)."""
        meta = self.catalog.table(table)
        columns = columns or meta.schema.names
        held = self._resident.get(table)
        if held is not None:
            yield self._resident_columns(held, columns)
            return
        # translate renamed columns to their on-disk names for the
        # stripe readers, but key all outputs by the REQUESTED names
        storage_of = {c: self.storage_column_name(table, c)
                      for c in columns}
        requested_of = {s: c for c, s in storage_of.items()}
        man = self.manifest(table)
        records = (list(man["shards"].get(str(shard_id), []))
                   + self._overlay_records(table, shard_id))
        verify = self._verify_enabled()
        for rec in records:
            dmask = self.effective_delete_mask(table, shard_id, rec)

            def read_one(path):
                # a stripe with deletions reads whole (positions must
                # align with the bitmap), trading its chunk skipping
                # for correctness
                reader = StripeReader(path, verify=verify)
                # columns added by ALTER TABLE after this stripe was
                # written read as all-NULL (schema evolution is
                # manifest-level; old stripes are immutable)
                present = [storage_of[c] for c in columns
                           if storage_of[c] in reader._by_name]
                absent = [c for c in columns
                          if storage_of[c] not in reader._by_name]
                if present or not absent:
                    rv, rm, rn = reader.read(
                        present,
                        None if dmask is not None else chunk_filter)
                    rv = {requested_of[s]: a for s, a in rv.items()}
                    rm = {requested_of[s]: a for s, a in rm.items()}
                else:  # projection of only post-ALTER columns
                    rv, rm, rn = {}, {}, reader.row_count
                return rv, rm, rn, absent

            v, m, n, missing = self.verified_read(table, shard_id,
                                                  rec["file"], read_one)
            for c in missing:
                dt = meta.schema.column(c).dtype.numpy_dtype
                v[c] = np.zeros(n, dtype=dt)
                m[c] = np.zeros(n, dtype=np.bool_)
            if dmask is not None:
                keep = ~dmask
                v = {c: a[keep] for c, a in v.items()}
                m = {c: a[keep] for c, a in m.items()}
                n = int(keep.sum())
            yield v, m, n

    @staticmethod
    def _resident_columns(held, columns: list[str]):
        """(values, validity, rows) of a held intermediate result,
        projected: the arrays themselves, which no reader writes to."""
        values, validity, n_rows = held
        return ({c: values[c] for c in columns},
                {c: validity[c] for c in columns}, n_rows)

    def read_shard(self, table: str, shard_id: int,
                   columns: list[str] | None = None, chunk_filter=None,
                   ) -> tuple[dict[str, np.ndarray], dict[str, np.ndarray], int]:
        """Concatenate all visible stripes of one shard (projected).

        A failed read carries (table, shard_id) on the exception so the
        statement retry loop can mark the placement suspect and fail the
        next attempt's routing over to a surviving replica — the
        adaptive-executor read-failover seam."""
        from ..utils.faultinjection import fault_point

        try:
            fault_point("store.read_shard")
            return self._read_shard(table, shard_id, columns, chunk_filter)
        except Exception as e:
            if isinstance(e, (StorageError, OSError)) or \
                    getattr(e, "injected_fault", False):
                e.table = table
                e.shard_id = shard_id
            raise

    def _read_shard(self, table: str, shard_id: int,
                    columns: list[str] | None = None, chunk_filter=None,
                    ) -> tuple[dict[str, np.ndarray], dict[str, np.ndarray], int]:
        meta = self.catalog.table(table)
        columns = columns or meta.schema.names
        held = self._resident.get(table)
        if held is not None:
            return self._resident_columns(held, columns)
        vals: dict[str, list[np.ndarray]] = {c: [] for c in columns}
        mask: dict[str, list[np.ndarray]] = {c: [] for c in columns}
        total = 0
        for v, m, n in self.iter_shard_stripes(table, shard_id, columns,
                                               chunk_filter):
            total += n
            for c in columns:
                vals[c].append(v[c])
                mask[c].append(m[c])
        out_v = {}
        out_m = {}
        for c in columns:
            dtype = meta.schema.column(c).dtype
            out_v[c] = (np.concatenate(vals[c]) if vals[c]
                        else np.empty(0, dtype=dtype.numpy_dtype))
            out_m[c] = (np.concatenate(mask[c]) if mask[c]
                        else np.empty(0, dtype=np.bool_))
        return out_v, out_m, total

    def move_shard_storage(self, table: str, shard_id: int,
                           dest_store: "TableStore") -> int:
        """Copy a shard's stripe files + manifest records into another store
        (the data plane of shard moves; ref: operations/worker_shard_copy.c).
        Returns rows moved.  Catalog placement updates are the caller's job."""
        import shutil

        paths = self.shard_stripe_paths(table, shard_id)
        man = self.manifest(table)
        records = man["shards"].get(str(shard_id), [])
        os.makedirs(dest_store.shard_dir(table, shard_id), exist_ok=True)
        for p, rec in zip(paths, records):
            shutil.copy2(p, os.path.join(
                dest_store.shard_dir(table, shard_id), rec["file"]))
            if rec.get("deletes"):
                shutil.copy2(
                    self._delete_mask_path(table, shard_id, rec["deletes"]),
                    dest_store._delete_mask_path(table, shard_id,
                                                 rec["deletes"]))
        with dest_store._lock:
            dman = dest_store.manifest(table)
            dman["shards"][str(shard_id)] = [dict(r) for r in records]
            dman["next_stripe"] = max(dman["next_stripe"], man["next_stripe"])
            dest_store._save_manifest(table)
            dest_store.bump_data_version(table)
        return sum(r.get("live_rows", r["rows"]) for r in records)
