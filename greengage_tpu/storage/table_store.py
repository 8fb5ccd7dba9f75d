"""TableStore: hash-distributed columnar tables on disk.

Reference parity: the AOCS access method + cdbhash placement + appendonly
writer (src/backend/access/aocs/aocsam.c, src/backend/cdb/cdbhash.c,
appendonlywriter.c). Each table is stored as per-segment, per-column block
files; every INSERT/COPY appends new segment files and publishes them with
one manifest commit (snapshot-isolated, see manifest.py).

Placement spec (must match ops/hashing.py on device):
  col_hash = fmix32-based hash of the 64-bit value (NULL -> 0)
  row_hash = col_hash[0], then combine(acc, col_hash[i]) for the rest
  segment  = row_hash % numsegments     (RANDOM: round-robin; REPLICATED: all)
TEXT columns hash their utf-8 bytes (via the dictionary hash LUT), never the
dictionary code, so placement is stable across dictionary growth.
"""

from __future__ import annotations

import json
import os
import shutil
import tempfile
import threading
import uuid
import datetime
import hashlib
import operator
import time as _time

import numpy as np

from greengage_tpu import types as T
from greengage_tpu.catalog import Catalog, PolicyKind, TableSchema
from greengage_tpu.runtime import trace as _trace
from greengage_tpu.runtime.faultinject import FaultError, faults
from greengage_tpu.runtime.logger import counters
from greengage_tpu.storage import native
from greengage_tpu.storage.blockcache import MISS, CacheRegistry
from greengage_tpu.storage.blockfile import (current_tally, fit_slot,
                                             fsync_dir, read_column_file,
                                             verify_column_file,
                                             write_column_file)
from greengage_tpu.storage.corruption import CorruptionError
from greengage_tpu.storage.dictionary import Dictionary
from greengage_tpu.storage.manifest import IntentConflict, Manifest


class _RawChunk:
    """One segment's raw TEXT column: per-row END offsets + validity, with
    the byte blob loaded LAZILY — scans/ANALYZE only need offsets/validity
    (small files); predicates and projections pull the blob on demand.

    ``blob_paths`` are manifest relpaths when ``reader`` is given (the
    store's checked, self-healing read), else filesystem paths."""

    def __init__(self, ends: np.ndarray, valid: np.ndarray | None,
                 blob_paths: list[str], reader=None):
        self.ends = ends
        self.valid = valid
        self._blob_paths = blob_paths
        self._reader = reader
        self._strs: list[str] | None = None

    def __len__(self):
        return len(self.ends)

    def blob(self) -> np.ndarray:
        """Concatenated utf-8 byte blob across this segment's files."""
        read = self._reader or read_column_file
        blobs = [read(p).astype(np.uint8) for p in self._blob_paths]
        return np.concatenate(blobs) if blobs else np.zeros(0, np.uint8)

    def strings(self) -> list[str]:
        if self._strs is None:
            b = self.blob().tobytes()
            starts = np.concatenate([np.zeros(1, np.int64), self.ends[:-1]]) \
                if len(self.ends) else np.zeros(0, np.int64)
            self._strs = [b[s:e].decode("utf-8")
                          for s, e in zip(starts, self.ends)]
        return self._strs


def merge_segfile_records(tx: dict, table: str, records: list) -> None:
    """Merge staged-file records [(seg, [rel files], nrows)] into a
    manifest transaction (idempotent re-apply for optimistic write retry)."""
    tmeta = tx["tables"].setdefault(table, {"segfiles": {}, "nrows": {}})
    for seg, rels, n in records:
        tmeta["segfiles"].setdefault(str(seg), []).extend(rels)
        tmeta["nrows"][str(seg)] = tmeta["nrows"].get(str(seg), 0) + n


_MIRROR_MAP_CACHE: dict = {}   # root -> (mtime, {content: dir})
# read-path self-heal resolves mirror roots from staging-pool threads
# while FTS promotion re-reads the operator map (gg check races)
_mirror_map_mu = threading.Lock()


def mirror_root(root: str, content: int) -> str:
    """Directory tree holding content ``content``'s replicated files (the
    mirror segment's data directory). Default: <root>/mirror/content<k>.
    An operator-placed ``<root>/mirror_roots.json`` overrides per content
    with ABSOLUTE paths on other disks/hosts (`gg mirrorroots --roots`) —
    the cross-host spread placement of gpaddmirrors/gpinitsystem, so a
    lost data disk cannot take a content's primary AND mirror together
    (gp_segment_configuration hostname/address separation)."""
    mp = os.path.join(root, "mirror_roots.json")
    try:
        mtime = os.stat(mp).st_mtime_ns
        with _mirror_map_mu:
            cached = _MIRROR_MAP_CACHE.get(root)
            if cached is None or cached[0] != mtime:
                with open(mp) as f:
                    cached = _MIRROR_MAP_CACHE[root] = (mtime, json.load(f))
        override = cached[1].get(str(content))
        if override:
            return os.path.join(override, f"content{content}")
    except OSError:
        with _mirror_map_mu:
            _MIRROR_MAP_CACHE.pop(root, None)
    except ValueError:
        # malformed operator edit: fall back to the default placement
        # rather than taking down every mirror-maintenance path
        with _mirror_map_mu:
            _MIRROR_MAP_CACHE.pop(root, None)
    return os.path.join(root, "mirror", f"content{content}")


# fixed-width device prefix for raw TEXT predicates (raw_prefix below):
# enough for TPC-H comment/name prefixes; longer literals fall back to the
# host path
RAW_PREFIX_BYTES = 32
RAW_PREFIX_WORDS = RAW_PREFIX_BYTES // 8
# wide byte window for GENERAL device LIKE (contains/suffix/multi-part):
# covers every TPC-H comment-class column; columns with longer rows fall
# back to the host path (decidability needs the whole string on device)
RAW_WIDE_BYTES = 128
RAW_WIDE_WORDS = RAW_WIDE_BYTES // 8


def _as_i64(arr: np.ndarray) -> np.ndarray:
    """Reinterpret a column's device dtype as int64 for hashing.

    float64 keys are canonicalized first (-0.0 -> 0.0, all NaNs -> one NaN
    bit pattern) so SQL-equal values co-locate — the hashfloat8 parity rule.
    """
    if arr.dtype == np.float64:
        arr = np.where(arr == 0.0, 0.0, arr)
        arr = np.where(np.isnan(arr), np.nan, arr)
        return arr.view(np.int64)
    return arr.astype(np.int64)


class TableStore:
    def __init__(self, root: str, catalog: Catalog):
        self.root = root
        self.catalog = catalog
        self.manifest = Manifest(root)
        # wired by the session after construction: the settings registry
        # (storage_autorepair) and the cluster logger (repair/quarantine
        # events); both optional so bare TableStore use keeps defaults
        self.settings = None
        self.log = None
        self._dicts: dict[tuple[str, str], Dictionary] = {}
        # in-memory dictionaries for string-function results over
        # dictionary columns (("@expr", sha) refs); deterministic content
        # hash keys them so concurrent binders and multihost lockstep
        # binding agree without persistence
        self._derived: dict[tuple[str, str], Dictionary] = {}
        # every read-path cache lives in ONE byte-accounted LRU registry
        # (storage/blockcache.py, the bufmgr analog): shared budget
        # (scan_cache_limit_mb), global recency eviction, manifest-version
        # invalidation, hit/miss/evict counters. Thread-safe — the
        # executor's staging pool reads through these concurrently.
        self.blockcache = CacheRegistry()
        # decoded block files: (table, rel, block_indices|None) -> ndarray
        self._block_cache = self.blockcache.cache("blocks")
        # parsed + verified footers: (table, rel) -> footer dict
        self._footer_cache = self.blockcache.cache("footers")
        self._raw_cache = self.blockcache.cache("raw")
        # (table, col, seg, version) -> RawChunk
        self._hp_cache = self.blockcache.cache("hostpred")
        # (table, seg, name, version) -> result
        # transient per-version dictionaries over raw columns (group/sort/
        # join keys on raw TEXT): ref registry + per-segment code arrays
        self._rawdict_refs: dict = {}   # (table, col, version) -> ref
        self._rawcode_cache = self.blockcache.cache("rawcode")
        # (storage, seg, col, version) -> (codes, valid)
        # deletion-bitmap keep masks (visimap analog): (table, seg, version)
        # -> bool[manifest nrows] keep mask, or None when nothing deleted
        self._delmask_cache = self.blockcache.cache("delmask")
        # packed fixed-width prefixes of raw TEXT columns for DEVICE
        # predicates: (table, col, seg, version) -> (words[n,K] int64,
        # lengths[n] int32)
        self._rawprefix_cache = self.blockcache.cache("rawprefix")
        # dictionary load/build serialization: concurrent staging threads
        # must agree on ONE code space (raw_dictionary assigns first-seen
        # codes; two racing builders would mint divergent codes)
        self._dict_lock = threading.RLock()
        # read-path self-heal under concurrency: per-(table, rel) repair
        # locks + a repair generation, so parallel readers tripping the
        # same bad file repair-or-quarantine it exactly once
        self._repair_mu = threading.Lock()
        self._repair_locks: dict = {}
        self._repair_gen: dict = {}
        self._tl = threading.local()   # per-thread last_prune

    # ---- per-content data roots (mirror failover) ----------------------
    def data_root(self, content: int) -> str:
        """Directory holding content ``content``'s segment files. Normally
        <root>/data; while a promoted mirror is acting primary for this
        content, its mirror tree — so every read AND write lands on the
        surviving copy after failover (runtime/replication.py)."""
        segs = getattr(self.catalog, "segments", None)
        if segs is not None:
            acting = segs.acting_primary(content)
            if acting is not None and acting.preferred_role.value == "m":
                return mirror_root(self.root, content)
        return os.path.join(self.root, "data")

    @staticmethod
    def rel_content(rel: str) -> int:
        """Content id encoded in a manifest relpath ('seg<k>/<file>')."""
        return int(rel.split(os.sep, 1)[0][3:])

    def seg_file_path(self, table: str, rel: str) -> str:
        """rel is 'seg<k>/<file>' as stored in the manifest."""
        return os.path.join(self.data_root(self.rel_content(rel)), table, rel)

    def storage_ok(self, content: int) -> bool:
        """Every manifest-referenced file of this content is present on its
        acting root (the FTS storage-health probe). Quarantine RENAMES bad
        files out of the tree, so an unrepairable corruption fails this
        probe and FTS failover takes over."""
        snap = self.manifest.snapshot()
        root = self.data_root(content)
        for tname, tmeta in snap.get("tables", {}).items():
            for rel in tmeta.get("segfiles", {}).get(str(content), []):
                if not os.path.exists(os.path.join(root, tname, rel)):
                    return False
        return True

    # ---- corruption handling: self-heal, quarantine, checked reads -----
    # The storage-side twin of gang recovery (docs/ROBUSTNESS.md): committed
    # block files are immutable and (with mirrors) exist twice, so a read
    # that trips a frame/footer checksum repairs from the IN-SYNC standby
    # tree and retries ONCE; a file with no healthy copy is renamed into
    # <root>/.quarantine/ with a JSON sidecar, which fails storage_ok and
    # hands the content to FTS failover. Reference: AO block checksums +
    # gprecoverseg full recovery (cdbappendonlystorageformat.c).

    def _log_event(self, severity: str, message: str) -> None:
        log = getattr(self, "log", None)
        if log is not None:
            try:
                log.log(severity, "storage", message)
            except Exception:
                pass   # observability must never fail the read

    def standby_root(self, content: int) -> str | None:
        """The tree holding the OTHER copy of this content's files (mirror
        tree while the preferred primary acts; data tree after failover).
        None when the content has no mirror pair."""
        segs = getattr(self.catalog, "segments", None)
        if segs is None:
            return None
        from greengage_tpu.catalog.segments import SegmentRole

        try:
            segs.entry(content, SegmentRole.MIRROR)
        except KeyError:
            return None
        data = os.path.join(self.root, "data")
        if os.path.normpath(self.data_root(content)) == os.path.normpath(data):
            return mirror_root(self.root, content)
        return data

    def repair_file(self, table: str, content: int, rel: str,
                    path: str) -> bool:
        """Copy ``rel`` from the in-sync standby tree over the bad acting
        copy (fsynced), then re-verify EVERY frame of the repaired file.
        False when no trustworthy standby copy exists (no mirror, stale
        sync marker, or the file is absent there); raises CorruptionError
        when the standby copy is itself corrupt."""
        from greengage_tpu.runtime.replication import copy_durable, tree_version

        standby = self.standby_root(content)
        if standby is None:
            return False
        if tree_version(standby, content) != self.manifest.snapshot().get(
                "version", 0):
            return False   # stale standby: copying could resurrect old data
        src = os.path.join(standby, table, rel)
        if not os.path.exists(src):
            return False
        faults.check("repair_copy", segment=content)
        # inject=False: repair judges the REAL bytes of both copies — an
        # armed read-time fault must not condemn healthy files
        verify_column_file(src, inject=False)   # corrupt standby raises
        os.makedirs(os.path.dirname(path), exist_ok=True)
        # tmp is repairer-unique: concurrent readers racing the same bad
        # file must not interleave writes into one tmp (each atomic
        # replace then publishes a complete, re-verified copy)
        copy_durable(src, path, tmp=f"{path}.repair.{uuid.uuid4().hex[:8]}")
        verify_column_file(path, inject=False)  # repaired copy must be clean
        self._drop_bidx(path)     # sidecar may index the bad bytes
        return True

    def _drop_bidx(self, path: str) -> None:
        if path.endswith(".ggb"):
            try:
                os.remove(path[: -len(".ggb")] + ".bidx.npz")
            except OSError:
                pass

    def quarantine_file(self, path: str, err: CorruptionError) -> str | None:
        """Rename a bad file into <root>/.quarantine/ with a JSON sidecar
        recording the cause — preserved for forensics, and its absence
        fails storage_ok so FTS can fail the segment over."""

        qdir = os.path.join(self.root, ".quarantine")
        os.makedirs(qdir, exist_ok=True)
        qname = f"{uuid.uuid4().hex[:8]}.{os.path.basename(path)}"
        qpath: str | None = os.path.join(qdir, qname)
        try:
            os.replace(path, qpath)
        except OSError:
            try:
                shutil.move(path, qpath)   # mirror roots may be other disks
            except OSError:
                qpath = None   # cannot move (already gone?): sidecar only
        self._drop_bidx(path)
        sidecar = dict(err.to_dict(),
                       quarantined_from=path, quarantined_to=qpath,
                       time=datetime.datetime.now(datetime.timezone.utc)
                       .isoformat(timespec="seconds"))
        try:
            with open(os.path.join(qdir, qname + ".json"), "w") as f:
                json.dump(sidecar, f, indent=1)
        except OSError:
            pass
        counters.inc("storage_quarantine")
        self._log_event("ERROR",
                        f"quarantined {path} -> {qpath}: {err.cause} "
                        f"({err.message})")
        return qpath

    def handle_corruption(self, table: str, content: int, rel: str,
                          path: str, err: CorruptionError) -> None:
        """Decide repair vs quarantine for one located corruption. Returns
        after a verified repair; otherwise quarantines the acting file
        (and a corrupt standby copy, so nothing ever trusts it) and
        re-raises the typed error."""
        settings = getattr(self, "settings", None)
        autorepair = settings is None or getattr(settings,
                                                 "storage_autorepair", True)
        if autorepair:
            try:
                if self.repair_file(table, content, rel, path):
                    counters.inc("storage_repair")
                    self._mark_rel_changed(table, rel)
                    self._log_event(
                        "WARNING",
                        f"repaired {table}/{rel} (content {content}) from "
                        f"standby tree after {err.cause}")
                    return
            except FaultError:
                pass   # injected repair_copy failure: fall through
            except CorruptionError as e2:   # before OSError: its subclass
                # both copies corrupt: quarantine the standby copy too so
                # rebuild/promotion never trusts it (unless the failure
                # was the post-repair re-verify of the ACTING file, which
                # the fall-through below already quarantines once)
                spath = getattr(e2, "path", None)
                if spath and spath != path and os.path.exists(spath):
                    self.quarantine_file(
                        spath, e2.locate(table=table, content=content,
                                         relpath=rel))
            except OSError:
                # EIO/ENOSPC mid-copy or mid-verify: a failed repair, not
                # a new error class — the detected-bad file must still
                # quarantine (and fail storage_ok) below
                pass
        if err.cause != "missing":
            self.quarantine_file(path, err)
            self._mark_rel_changed(table, rel)
        raise err

    # -- repair concurrency helpers --------------------------------------
    def _repair_lock_for(self, table: str, rel: str) -> threading.Lock:
        with self._repair_mu:
            lk = self._repair_locks.get((table, rel))
            if lk is None:
                lk = self._repair_locks[(table, rel)] = threading.Lock()
            return lk

    def _mark_rel_changed(self, table: str, rel: str) -> None:
        """A repair or quarantine replaced/removed this rel's bytes: bump
        the repair generation (waiting readers re-judge the NEW bytes
        instead of acting on a stale failure) and drop cached blocks."""
        with self._repair_mu:
            self._repair_gen[(table, rel)] = \
                self._repair_gen.get((table, rel), 0) + 1
        self._block_cache.drop(lambda k: k[0] == table and k[1] == rel)
        self._footer_cache.pop((table, rel), None)

    def _read_checked(self, table: str, rel: str, reader):
        """Run ``reader(path)`` with read-path self-heal: corruption (or a
        vanished manifest-referenced file) repairs from the standby tree
        and retries ONCE; unrepairable damage quarantines and raises.

        Concurrency contract (the staging thread pool reads through this):
        parallel readers tripping the same bad file serialize on a per-rel
        lock and repair-or-quarantine EXACTLY once — a reader that waited
        out another thread's repair re-reads the healed bytes instead of
        double-repairing, and one that waited out a quarantine surfaces
        'missing' instead of double-quarantining."""
        content = self.rel_content(rel)
        path = self.seg_file_path(table, rel)
        with self._repair_mu:
            gen0 = self._repair_gen.get((table, rel), 0)
        try:
            return reader(path, content)
        except FileNotFoundError:
            err = CorruptionError(
                "missing", "manifest-referenced file is missing", path=path)
        except CorruptionError as e:
            err = e
        with self._repair_lock_for(table, rel):
            with self._repair_mu:
                changed = self._repair_gen.get((table, rel), 0) != gen0
            if changed:
                # another thread already repaired (or quarantined) this
                # file while we waited: judge the CURRENT bytes
                try:
                    return reader(path, content)
                except FileNotFoundError:
                    err = CorruptionError(
                        "missing", "manifest-referenced file is missing",
                        path=path)
                except CorruptionError as e:
                    err = e
            err.locate(table=table, content=content, relpath=rel)
            self.handle_corruption(table, content, rel, path, err)
            return reader(path, content)

    def read_file(self, table: str, rel: str,
                  block_indices: list[int] | None = None,
                  out: np.ndarray | None = None) -> np.ndarray:
        """Checked read of one manifest-referenced block file, served from
        the byte-accounted block cache when resident (committed block
        files are immutable; repair/quarantine invalidates explicitly).
        Cache misses count scan_files_read / scan_bytes_decoded.

        ``out``: optional preallocated destination (a staging-buffer slot)
        that the rows land in on the CALLING thread, whichever way they
        come: a miss decodes the frames straight into it (the cached
        value is then a view of it), a hit copies the cached array into
        it (the cache entry stays what it was). Either way the return
        value is a view of ``out`` and the caller skips its own copy; a
        destination of another dtype, or too short, is passed over."""
        key = (table, rel,
               None if block_indices is None else tuple(block_indices))
        hit = self._block_cache.get(key, MISS)
        if hit is not MISS:
            tally = current_tally()
            if tally is not None:
                tally.cache_hits += 1
            slot = fit_slot(out, hit.dtype, len(hit))
            if slot is None:
                return hit
            t0 = _time.perf_counter_ns()
            np.copyto(slot, hit)
            if tally is not None:
                tally.slot_copies += 1
                tally.copy_ns += _time.perf_counter_ns() - t0
            return slot
        arr = self._read_checked(
            table, rel,
            lambda p, c: read_column_file(p, block_indices, segment=c,
                                          out=out))
        counters.inc("scan_files_read")
        counters.inc("scan_bytes_decoded", int(arr.nbytes))
        # a dest-decoded result is a VIEW of the caller's staging buffer,
        # whose memory stays pinned until the buffer's LAST view evicts:
        # charge the full padded slot we were handed (the per-segment
        # views of one buffer then sum to its true footprint), never just
        # the view's own rows
        nb = arr.nbytes
        if out is not None and getattr(arr, "base", None) is not None:
            nb = max(nb, out.nbytes)
        self._block_cache.put(key, arr, nbytes=nb)
        return arr

    def read_footer_checked(self, table: str, rel: str) -> dict:
        from greengage_tpu.storage.blockfile import read_footer

        hit = self._footer_cache.get((table, rel), MISS)
        if hit is not MISS:
            return hit
        footer = self._read_checked(table, rel, lambda p, c: read_footer(p))
        self._footer_cache.put((table, rel), footer, nbytes=512)
        return footer

    # ---- dictionaries --------------------------------------------------
    def dictionary(self, table: str, col: str) -> Dictionary:
        if table == "@rawdict":
            # transient raw-TEXT dicts are bounded-evicted; a cached plan
            # may still hold an evicted ref — rebuild from the key, which
            # encodes parent:col:version (exactly raw_dictionary's
            # inputs). Probe and fetch under _dict_lock: raw_dictionary's
            # >16 transient bound evicts CONCURRENTLY from staging-pool
            # threads, and an unlocked membership test could pass right
            # before the eviction lands (gg check races).
            with self._dict_lock:
                hit = self._derived.get((table, col))
            if hit is None:
                parent, rcol, ver = col.rsplit(":", 2)
                snap = self.manifest.snapshot()
                if snap.get("version", 0) != int(ver):
                    raise KeyError(
                        f"raw dictionary {col} evicted and manifest moved to "
                        f"v{snap.get('version', 0)}; plan cache is stale")
                self.raw_dictionary(parent, rcol, snap)
                with self._dict_lock:
                    hit = self._derived[(table, col)]
            return hit
        if table == "@expr":
            with self._dict_lock:
                return self._derived[(table, col)]
        # partition children share the PARENT's dictionary: one code space
        # per logical table, so codes compare/join across partitions
        table = table.split("#", 1)[0]
        key = (table, col)
        # unlocked fast-path probe, double-checked under the lock below:
        # a persisted dict is immutable once loaded and evicted only by
        # DROP/recreate DDL (_invalidate_dicts), so a hit is always a
        # valid value for any scan that began before the drop, and a
        # stale miss only costs the locked re-probe — the per-scan hot
        # path skips the mutex
        d = self._dicts.get(key)   # gg:ok(races)
        if d is None:
            with self._dict_lock:   # one load per dict under parallel staging
                d = self._dicts.get(key)
                if d is None:
                    d = self._dicts[key] = Dictionary.load(
                        self._dict_path(table, col))
        return d

    def derived_dictionary(self, values: list[str]) -> tuple[str, str]:
        """Register (or reuse) an in-memory dictionary for a string-function
        result; -> ("@expr", sha1) ref usable wherever a (table, col)
        dict_ref is (hash LUTs, sort ranks, result decode)."""

        h = hashlib.sha1("\x00".join(values).encode()).hexdigest()[:16]
        ref = ("@expr", h)
        with self._dict_lock:
            if ref not in self._derived:
                self._derived[ref] = Dictionary(list(values))
        return ref

    def raw_dictionary(self, table: str, col: str, snapshot=None) -> tuple:
        """Transient dictionary over a raw TEXT column's live strings —
        one first-seen code space across all segments (and partition
        children), cached per manifest version. Lets raw columns flow
        through every dictionary-based path (GROUP BY hashing, sort rank
        LUTs, join translation, result decode) at O(rows) host cost,
        without persisting a dictionary that high-NDV data would bloat.
        -> ("@rawdict", key) usable as a dict_ref."""
        snap = snapshot or self.manifest.snapshot()
        version = snap.get("version", 0)
        parent = table.split("#", 1)[0]
        key = (parent, col, version)
        with self._dict_lock:
            # serialized: two staging threads racing this build would mint
            # DIVERGENT first-seen code spaces for the same column
            hit = self._rawdict_refs.get(key)
            if hit is not None:
                return hit
            schema = self.catalog.get(parent)
            d = Dictionary()
            nseg = schema.policy.numsegments
            for storage in schema.storage_tables():
                for seg in range(nseg):
                    chunk = self.raw_chunk(storage, seg, col, snap)
                    codes = d.encode(chunk.strings())
                    self._rawcode_cache.put(
                        (storage, seg, col, version),
                        (codes.astype(np.int32), chunk.valid),
                        version=version)
            ref = ("@rawdict", f"{parent}:{col}:{version}")
            self._derived[ref] = d
            self._rawdict_refs[key] = ref
            if len(self._rawdict_refs) > 16:   # bound transient memory
                old_key = next(iter(self._rawdict_refs))  # (parent, col, ver)
                old_ref = self._rawdict_refs.pop(old_key)
                self._derived.pop(old_ref, None)
                self._rawcode_cache.drop(
                    lambda k: k[0].split("#", 1)[0] == old_key[0]
                    and k[2] == old_key[1] and k[3] == old_key[2])
            return ref

    def raw_codes(self, table: str, seg: int, col: str, snapshot=None):
        """-> (int32 codes, valid|None) for one segment of a raw column
        under the transient dictionary (staged as an '@rc:' column)."""
        snap = snapshot or self.manifest.snapshot()
        version = snap.get("version", 0)
        key = (table, seg, col, version)
        hit = self._rawcode_cache.get(key, MISS)
        if hit is not MISS:
            return hit
        ref = self.raw_dictionary(table, col, snap)
        hit = self._rawcode_cache.get(key, MISS)
        if hit is not MISS:
            return hit
        # the code entry was byte-evicted while its dictionary survived:
        # re-encode just this segment (every string already has a code, so
        # encode() cannot grow the dictionary here)
        with self._dict_lock:
            d = self._derived.get(ref)
            if d is None:
                # the >16 transient-dict bound evicted OUR ref between
                # raw_dictionary() returning and this lock: rebuild (the
                # registry miss makes raw_dictionary re-encode every
                # segment, repopulating the code cache too)
                self._rawdict_refs.pop(
                    (table.split("#", 1)[0], col, version), None)
                ref = self.raw_dictionary(table, col, snap)
                d = self._derived[ref]
            chunk = self.raw_chunk(table, seg, col, snap)
            res = (d.encode(chunk.strings()).astype(np.int32), chunk.valid)
            self._rawcode_cache.put(key, res, version=version)
            return res

    def _dict_path(self, table: str, col: str) -> str:
        table = table.split("#", 1)[0]
        return os.path.join(self.root, "data", table, f"dict_{col}.json")

    # ---- placement -----------------------------------------------------
    def row_hashes(self, schema: TableSchema, cols: dict[str, np.ndarray],
                   valids: dict[str, np.ndarray | None], keys: tuple[str, ...]) -> np.ndarray:
        acc = None
        for k in keys:
            c = schema.column(k)
            arr = cols[k]
            if c.type.kind is T.Kind.TEXT:
                lut = self.dictionary(schema.name, k).hashes()
                h = lut[arr] if len(lut) else np.zeros(len(arr), dtype=np.uint32)
            else:
                h = native.hash_i64(_as_i64(arr))
            v = valids.get(k)
            if v is not None:
                h = np.where(v, h, np.uint32(0))
            acc = h if acc is None else native.hash_combine(acc, h)
        return acc

    def segment_for_values(self, schema: TableSchema, values: dict) -> int:
        """The one segment owning rows whose distribution keys equal
        ``values`` (storage representation: TEXT = dictionary code, absent
        string = -1 which hits the sentinel hash row). Direct-dispatch's
        hash computation (cdbtargeteddispatch.c analog), bit-identical to
        placement."""
        cols = {}
        valids = {}
        for k in schema.policy.keys:
            v = values[k]
            c = schema.column(k)
            if v is None:
                cols[k] = np.zeros(1, dtype=np.int64)
                valids[k] = np.zeros(1, dtype=bool)
            elif c.type.kind is T.Kind.TEXT:
                cols[k] = np.array([v], dtype=np.int32)
            else:
                cols[k] = np.array([v], dtype=c.type.np_dtype)
        rh = self.row_hashes(schema, cols, valids, schema.policy.keys)
        return int(rh[0] % np.uint32(schema.policy.numsegments))

    def _placement(self, schema: TableSchema, cols, valids, nrows: int, row_offset: int) -> np.ndarray:
        pol = schema.policy
        nseg = pol.numsegments
        if pol.kind is PolicyKind.HASH:
            rh = self.row_hashes(schema, cols, valids, pol.keys)
            return (rh % np.uint32(nseg)).astype(np.int32)
        if pol.kind is PolicyKind.RANDOM:
            return ((np.arange(nrows, dtype=np.int64) + row_offset) % nseg).astype(np.int32)
        raise AssertionError("REPLICATED handled by caller")

    # ---- write path ----------------------------------------------------
    def insert(self, table: str, columns: dict[str, list | np.ndarray],
               valids: dict[str, np.ndarray] | None = None, tx: dict | None = None,
               stream_marks: dict[str, int] | None = None) -> int:
        """Append rows; returns row count. Encodes TEXT, places rows onto
        segments, writes per-segment column files, commits the manifest
        (or stages into an open tx for DTM-lite two-phase commit).
        ``stream_marks`` ({stream_id: batch_seq}) rides an ingest
        micro-batch's commit record as the stream's durable resume
        watermark (forces the write-intent path)."""
        schema = self.catalog.get(table)
        valids = dict(valids or {})
        for c in schema.columns:
            v = valids.get(c.name)
            if not c.nullable and v is not None and not np.all(v):
                raise ValueError(
                    f'null value in column "{c.name}" violates not-null constraint')
        nrows = None
        enc: dict[str, np.ndarray] = {}
        raw_strs: dict[str, np.ndarray] = {}   # raw-encoded TEXT columns
        dict_sizes = {c.name: len(self.dictionary(table, c.name))
                      for c in schema.columns
                      if c.type.kind is T.Kind.TEXT and c.encoding != "raw"}
        with _trace.span("encode", cat="write", table=table):
            for c in schema.columns:
                if c.name not in columns:
                    raise ValueError(f"missing column {c.name}")
                raw = columns[c.name]
                if c.type.kind is T.Kind.TEXT:
                    c = self._resolve_text_encoding(schema, c, raw)
                    if c.encoding == "raw":
                        vals = (raw.decode() if isinstance(raw, T.Coded)
                                else np.asarray(raw, dtype=object))
                        raw_strs[c.name] = vals
                        # placeholder for ragged checks; never hashed (raw
                        # distribution keys are rejected in _resolve)
                        arr = np.zeros(len(vals), dtype=np.int64)
                        enc[c.name] = arr
                        nrows = len(arr) if nrows is None else nrows
                        if len(arr) != nrows:
                            raise ValueError("ragged insert")
                        continue
                    d = self.dictionary(table, c.name)
                    vmask = valids.get(c.name)
                    if isinstance(raw, T.Coded):
                        arr = d.encode_coded(list(raw.vocab), raw.codes)
                        if vmask is not None:
                            arr = np.where(vmask, arr, d.encode([""])[0])
                    elif vmask is None:
                        arr = d.encode(list(raw))
                    else:
                        strs = ["" if not ok else s for s, ok in zip(raw, vmask)]
                        arr = d.encode(strs)
                elif c.type.kind is T.Kind.DECIMAL and not isinstance(raw, np.ndarray):
                    arr = np.array([T.decimal_to_int(v, c.type.scale) for v in raw], dtype=np.int64)
                elif c.type.kind is T.Kind.DATE and not isinstance(raw, np.ndarray):
                    arr = np.array([T.date_to_days(v) for v in raw], dtype=np.int32)
                else:
                    arr = np.asarray(raw, dtype=c.type.np_dtype)
                enc[c.name] = arr
                nrows = len(arr) if nrows is None else nrows
                if len(arr) != nrows:
                    raise ValueError("ragged insert")

        return self._append_encoded(table, schema, enc, valids, raw_strs,
                                    tx, dict_sizes, stream_marks=stream_marks)

    def _append_encoded(self, table, schema, enc, valids, raw_strs, tx,
                        dict_sizes, stream_marks=None) -> int:
        """Shared append tail of insert()/insert_encoded(): placement,
        segfile write, manifest merge (with the optimistic CAS retry)."""
        nrows = len(next(iter(enc.values()))) if enc else 0
        own_tx = tx is None
        if own_tx:
            tx = self.manifest.begin()
        tmeta = tx["tables"].setdefault(table, {"segfiles": {}, "nrows": {}})
        # tx-unique file id: concurrent writers can never clobber each other's
        # staged files; the losing writer's orphans are unreachable via the
        # manifest (appendonlywriter segfile-concurrency analog).
        fileno = uuid.uuid4().hex[:12]

        nseg = schema.policy.numsegments
        total_existing = sum(tmeta["nrows"].get(str(s), 0) for s in range(nseg))
        if schema.policy.kind is PolicyKind.REPLICATED:
            seg_rows = [np.arange(nrows)] * nseg
        else:
            seg_of = self._placement(schema, enc, valids, nrows, total_existing)
            seg_rows = [np.nonzero(seg_of == s)[0] for s in range(nseg)]

        with _trace.span("append", cat="write", table=table,
                         rows=nrows) as sp:
            records = self._write_segfiles(schema, table, tmeta, enc, valids,
                                           seg_rows, fileno,
                                           raw_strs=raw_strs)
            _trace.annotate(sp, files=sum(len(r[1]) for r in records))
        counters.inc("rows_inserted", nrows)

        if own_tx:
            with _trace.span("commit", cat="write", table=table):
                self._commit_append(table, tx, records, dict_sizes,
                                    stream_marks)
        # else a DTM-managed tx: the caller drives prepare/commit and must
        # call flush_dicts(table) between those phases (see runtime/dtm.py)
        return nrows

    def _commit_append(self, table, tx, records, dict_sizes,
                       stream_marks) -> None:
        """The autocommit append's manifest commit (write intent, else the
        per-table CAS with its optimistic retry)."""
        # Ordering: stage files -> prepare_delta (the PER-TABLE
        # sequence CAS — appenders to different tables never contend)
        # -> persist dictionaries (fsynced; superset-safe) -> commit
        # (one fsynced commit-log line). A concurrent SAME-TABLE CAS
        # conflict RETRIES against the fresh snapshot: the staged
        # files are tx-unique and remain valid, so only the manifest
        # record needs re-merging (the appendonly writer's
        # segfile-concurrency model — writers never block readers and
        # autocommit writers serialize optimistically). Each retry is
        # counted in manifest_cas_retry_total (zero for cross-table
        # workloads by construction).

        from greengage_tpu.runtime.logger import counters as _counters

        # a CROSS-PROCESS retry is only safe when this insert assigned
        # no new dictionary codes: a concurrent writer in another
        # process may have claimed the same codes for different words
        # (in-process writers share Dictionary objects and serialize on
        # the session write lock, so they never hit this)
        dict_grew = any(
            len(self.dictionary(table, n)) != sz
            for n, sz in dict_sizes.items())
        if not dict_grew and (stream_marks is not None
                              or self._use_write_intents()):
            # WRITE-INTENT fast path (autocommit appends): a txid-named
            # intent + one merge line carrying these records — no
            # per-table claim, so N same-table appenders commit with
            # ZERO retries (manifest_cas_retry_total stays flat by
            # construction). Gated on `not dict_grew`: an insert that
            # assigned new dictionary codes must keep the per-table
            # CAS, whose conflict is the only cross-process signal
            # that another writer may hold the same codes.
            self.flush_dicts(table)
            ihandle = self.manifest.stage_intent(
                table, records, streams=stream_marks)
            try:
                self.manifest.commit_intent(ihandle)
            except BaseException:
                self.manifest.abort_intent(ihandle)
                raise
            self.maybe_fold_manifest()
            return
        def _fold_stream_marks(tx_):
            # Dictionary growth forces a streamed micro-batch onto
            # the CAS path; the full-state line it stages must still
            # carry the stream's resume watermark — otherwise the
            # rows commit but the durable watermark never advances,
            # and after kill-9 the client resumes from a stale seq
            # and replays already-durable batches (double-apply).
            if not stream_marks:
                return
            state = tx_["tables"].setdefault(
                table, {"segfiles": {}, "nrows": {}})
            marks = state.setdefault("streams", {})
            for sid, sq in stream_marks.items():
                marks[str(sid)] = max(int(marks.get(str(sid), 0)),
                                      int(sq))

        _fold_stream_marks(tx)
        last = None
        for attempt in range(20):
            try:
                handle = self.manifest.prepare_delta(tx, [table])
                break
            except RuntimeError as e:
                last = e
                if dict_grew:
                    self._invalidate_dicts(table)
                    raise
                _counters.inc("manifest_cas_retry_total")
                _time.sleep(0.01 * (attempt + 1))
                tx = self.manifest.begin()
                merge_segfile_records(tx, table, records)
                _fold_stream_marks(tx)
        else:
            self._invalidate_dicts(table)
            raise RuntimeError(
                f"write-write conflict persisted after retries: {last}")
        self.flush_dicts(table)
        try:
            self.manifest.commit_delta(handle)
        except BaseException:
            self.manifest.abort_delta(handle)
            raise
        self.maybe_fold_manifest()

    def _use_write_intents(self) -> bool:
        """GUC gate for the intent append path (write_intents_enabled,
        default on). self.settings is None for bare TableStore uses
        (tools, unit tests) — those default on too."""
        return bool(getattr(self.settings, "write_intents_enabled", True))

    def _resolve_text_encoding(self, schema, col, raw_values):
        """First-insert decision for TEXT encoding="auto": high-NDV columns
        go raw (byte blob + offsets; arbitrary-cardinality strings, the
        varlena analog), low-NDV go dict. Distribution keys are always dict
        (placement hashes string bytes via the dictionary LUT)."""
        if col.encoding != "auto":
            return col
        from greengage_tpu.catalog.schema import Column

        if col.name in schema.policy.keys or isinstance(raw_values, T.Coded):
            mode = "dict"
        else:
            sample = list(raw_values[:100_000])
            mode = ("raw" if len(sample) >= 4096
                    and len(set(sample)) > 0.5 * len(sample) else "dict")
        new = Column(col.name, col.type, col.nullable, mode)
        schema.columns[[c.name for c in schema.columns].index(col.name)] = new
        self.catalog._save()
        return new

    def raw_column_names(self, table: str) -> set:
        return {c.name for c in self.catalog.get(table).columns
                if c.type.kind is T.Kind.TEXT and c.encoding == "raw"}

    def has_raw_columns(self, table: str) -> bool:
        return bool(self.raw_column_names(table))

    def flush_dicts(self, table: str) -> None:
        schema = self.catalog.get(table)
        table = table.split("#", 1)[0]   # children share the parent dict
        for c in schema.columns:
            if c.type.kind is not T.Kind.TEXT:
                continue
            with self._dict_lock:   # loaders insert from staging threads
                d = self._dicts.get((table, c.name))
            if d is not None:
                os.makedirs(os.path.join(self.root, "data", table),
                            exist_ok=True)
                d.save(self._dict_path(table, c.name))

    def _invalidate_dicts(self, table: str) -> None:
        table = table.split("#", 1)[0]
        with self._dict_lock:   # staging threads load dicts concurrently
            for key in [k for k in self._dicts if k[0] == table]:
                del self._dicts[key]

    def _invalidate_dicts_all(self) -> None:
        with self._dict_lock:
            self._dicts.clear()

    # ---- read path -----------------------------------------------------
    @property
    def last_prune(self):
        """(blocks kept, blocks total) of THIS THREAD's last read — the
        staging pool runs read_segment concurrently, so the stat is
        thread-local; each worker reads its own right after its read."""
        return getattr(self._tl, "last_prune", None)

    @last_prune.setter
    def last_prune(self, value) -> None:
        self._tl.last_prune = value

    def block_index(self, base: str, rel: str, table: str | None = None):
        """Per-segfile block-value index (the btree/bitmap AM analog for
        append-only block storage): sorted (value, block) pairs, deduped
        per block, as a rebuildable .bidx.npz sidecar next to the data
        file. An equality probe binary-searches the values and returns
        exactly the blocks containing the key — block-selective scans on
        UNCLUSTERED data, where zone maps (which need clustering) keep
        everything. Low-NDV columns degenerate to few (value, block)
        runs — the bitmap-index shape; high-NDV to a dense sorted list —
        the btree shape. Sidecars are derived data: built lazily, not in
        the manifest, reaped with their data file. ``table`` (the storage
        table owning ``rel``) enables checked self-healing reads."""
        from greengage_tpu.storage.blockfile import (read_column_file,
                                                     read_footer)

        path = os.path.join(base, rel)
        sidecar = path[:-len(".ggb")] + ".bidx.npz"
        try:
            if os.path.getmtime(sidecar) >= os.path.getmtime(path):
                with np.load(sidecar) as z:
                    return z["values"], z["blocks"]
        except (OSError, ValueError, KeyError):
            pass
        if table is not None:
            footer = self.read_footer_checked(table, rel)
            data = self.read_file(table, rel)
        else:
            footer = read_footer(path)
            data = read_column_file(path)
        vals_parts, blk_parts = [], []
        row = 0
        for i, b in enumerate(footer["blocks"]):
            u = np.unique(data[row:row + b["nrows"]])
            vals_parts.append(u)
            blk_parts.append(np.full(len(u), i, np.int32))
            row += b["nrows"]
        values = (np.concatenate(vals_parts) if vals_parts
                  else np.empty(0, data.dtype))
        blocks = (np.concatenate(blk_parts) if blk_parts
                  else np.empty(0, np.int32))
        order = np.argsort(values, kind="stable")
        values, blocks = values[order], blocks[order]
        try:
            fd, tmp = tempfile.mkstemp(dir=base, prefix=".bidx",
                                       suffix=".npz")
            os.close(fd)
            np.savez(tmp, values=values, blocks=blocks)
            os.replace(tmp, sidecar)
        except OSError:
            pass   # cache write failure: the in-memory index still serves
        return values, blocks

    @staticmethod
    def _index_blocks_for(values, blocks, op, val) -> set:
        """Blocks containing any value satisfying ``op val``: equality is
        the point probe, range ops slice the sorted value run — the btree
        range-scan analog (nbtsearch.c _bt_first) over block addresses.
        On unclustered data a wide range keeps most blocks (honest); a
        selective range keeps only the blocks its few values live in."""
        if op == "=":
            lo = np.searchsorted(values, val, side="left")
            hi = np.searchsorted(values, val, side="right")
        elif op == "<":
            lo, hi = 0, np.searchsorted(values, val, side="left")
        elif op == "<=":
            lo, hi = 0, np.searchsorted(values, val, side="right")
        elif op == ">":
            lo, hi = np.searchsorted(values, val, side="right"), len(values)
        elif op == ">=":
            lo, hi = np.searchsorted(values, val, side="left"), len(values)
        else:
            return set(blocks.tolist())
        return set(blocks[lo:hi].tolist())

    def _kept_blocks(self, table, files, base, prune, indexed_cols=frozenset()):
        """Per data-fileno block keep-list: a block survives only if EVERY
        pushed predicate could match its zone map [zmin, zmax] AND, for
        equality predicates on indexed columns, the block index says the
        key is present. -> ({fileno: [block idx]}, kept, total); filenos
        absent from the dict keep all blocks."""
        keep: dict[str, list[int]] = {}
        kept = total = 0
        by_fileno_nblocks: dict[str, int] = {}
        by_col = {}
        for col, op, val in prune:
            by_col.setdefault(col, []).append((op, val))
        for rel in files:   # one footer read per relevant file
            fn = os.path.basename(rel)
            parts = fn.split(".")
            if len(parts) != 3 or not fn.endswith(".ggb"):
                continue   # data files only: <col>.<fileno>.ggb
            col, fileno = parts[0], parts[1]
            preds = by_col.get(col)
            if not preds:
                continue
            blocks = self.read_footer_checked(table, rel)["blocks"]
            by_fileno_nblocks[fileno] = len(blocks)
            idx_keep: set | None = None
            if col in indexed_cols and preds:
                vals, blks = self.block_index(base, rel, table=table)
                for op, v in preds:
                    hit = self._index_blocks_for(vals, blks, op, v)
                    idx_keep = hit if idx_keep is None else idx_keep & hit
            ok = []
            for i, b in enumerate(blocks):
                if idx_keep is not None and i not in idx_keep:
                    continue
                if "zmin" not in b:
                    ok.append(i)
                    continue
                lo, hi = b["zmin"], b["zmax"]
                good = True
                for op, val in preds:
                    if not ((op == "=" and lo <= val <= hi)
                            or (op == "<" and lo < val)
                            or (op == "<=" and lo <= val)
                            or (op == ">" and hi > val)
                            or (op == ">=" and hi >= val)):
                        good = False
                        break
                if good:
                    ok.append(i)
            prev = keep.get(fileno)
            if prev is None:
                keep[fileno] = ok
            else:
                prev_set = set(prev)
                keep[fileno] = [i for i in ok if i in prev_set]
        for fileno, ok in keep.items():
            total += by_fileno_nblocks.get(fileno, 0)
            kept += len(ok)
        # a fileno that keeps every block reads as unpruned: one cache
        # entry a file whatever the predicate, shared with the plain scan
        keep = {f: ok for f, ok in keep.items()
                if len(ok) < by_fileno_nblocks.get(f, 0)}
        return keep, kept, total

    def read_segment(self, table: str, seg: int, columns: list[str] | None = None,
                     snapshot: dict | None = None, prune: tuple | None = None,
                     dest: dict | None = None):
        """-> (cols: {name: np.ndarray}, valids: {name: np.ndarray|None}, nrows).

        ``prune``: zone-map predicates [(col, op, value)] — blocks they rule
        out are skipped for EVERY requested column (block partitioning is
        identical across a fileno's columns), shrinking the staged rows.

        ``dest``: optional {col: preallocated array} destinations (the
        executor's staging-buffer slots). A plain column of at most one
        data file and no deletion bitmap lands STRAIGHT in its slot, on
        this thread (``read_file(out=)``: decoded there on a miss, copied
        there on a block-cache hit; under a keep list the kept blocks'
        rows, in the slot's prefix) and the returned array is a view of
        it, so staging has nothing left to copy. More data files (their
        rows are concatenated), a deletion bitmap (rows are filtered
        after assembly) and the virtual '@' columns return arrays of
        their own."""
        schema = self.catalog.get(table)
        snap = snapshot or self.manifest.snapshot()
        tmeta = snap["tables"].get(table, {"segfiles": {}, "nrows": {}})
        files = tmeta["segfiles"].get(str(seg), [])
        want = columns if columns is not None else schema.column_names
        cols: dict[str, np.ndarray] = {}
        valids: dict[str, np.ndarray | None] = {}
        nrows = tmeta["nrows"].get(str(seg), 0)
        base = os.path.join(self.data_root(seg), table)
        keep = None
        self.last_prune = None
        # deletion bitmap (visimap analog): rows marked deleted are dropped
        # after assembly. Zone-map block pruning is skipped while a bitmap
        # exists — pruned blocks would desync the bitmap's row numbering;
        # VACUUM compaction restores pruned scans.
        keep_rows = self.delmask_keep(table, seg, snap)
        tally = current_tally()
        if prune and keep_rows is not None and tally is not None:
            tally.prune_skipped = True
        if prune and keep_rows is None:
            idx_cols = frozenset(
                d["column"] for d in getattr(schema, "indexes", {}).values())
            keep, kept_n, total_n = self._kept_blocks(table, files, base,
                                                      prune, idx_cols)
            self.last_prune = (kept_n, total_n)
        for name in want:
            if name.startswith("@rc:"):
                # raw column under its transient dictionary (group/sort/
                # join keys on raw TEXT)
                arr, vmask = self.raw_codes(table, seg, name[4:], snap)
                cols[name] = arr
                valids[name] = vmask
                continue
            if name.startswith("@hp:"):
                # host-evaluated predicate over a raw TEXT column: the
                # device stages a boolean column (the dictionary-LUT idea
                # at O(rows) host cost; cached per manifest version)
                arr, vmask = self.eval_host_pred(table, seg, name, snap)
                cols[name] = arr
                valids[name] = vmask
                continue
            if name.startswith("@rp:"):
                # one packed-prefix word of a raw column (device eq/LIKE)
                _, rcol, w = name.split(":", 2)
                words, _l = self.raw_prefix(table, seg, rcol, snap)
                cols[name] = words[:, int(w)]
                valids[name] = self.raw_chunk(table, seg, rcol, snap).valid
                continue
            if name.startswith("@rw:"):
                # one WIDE packed word (general device LIKE byte window);
                # pack only the lanes the column's max length needs
                _, rcol, w = name.split(":", 2)
                nw = max(-(-self.raw_max_len(table, rcol, snap) // 8),
                         int(w) + 1)
                words, _l = self.raw_prefix(table, seg, rcol, snap,
                                            nwords=min(nw, RAW_WIDE_WORDS))
                cols[name] = words[:, int(w)]
                valids[name] = self.raw_chunk(table, seg, rcol, snap).valid
                continue
            if name.startswith("@rl:"):
                rcol = name[4:]
                cols[name] = self.raw_lengths(table, seg, rcol, snap)
                valids[name] = self.raw_chunk(table, seg, rcol, snap).valid
                continue
            c = schema.column(name)
            stored_raw = c.type.kind is T.Kind.TEXT and (
                c.encoding == "raw"
                or any(os.path.basename(rel).startswith(name + ".")
                       and rel.endswith(".rawoffs.ggb") for rel in files))
            if stored_raw:
                # device sees a stable row surrogate; strings decode at
                # result finalize (fetch_raw). The file check guards the
                # crash window where raw segfiles committed but the
                # catalog's encoding resolution didn't persist — reading
                # offs/bytes blobs as int32 codes would be garbage
                cols[name] = ((np.int64(seg) << np.int64(40))
                              + np.arange(nrows, dtype=np.int64))
                valids[name] = self.raw_chunk(table, seg, name, snap).valid
                continue
            data_parts, valid_parts = [], []
            data_rels, valid_rels = [], []
            for rel in files:
                fn = os.path.basename(rel)
                if fn.startswith(name + ".") and fn.endswith(".ggb"):
                    if fn.endswith(".valid.ggb"):
                        valid_rels.append(rel)
                    else:
                        data_rels.append(rel)
            # in place: at most one data file and no deletion bitmap —
            # the rows land in the caller's slot, pruned or not
            d = None
            if dest is not None and keep_rows is None \
                    and len(data_rels) <= 1:
                d = dest.get(name)
            elif tally is not None and dest is not None and name in dest:
                tally.off_slot = "delmask" if keep_rows is not None \
                    else "files"

            def _bidx(rel):
                # the kept-block slice applies to data AND valid files of
                # a fileno alike (block partitioning is identical), or the
                # two would misalign after pruning
                if keep is None:
                    return None
                parts = os.path.basename(rel).split(".")
                return keep.get(parts[1] if len(parts) >= 3 else None)

            for rel in valid_rels:
                valid_parts.append((rel, self.read_file(table, rel,
                                                        _bidx(rel))))
            for rel in data_rels:
                data_parts.append((rel, self.read_file(table, rel,
                                                       _bidx(rel), out=d)))
            if len(data_parts) == 1:
                # single segfile (the common post-load shape): the view of
                # the caller's slot, or the cache-resident array as-is —
                # staging copies that one into its own buffer, so nothing
                # downstream mutates it
                cols[name] = data_parts[0][1]
            elif data_parts:
                cols[name] = np.concatenate([a for _, a in data_parts])
            else:
                # no rows: none to copy, so the slot's empty prefix will do
                cols[name] = fit_slot(d, c.type.np_dtype, 0)
                if cols[name] is None:
                    cols[name] = np.empty(0, dtype=c.type.np_dtype)
            if valid_parts:
                # files without a .valid sibling are all-valid
                vmap = {r.replace(".valid.ggb", ".ggb"): a for r, a in valid_parts}
                vs = []
                for r, a in data_parts:
                    vs.append(vmap.get(r, np.ones(len(a), dtype=np.uint8)))
                valids[name] = np.concatenate(vs).astype(bool)
            else:
                valids[name] = None
            if keep is None and len(cols[name]) != nrows:
                raise IOError(f"{table}.{name} seg{seg}: {len(cols[name])} rows, manifest says {nrows}")
        if keep is not None and want:
            nrows = len(next(iter(cols.values()))) if cols else 0
        if keep_rows is not None:
            # raw-TEXT surrogates keep their ORIGINAL row numbers through
            # the filter (they were generated before it), so fetch_raw
            # still indexes the full blob correctly
            for name in cols:
                cols[name] = cols[name][keep_rows]
                v = valids.get(name)
                if v is not None:
                    valids[name] = v[keep_rows]
            nrows = int(keep_rows.sum())
        return cols, valids, nrows

    # ---- raw TEXT columns (varlena analog) -----------------------------
    def raw_chunk(self, table: str, seg: int, col: str, snapshot=None):
        """Assembled (blob, offsets, valid, strings-cache) for one raw TEXT
        column of one segment, manifest-version cached."""
        snap = snapshot or self.manifest.snapshot()
        version = snap.get("version", 0)
        key = (table, col, seg, version)
        hit = self._raw_cache.get(key, MISS)
        if hit is not MISS:
            return hit
        tmeta = snap["tables"].get(table, {"segfiles": {}})
        files = tmeta["segfiles"].get(str(seg), [])
        blob_rels, offs_parts, valid_parts = [], [], []
        bytes_base = 0
        valid_for = {}
        for rel in files:
            fn = os.path.basename(rel)
            if fn.startswith(col + ".") and fn.endswith(".valid.ggb"):
                valid_for[fn.replace(".valid.ggb", "")] = self.read_file(
                    table, rel)
        for rel in files:
            fn = os.path.basename(rel)
            if fn.startswith(col + ".") and fn.endswith(".rawoffs.ggb"):
                offs = self.read_file(table, rel).astype(np.int64)
                n = len(offs) - 1
                offs_parts.append(offs[1:] + bytes_base)   # per-row END offsets
                blob_rels.append(rel.replace(".rawoffs.ggb", ".rawbytes.ggb"))
                v = valid_for.get(fn.replace(".rawoffs.ggb", ""))
                valid_parts.append(np.asarray(v, bool) if v is not None
                                   else np.ones(n, dtype=bool))
                bytes_base += int(offs[-1])
        ends = np.concatenate(offs_parts) if offs_parts else np.zeros(0, np.int64)
        valid = np.concatenate(valid_parts) if valid_parts else np.zeros(0, bool)
        chunk = _RawChunk(ends, None if valid.all() else valid, blob_rels,
                          reader=lambda rel: self.read_file(table, rel))
        self._raw_cache.put(key, chunk, version=version)
        return chunk

    def raw_max_len(self, table: str, col: str, snapshot=None) -> int:
        """Max utf-8 byte length over every committed row of a raw column
        (cached per version) — gates device-decidability of general LIKE:
        rows longer than the staged window could match past it."""
        snap = snapshot or self.manifest.snapshot()
        version = snap.get("version", 0)
        key = ("@maxlen", table, col, version)
        hit = self._rawprefix_cache.get(key, MISS)
        if hit is not MISS:
            return hit
        schema = self.catalog.get(table)
        best = 0
        for seg in range(schema.policy.numsegments):
            chunk = self.raw_chunk(table, seg, col, snap)
            ends = chunk.ends
            if len(ends):
                starts = np.concatenate([np.zeros(1, np.int64), ends[:-1]])
                best = max(best, int((ends - starts).max()))
        self._rawprefix_cache.put(key, best, version=version)
        return best

    def raw_lengths(self, table: str, seg: int, col: str, snapshot=None):
        """Exact byte lengths of a raw column's rows for one segment —
        O(rows) offset subtraction straight off the chunk, WITHOUT the
        byte-window packing raw_prefix pays (an @rl-only consumer, e.g.
        ``length(col)`` device chains, must not fund word lanes it never
        reads). Cached per version under the same key raw_prefix shares,
        so either producer serves later readers."""
        snap = snapshot or self.manifest.snapshot()
        version = snap.get("version", 0)
        lkey = ("@len", table, col, seg, version)
        hit = self._rawprefix_cache.get(lkey, MISS)
        if hit is not MISS:
            return hit
        chunk = self.raw_chunk(table, seg, col, snap)
        ends = chunk.ends
        starts = (np.concatenate([np.zeros(1, np.int64), ends[:-1]])
                  if len(ends) else np.zeros(0, np.int64))
        lengths = (ends - starts).astype(np.int32)
        self._rawprefix_cache.put(lkey, lengths, version=version)
        return lengths

    def raw_is_ascii(self, table: str, col: str, snapshot=None) -> bool:
        """True when every committed byte of a raw column is < 0x80
        (cached per version) — gates the byte-window scalar lowerings
        whose semantics count CHARACTERS (upper/lower/substr/length):
        over pure ASCII, bytes and characters coincide, so the device
        byte ops are exact; otherwise those chains stay on the host."""
        snap = snapshot or self.manifest.snapshot()
        version = snap.get("version", 0)
        key = ("@ascii", table, col, version)
        hit = self._rawprefix_cache.get(key, MISS)
        if hit is not MISS:
            return hit
        schema = self.catalog.get(table)
        ok = True
        for seg in range(schema.policy.numsegments):
            chunk = self.raw_chunk(table, seg, col, snap)
            if len(chunk.ends):
                blob = chunk.blob()
                if len(blob) and int(blob.max()) >= 0x80:
                    ok = False
                    break
        self._rawprefix_cache.put(key, ok, version=version)
        return ok

    def raw_prefix(self, table: str, seg: int, col: str, snapshot=None,
                   nwords: int = RAW_PREFIX_WORDS):
        """Packed fixed-width byte prefix of a raw TEXT column, the device
        representation for on-device equality/LIKE-prefix predicates
        (VERDICT r3 #7): the first RAW_PREFIX_BYTES utf-8 bytes of every
        row packed big-endian into RAW_PREFIX_WORDS int64 lanes (equal
        strings <=> equal words + equal length; utf-8 preserves prefix
        relations), plus the exact byte length. O(rows x 32) vectorized
        numpy, manifest-version cached — NOT the per-statement O(heap)
        python of the host-predicate fallback.
        -> (words [n, RAW_PREFIX_WORDS] int64, lengths [n] int32)."""
        snap = snapshot or self.manifest.snapshot()
        version = snap.get("version", 0)
        key = (table, col, seg, version, nwords)
        lkey = ("@len", table, col, seg, version)
        hit = self._rawprefix_cache.get(key, MISS)
        if hit is not MISS:
            lens_hit = self._rawprefix_cache.get(lkey, MISS)
            if lens_hit is not MISS:    # may be independently evicted
                return hit, lens_hit
        chunk = self.raw_chunk(table, seg, col, snap)
        ends = chunk.ends
        n = len(ends)
        blob = chunk.blob()
        starts = (np.concatenate([np.zeros(1, np.int64), ends[:-1]])
                  if n else np.zeros(0, np.int64))
        lengths = (ends - starts).astype(np.int32)
        words = np.zeros((n, nwords), np.uint64)
        if n and len(blob):
            # chunk rows: the transient n x width gather matrices would
            # otherwise spike ~KB/row of host memory on big segments
            # scale the chunk inversely with the window so the transient
            # gather matrices stay ~bounded regardless of nwords
            CH = max((1 << 22) // max(nwords, 1), 1 << 16)
            steps = np.arange(nwords * 8, dtype=np.int64)[None, :]
            for a in range(0, n, CH):
                b = min(a + CH, n)
                idx = starts[a:b, None] + steps
                m = idx < ends[a:b, None]
                data = np.where(m, blob[np.minimum(idx, len(blob) - 1)],
                                np.uint8(0)).astype(np.uint64)
                for w in range(nwords):
                    acc = np.zeros(b - a, np.uint64)
                    for j in range(8):
                        acc = (acc << np.uint64(8)) | data[:, w * 8 + j]
                    words[a:b, w] = acc
        self._rawprefix_cache.put(key, words.view(np.int64), version=version)
        self._rawprefix_cache.put(lkey, lengths, version=version)
        return words.view(np.int64), lengths

    @staticmethod
    def host_pred_name(col: str, payload: dict) -> str:
        """Virtual staged-column name carrying a host-evaluated raw-text
        predicate: '@hp:<col>:<hex json payload>'."""

        return f"@hp:{col}:{json.dumps(payload, sort_keys=True).encode().hex()}"

    def eval_host_pred(self, table: str, seg: int, name: str, snapshot=None):
        """-> (bool array, valid|None) for one '@hp:' virtual column."""

        snap = snapshot or self.manifest.snapshot()
        version = snap.get("version", 0)
        key = (table, seg, name, version)
        hit = self._hp_cache.get(key, MISS)
        if hit is not MISS:
            return hit
        _, col, hexpayload = name.split(":", 2)
        payload = json.loads(bytes.fromhex(hexpayload))
        chunk = self.raw_chunk(table, seg, col, snap)
        strs = chunk.strings()
        op = payload["op"]
        if op == "like":
            rx = T.like_to_regex(payload["pattern"])
            out = np.fromiter((rx.fullmatch(s) is not None for s in strs),
                              bool, len(strs))
        elif op == "eq":
            out = np.fromiter((s == payload["value"] for s in strs),
                              bool, len(strs))
        elif op == "in":
            vals = set(payload["values"])
            out = np.fromiter((s in vals for s in strs), bool, len(strs))
        elif op == "chain":
            # string-function chain + comparison (utils/strfuncs semantics)

            from greengage_tpu.utils import strfuncs

            chain = payload["chain"]
            vals = [strfuncs.apply_chain(s, chain) for s in strs]
            cmp = payload["cmp"]
            if cmp == "like":
                rx = T.like_to_regex(payload["value"])
                out = np.fromiter(
                    (rx.fullmatch(v) is not None for v in vals),
                    bool, len(vals))
            elif cmp == "in":
                targets = set(payload["value"])
                out = np.fromiter((v in targets for v in vals),
                                  bool, len(vals))
            else:
                fn = {"=": operator.eq, "<>": operator.ne,
                      "<": operator.lt, "<=": operator.le,
                      ">": operator.gt, ">=": operator.ge}[cmp]
                tgt = payload["value"]
                out = np.fromiter((fn(v, tgt) for v in vals),
                                  bool, len(vals))
        else:
            raise ValueError(f"unknown host predicate op {op}")
        res = (out, chunk.valid)
        self._hp_cache.put(key, res, version=version)
        return res

    def fetch_raw(self, table: str, col: str, surrogates: np.ndarray,
                  snapshot=None):
        """Decode raw-TEXT row surrogates ((seg << 40) | row) back to
        strings for result finalize."""
        out = np.empty(len(surrogates), dtype=object)
        if len(surrogates) == 0:
            return out
        sur = np.asarray(surrogates, np.int64)
        segs = sur >> np.int64(40)
        rows = sur & np.int64((1 << 40) - 1)
        for s in np.unique(segs):
            chunk = self.raw_chunk(table, int(s), col, snapshot)
            strs = chunk.strings()
            mask = segs == s
            out[mask] = [strs[r] for r in rows[mask]]
        return out

    def rewrite_table(self, table: str, new_numsegments: int) -> int:
        """ALTER TABLE ... EXPAND TABLE analog (tablecmds.c:4067): re-place
        every row at the new cluster width and publish atomically. Works on
        already-encoded columns (TEXT codes kept; placement hashes go through
        the dictionary LUT so string placement stays bytes-based)."""
        from greengage_tpu.catalog.schema import DistPolicy, PolicyKind

        schema = self.catalog.get(table)
        raw_names = self.raw_column_names(table)
        old_nseg = schema.policy.numsegments
        # gather all rows from the old layout
        parts_cols: dict[str, list] = {c.name: [] for c in schema.columns}
        parts_valids: dict[str, list] = {c.name: [] for c in schema.columns}
        any_valid = {c.name: False for c in schema.columns}
        snap = self.manifest.snapshot()
        total = 0
        read_segs = 1 if schema.policy.kind is PolicyKind.REPLICATED else old_nseg
        for seg in range(read_segs):
            cols, valids, n = self.read_segment(table, seg, snapshot=snap)
            total += n
            for c in schema.columns:
                if c.name in raw_names:
                    # re-placement needs the actual strings, not surrogates;
                    # the deletion bitmap filter must match read_segment's
                    strs = np.asarray(
                        self.raw_chunk(table, seg, c.name, snap).strings(),
                        dtype=object)
                    km = self.delmask_keep(table, seg, snap)
                    cols[c.name] = strs if km is None else strs[km]
                parts_cols[c.name].append(cols[c.name])
                v = valids[c.name]
                if v is not None:
                    any_valid[c.name] = True
                parts_valids[c.name].append(
                    v if v is not None else np.ones(n, dtype=bool))
        enc = {c.name: np.concatenate(parts_cols[c.name]) if parts_cols[c.name]
               else np.empty(0, dtype=(object if c.name in raw_names
                                       else c.type.np_dtype))
               for c in schema.columns}
        raw_strs = {n: enc[n] for n in raw_names}
        for n in raw_names:   # placeholder for width checks; never hashed
            enc[n] = np.zeros(len(raw_strs[n]), np.int64)
        valids = {
            c.name: np.concatenate(parts_valids[c.name])
            for c in schema.columns
            if any_valid[c.name] and parts_valids[c.name]
        }

        new_policy = DistPolicy(schema.policy.kind, schema.policy.keys, new_numsegments)
        old_files = [
            rel for files in snap["tables"].get(table, {"segfiles": {}})["segfiles"].values()
            for rel in files
        ]
        tx = self.manifest.begin()
        # the manifest carries the table width so layout + width publish in
        # ONE atomic commit; the catalog copy is reconciled from it on open
        tx["tables"][table] = {"segfiles": {}, "nrows": {},
                               "numsegments": new_numsegments}
        tmeta = tx["tables"][table]
        nrows = len(next(iter(enc.values()))) if enc else 0
        if new_policy.kind is PolicyKind.REPLICATED:
            seg_rows = [np.arange(nrows)] * new_numsegments
        elif new_policy.kind is PolicyKind.HASH:
            rh = self.row_hashes(schema, enc, valids, new_policy.keys)
            seg_of = (rh % np.uint32(new_numsegments)).astype(np.int32)
            seg_rows = [np.nonzero(seg_of == s)[0] for s in range(new_numsegments)]
        else:
            seg_of = (np.arange(nrows) % new_numsegments).astype(np.int32)
            seg_rows = [np.nonzero(seg_of == s)[0] for s in range(new_numsegments)]
        self._write_segfiles(schema, table, tmeta, enc, valids, seg_rows,
                             uuid.uuid4().hex[:12], raw_strs=raw_strs)
        v = self.manifest.prepare(tx)
        try:
            self.manifest.commit(v)
        except BaseException:
            # a lost commit (cross-process fold raced the root version
            # guard) must release the staged claim, as commit_tx does
            self.manifest.abort(v)
            raise
        # catalog: table now spans the new width (manifest is authoritative
        # if we crash before this save — see reconcile_widths)
        schema.policy = new_policy
        self.catalog._save()
        # GC the old layout's files (unreachable from the new manifest)
        for rel in old_files:
            try:
                os.remove(self.seg_file_path(table, rel))
            except OSError:
                pass
        return nrows

    def stage_replace(self, tx: dict, table: str, enc: dict, valids: dict,
                      raw_strs: dict | None = None) -> list:
        """Stage a full-table replacement into a manifest transaction.
        Returns the OLD file rels (unreachable once the tx commits; the
        caller GCs them post-commit). ``enc`` holds storage-representation
        arrays (TEXT = dictionary codes; raw TEXT = placeholder, actual
        strings in ``raw_strs``); placement is recomputed, so updated
        distribution keys move rows to their new owner segments
        (SplitUpdate's explicit redistribution analog,
        src/backend/executor/nodeSplitUpdate.c)."""
        from greengage_tpu.catalog.schema import PolicyKind

        schema = self.catalog.get(table)
        raw_cols = self.raw_column_names(table)
        if raw_cols - set(raw_strs or ()):
            raise ValueError(
                f"table {table} republish is missing decoded strings for "
                f"raw columns {sorted(raw_cols - set(raw_strs or ()))}")
        for c in schema.columns:
            v = valids.get(c.name)
            if not c.nullable and v is not None and not np.all(v):
                raise ValueError(
                    f'null value in column "{c.name}" violates not-null constraint')
        nseg = schema.policy.numsegments
        old_files = [
            rel for files in tx["tables"].get(
                table, {"segfiles": {}})["segfiles"].values()
            for rel in files
        ]
        nrows = len(next(iter(enc.values()))) if enc else 0
        tx["tables"][table] = {"segfiles": {}, "nrows": {},
                               "numsegments": nseg}
        tmeta = tx["tables"][table]
        if schema.policy.kind is PolicyKind.REPLICATED:
            seg_rows = [np.arange(nrows)] * nseg
        elif schema.policy.kind is PolicyKind.HASH:
            rh = self.row_hashes(schema, enc, valids, schema.policy.keys)
            seg_of = (rh % np.uint32(nseg)).astype(np.int32)
            seg_rows = [np.nonzero(seg_of == s)[0] for s in range(nseg)]
        else:
            seg_of = (np.arange(nrows) % nseg).astype(np.int32)
            seg_rows = [np.nonzero(seg_of == s)[0] for s in range(nseg)]
        self._write_segfiles(schema, table, tmeta, enc, valids, seg_rows,
                             uuid.uuid4().hex[:12], raw_strs=raw_strs)
        return old_files

    GC_GRACE_S = 30.0   # snapshot readers finish well within this

    def maybe_fold_manifest(self) -> bool:
        """Checkpoint the delta backlog into the root snapshot once it
        reaches manifest_delta_fold_threshold commits (the
        checkpoint_segments analog). Opportunistic and race-tolerant —
        a concurrent fold/root writer simply wins the claim."""
        threshold = 64
        if self.settings is not None:
            threshold = int(getattr(self.settings,
                                    "manifest_delta_fold_threshold", 64))
        if self.manifest.delta_backlog() < max(1, threshold):
            return False
        return self.manifest.fold(min_deltas=max(1, threshold))

    def gc_files(self, table: str, rels: list, defer: bool = True) -> None:
        """Reclaim files made unreachable by a commit. Deletion is DEFERRED
        by a grace period: concurrent lock-free readers may still be
        scanning these files from an older snapshot (the server's
        concurrent SELECT vs UPDATE interleaving). defer=False deletes
        immediately (rollback of files nobody else ever saw)."""

        if defer:
            if not hasattr(self, "_pending_gc"):
                self._pending_gc = []
            self._pending_gc.append((_time.monotonic(), table, list(rels)))
            self.reap_gc()
            return
        for rel in rels:
            try:
                os.remove(self.seg_file_path(table, rel))
            except OSError:
                pass
            if rel.endswith(".ggb") and len(
                    os.path.basename(rel).split(".")) == 3:
                try:   # derived block-index sidecar dies with its file
                    os.remove(self.seg_file_path(table, rel)[:-len(".ggb")]
                              + ".bidx.npz")
                except OSError:
                    pass

    def reap_gc(self) -> int:
        """Delete deferred-GC entries older than the grace period."""

        pend = getattr(self, "_pending_gc", [])
        now = _time.monotonic()
        keep, removed = [], 0
        for ts, table, rels in pend:
            if now - ts >= self.GC_GRACE_S:
                self.gc_files(table, rels, defer=False)
                removed += len(rels)
            else:
                keep.append((ts, table, rels))
        self._pending_gc = keep
        return removed

    def sweep_orphans(self, grace_s: float = 120.0) -> int:
        """Delete segment files not referenced by the current manifest and
        older than ``grace_s`` (crashed writers' staging, rolled-back DML
        from dead processes, deferred GC lost at exit) — the VACUUM role.
        Recent files are spared: they may belong to an in-flight write."""

        snap = self.manifest.snapshot()
        referenced = set()
        for tname, tmeta in snap.get("tables", {}).items():
            for files in tmeta.get("segfiles", {}).values():
                for rel in files:
                    referenced.add((tname, os.path.basename(rel)))
        removed = 0
        now = _time.time()
        # sweep the mirror trees too: replication/repair stage (.tmp /
        # .repair.) there, and GC'd files' mirror copies are just as
        # unreachable as the acting copies
        roots = {os.path.join(self.root, "data")}
        segs = getattr(self.catalog, "segments", None)
        if segs is not None:
            for c in range(segs.numsegments):
                roots.add(mirror_root(self.root, c))
        for root in sorted(roots):
            if not os.path.isdir(root):
                continue
            for tname in os.listdir(root):
                tdir = os.path.join(root, tname)
                if not os.path.isdir(tdir):
                    continue
                for segdir in os.listdir(tdir):
                    sdir = os.path.join(tdir, segdir)
                    if not segdir.startswith("seg") or not os.path.isdir(sdir):
                        continue
                    for fn in os.listdir(sdir):
                        if not fn.endswith(".ggb"):
                            if ".repair." not in fn and not \
                                    fn.endswith(".tmp"):
                                continue
                            # crashed repair/copy staging: age out below
                        elif (tname, fn) in referenced:
                            continue
                        p = os.path.join(sdir, fn)
                        try:
                            if now - os.path.getmtime(p) >= grace_s:
                                os.remove(p)
                                removed += 1
                        except OSError:
                            pass
        # crashed writers' in-doubt intent markers age out under the same
        # grace: compose never reads them, so a swept one only turns a
        # parked writer's commit into a clean write-write conflict
        removed += self.manifest.sweep_intents(grace_s)
        return removed

    def replace_contents(self, table: str, enc: dict, valids: dict,
                         raw_strs: dict | None = None) -> None:
        """Autocommit full-table replacement (see stage_replace)."""
        tx = self.manifest.begin()
        old_files = self.stage_replace(tx, table, enc, valids, raw_strs)
        self.manifest.commit_tables_tx(tx, [table])
        self.gc_files(table, old_files)
        self.maybe_fold_manifest()

    # ---- deletion bitmaps (the appendonly visimap analog) ---------------
    # DELETE/UPDATE never rewrite data files: they publish a per-segment
    # deletion bitmap ('@del.<fileno>.ggb' — '@' can never collide with a
    # column identifier) recorded in BOTH tmeta["delmask"] (lookup) and
    # segfiles (replication/archive/orphan-sweep walk segfiles, so the
    # bitmap rides every existing durability path). The bitmap covers the
    # first len(mask) rows of the segment in manifest file order; rows
    # appended later are implicitly live. Full rewrites (stage_replace /
    # rewrite_table / VACUUM compaction) drop it.
    # Reference: src/backend/access/appendonly/appendonly_visimap.c:1.

    def delmask_keep(self, table: str, seg: int,
                     snapshot: dict | None = None):
        """-> bool[nrows] keep mask (True = live) or None when the segment
        has no deletions. Manifest-version cached."""
        snap = snapshot or self.manifest.snapshot()
        version = snap.get("version", 0)
        key = (table, seg, version)
        hit = self._delmask_cache.get(key, MISS)
        if hit is not MISS:
            return hit
        tmeta = snap["tables"].get(table, {})
        rel = tmeta.get("delmask", {}).get(str(seg))
        keep = None
        if rel is not None:
            deleted = self.read_file(table, rel)
            nrows = tmeta.get("nrows", {}).get(str(seg), 0)
            keep = np.ones(nrows, dtype=bool)
            keep[: len(deleted)] = ~deleted.astype(bool)
            if keep.all():
                keep = None
        self._delmask_cache.put(key, keep, version=version)
        return keep

    def live_rowcounts(self, table: str, snapshot: dict | None = None) -> list[int]:
        """Per-segment VISIBLE row counts (manifest nrows minus deletion
        bitmap) — what read_segment will actually return."""
        snap = snapshot or self.manifest.snapshot()
        out = []
        for seg, n in enumerate(self.segment_rowcounts(table, snap)):
            keep = self.delmask_keep(table, seg, snap)
            out.append(int(keep.sum()) if keep is not None else n)
        return out

    def stage_delmask(self, tx: dict, table: str,
                      masks: dict[int, np.ndarray]) -> list:
        """Stage new deletion bitmaps (1 = deleted, full manifest length)
        into a manifest tx; returns the REPLACED bitmap rels for GC."""
        schema = self.catalog.get(table)
        tmeta = tx["tables"].setdefault(table, {"segfiles": {}, "nrows": {}})
        dm = tmeta.setdefault("delmask", {})
        compresstype = schema.options.get("compresstype", "zlib")
        complevel = int(schema.options.get("compresslevel", 1))
        fileno = uuid.uuid4().hex[:12]
        old_rels = []
        for seg, mask in masks.items():
            mask = np.asarray(mask, dtype=np.uint8)
            segdir = os.path.join(self.data_root(seg), table, f"seg{seg}")
            os.makedirs(segdir, exist_ok=True)
            fn = f"@del.{fileno}.ggb"
            write_column_file(os.path.join(segdir, fn), mask,
                              compresstype, complevel)
            rel = os.path.join(f"seg{seg}", fn)
            old = dm.get(str(seg))
            files = tmeta["segfiles"].setdefault(str(seg), [])
            if old is not None:
                old_rels.append(old)
                if old in files:
                    files.remove(old)
            files.append(rel)
            dm[str(seg)] = rel
        return old_rels

    def set_delmask(self, table: str, masks: dict[int, np.ndarray]) -> None:
        """Autocommit bitmap publish (one per-table delta commit).

        Retried (bounded) when fenced off by a concurrent write-intent
        merge: re-staging the SAME bitmaps against the fresh snapshot is
        correct by the visimap prefix contract — each mask covers the
        first len(mask) rows in manifest order, and rows an intent
        appended after this DELETE's snapshot are implicitly live. Other
        conflicts (a concurrent full-state commit changed row visibility)
        still surface: retrying those would replay stale visibility."""
        last = None
        for attempt in range(10):
            tx = self.manifest.begin()
            with _trace.span("delmask", cat="write", table=table,
                             phase="write"):
                old = self.stage_delmask(tx, table, masks)
            try:
                with _trace.span("commit", cat="write", table=table):
                    self.manifest.commit_tables_tx(tx, [table])
            except IntentConflict as e:
                last = e
                # the freshly staged bitmap files never became visible
                staged = [tx["tables"][table]["delmask"][str(s)]
                          for s in masks]
                self.gc_files(table, staged, defer=False)
                counters.inc("manifest_cas_retry_total")
                _time.sleep(0.01 * (attempt + 1))
                continue
            self.gc_files(table, old)
            self.maybe_fold_manifest()
            return
        raise RuntimeError(
            f"write-write conflict persisted after retries: {last}")

    def insert_encoded(self, table: str, enc: dict, valids: dict,
                       raw_strs: dict | None = None,
                       tx: dict | None = None) -> int:
        """Append rows already in STORAGE representation (TEXT = dictionary
        codes, decimals scaled, dates as days) — the UPDATE republish-free
        path: the new row versions come straight off a raw-mode scan."""
        schema = self.catalog.get(table)
        for c in schema.columns:
            v = (valids or {}).get(c.name)
            if not c.nullable and v is not None and not np.all(v):
                raise ValueError(
                    f'null value in column "{c.name}" violates not-null '
                    "constraint")
        return self._append_encoded(table, schema, enc, dict(valids or {}),
                                    raw_strs or {}, tx, {})

    def reconcile_widths(self) -> None:
        """Crash recovery for expansion: the manifest's per-table width is
        the commit record; if the catalog copy lags (crash between manifest
        commit and catalog save in rewrite_table), adopt the manifest's."""
        from greengage_tpu.catalog.schema import DistPolicy

        snap = self.manifest.snapshot()
        changed = False
        for name, tmeta in snap["tables"].items():
            width = tmeta.get("numsegments")
            if width is None or name not in self.catalog:
                continue
            schema = self.catalog.get(name)
            if schema.policy.numsegments != width:
                schema.policy = DistPolicy(schema.policy.kind, schema.policy.keys, width)
                changed = True
        if changed:
            self.catalog._save()

    def _write_segfiles(self, schema, table, tmeta, enc, valids, seg_rows,
                        fileno, raw_strs=None) -> list:
        """Write per-segment column files, record them in ``tmeta``, and
        return the records for optimistic-retry re-merge."""
        compresstype = schema.options.get("compresstype", "zlib")
        complevel = int(schema.options.get("compresslevel", 1))
        raw_strs = raw_strs or {}
        records: list = []
        for s, idx in enumerate(seg_rows):
            if len(idx) == 0:
                continue
            # the STORAGE table name, not schema.name: partition children
            # ("t#part") share the parent's schema but own their directory
            segdir = os.path.join(self.data_root(s), table, f"seg{s}")
            os.makedirs(segdir, exist_ok=True)
            files = tmeta["segfiles"].setdefault(str(s), [])
            files_before = len(files)
            for c in schema.columns:
                if c.name in raw_strs:
                    # raw TEXT: utf-8 byte blob + row offsets (varlena-style
                    # datum stream, aocsam.c:661)
                    vmask = valids.get(c.name)
                    vals = raw_strs[c.name][idx]
                    ok = np.asarray(vmask, bool)[idx] if vmask is not None else None
                    bts = [b"" if (ok is not None and not ok[i]) or v is None
                           else str(v).encode("utf-8")
                           for i, v in enumerate(vals)]
                    lens = np.fromiter((len(b) for b in bts), np.int64, len(bts))
                    offs = np.concatenate(
                        [np.zeros(1, np.int64), np.cumsum(lens)])
                    blob = np.frombuffer(b"".join(bts), np.uint8).copy()
                    ofn = f"{c.name}.{fileno}.rawoffs.ggb"
                    bfn = f"{c.name}.{fileno}.rawbytes.ggb"
                    write_column_file(os.path.join(segdir, ofn), offs,
                                      compresstype, complevel)
                    write_column_file(os.path.join(segdir, bfn), blob,
                                      compresstype, complevel)
                    files.append(os.path.join(f"seg{s}", ofn))
                    files.append(os.path.join(f"seg{s}", bfn))
                else:
                    fn = f"{c.name}.{fileno}.ggb"
                    write_column_file(os.path.join(segdir, fn), enc[c.name][idx],
                                      compresstype, complevel)
                    files.append(os.path.join(f"seg{s}", fn))
                v = valids.get(c.name)
                if v is not None:
                    vfn = f"{c.name}.{fileno}.valid.ggb"
                    write_column_file(os.path.join(segdir, vfn),
                                      np.asarray(v, dtype=np.uint8)[idx],
                                      compresstype, complevel)
                    files.append(os.path.join(f"seg{s}", vfn))
            tmeta["nrows"][str(s)] = tmeta["nrows"].get(str(s), 0) + int(len(idx))
            records.append((s, list(files[files_before:]), int(len(idx))))
        return records

    def has_nulls(self, table: str, col: str, snapshot: dict | None = None) -> bool:
        """True if any committed segfile of this column has a validity file
        (compile-time schema for the executor's input staging)."""
        if col.startswith(("@hp:", "@rp:", "@rw:")):
            col = col.split(":", 2)[1]   # predicate nullability = column's
        elif col.startswith("@rc:") or col.startswith("@rl:"):
            col = col[4:]                # code/length nullability = column's
        snap = snapshot or self.manifest.snapshot()
        schema = self.catalog.get(table) if table in self.catalog else None
        names = (schema.storage_tables()
                 if schema is not None and schema.name == table else [table])
        marker = f"{col}."
        for name in names:
            tmeta = snap["tables"].get(name, {"segfiles": {}})
            for files in tmeta["segfiles"].values():
                for rel in files:
                    fn = os.path.basename(rel)
                    if fn.startswith(marker) and fn.endswith(".valid.ggb"):
                        return True
        return False

    def column_bounds(self, table: str, col: str,
                      snapshot: dict | None = None):
        """Exact global [min, max] over every committed block of a stored
        column, from block zone maps (blockfile.write_column_file) — the
        sound key-packing bounds the distributed ordered-window path needs
        (values at NULL positions are fillers inside the same zones, so
        the result is a superset of live values; never an underestimate).
        None when any block lacks a zone (TEXT/all-NaN) or no rows."""
        snap = snapshot or self.manifest.snapshot()
        schema = self.catalog.get(table) if table in self.catalog else None
        names = (schema.storage_tables()
                 if schema is not None and schema.name == table else [table])
        lo = hi = None
        for name in names:
            tmeta = snap["tables"].get(name, {"segfiles": {}})
            for seg, files in tmeta["segfiles"].items():
                for rel in files:
                    fn = os.path.basename(rel)
                    parts = fn.split(".")
                    if (len(parts) != 3 or not fn.endswith(".ggb")
                            or parts[0] != col):
                        continue
                    for b in self.read_footer_checked(name, rel)["blocks"]:
                        if not b["nrows"]:
                            continue
                        if "zmin" not in b:
                            return None
                        lo = b["zmin"] if lo is None else min(lo, b["zmin"])
                        hi = b["zmax"] if hi is None else max(hi, b["zmax"])
        return None if lo is None else (lo, hi)

    def segment_rowcounts(self, table: str, snapshot: dict | None = None) -> list[int]:
        schema = self.catalog.get(table)
        snap = snapshot or self.manifest.snapshot()
        names = (schema.storage_tables()
                 if schema.name == table else [table])
        out = [0] * schema.policy.numsegments
        for name in names:
            tmeta = snap["tables"].get(name, {"nrows": {}})
            for s in range(schema.policy.numsegments):
                out[s] += tmeta["nrows"].get(str(s), 0)
        return out
