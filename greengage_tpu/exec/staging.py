"""Pipelined host staging: the bufmgr/smgr read-ahead layer of the scan.

The reference keeps scans fed by overlapping disk I/O, decode, and tuple
delivery (heap/aocs_beginscan over the buffer manager); our reproduction
staged every cold scan through one serial Python loop — read, CRC+zlib
decode, pad, concatenate, transfer, per segment and per column. This
module supplies the three pipeline pieces the executor composes:

  - a shared READ POOL (``pool(settings)``): every (table, segment,
    column) unit of a statement's input spec reads+decodes concurrently
    (``column_units`` says which columns travel together). The native
    codec, zlib, and file I/O all release the GIL, so the pool gets real
    parallelism; TableStore's caches and read-path self-heal are
    thread-safe under it. ``scan_threads`` sizes it (0 = auto).
  - IN-PLACE staging buffers (``assemble``): one preallocated
    ``[nseg * cap]`` host array per staged column that per-segment decoded
    arrays are written into directly — replacing the pad-then-concatenate
    pair of copies (and skipping even that one copy when a single
    segment's array already fills the buffer exactly).
  - a spill-pass PREFETCHER (``PassPrefetcher``): while pass k's jitted
    program runs, a background thread warms pass k+1's cold block reads
    into the block cache (JAX async dispatch leaves the host idle there).
"""

from __future__ import annotations

import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from greengage_tpu.storage.blockcache import MISS  # noqa: F401 — one
# 'absent' sentinel shared with the store's caches (re-exported for the
# executor), so a lookup can never compare against the wrong module's


def scan_thread_count(settings) -> int:
    n = int(getattr(settings, "scan_threads", 0) or 0)
    if n <= 0:
        n = min(8, os.cpu_count() or 1)
    return max(n, 1)


class _InlineFuture:
    __slots__ = ("_value", "_err")

    def __init__(self, fn, args):
        try:
            self._value, self._err = fn(*args), None
        except BaseException as e:   # re-raised at result(), like a Future
            self._value, self._err = None, e

    def result(self):
        if self._err is not None:
            raise self._err
        return self._value


class _InlinePool:
    """scan_threads = 1: run units eagerly on the calling thread (no pool
    handoff overhead, deterministic single-threaded debugging)."""

    def submit(self, fn, *args):
        return _InlineFuture(fn, args)


_pool: ThreadPoolExecutor | None = None
_pool_size = 0
_pool_mu = threading.Lock()
_inline = _InlinePool()


def pool(settings):
    """The process-wide staging pool, resized when scan_threads changes.
    The displaced pool is NOT shut down here — a concurrent statement may
    still be submitting to it; dropping the reference lets it drain its
    in-flight units and be reclaimed once every holder finishes
    (ThreadPoolExecutor workers exit when their executor is collected)."""
    n = scan_thread_count(settings)
    if n <= 1:
        return _inline
    global _pool, _pool_size
    with _pool_mu:
        if _pool is None or _pool_size != n:
            _pool = ThreadPoolExecutor(max_workers=n,
                                       thread_name_prefix="gg-stage")
            _pool_size = n
        return _pool


def pool_queue_depth() -> int:
    """Read units waiting for a staging-pool thread right now — the
    `gg metrics` staging_pool_queue_depth gauge (a persistent backlog
    here means scan_threads is undersized for the workload)."""
    with _pool_mu:   # ps/metrics-frame rate; never on the read path
        p = _pool
    if p is None:
        return 0
    try:
        return p._work_queue.qsize()
    except (AttributeError, NotImplementedError):
        return 0


def column_units(storage_cols) -> list[list[str]]:
    """A table's storage columns split into read units, in the order the
    executor assembles them: one column a unit, except that the virtual
    columns of one raw TEXT column ('@rp:c:w', '@rw:c:w', '@rl:c', '@rc:c',
    '@hp:c:...') and the column itself share a unit, since they are cut
    from one derived structure (TableStore.raw_chunk / raw_prefix) that
    two units would build twice. No storage column (count(*)) still makes
    one empty unit: it carries the segment's row count."""
    units: dict[str, list[str]] = {}
    for c in storage_cols:
        source = c.split(":", 2)[1] if c.startswith("@") else c
        units.setdefault(source, []).append(c)
    return list(units.values()) or [[]]


def fill_buffer(nseg: int, cap: int, dtype, parts, fill=0) -> np.ndarray:
    """One staging buffer for one column: ``parts`` yields (seg, array)
    with len(array) <= cap; every other position holds ``fill``. When a
    single segment's array already IS the full buffer (nseg == 1,
    len == cap, right dtype) it stages as-is — the no-copy fast path the
    old pad-then-concatenate could never take."""
    parts = list(parts)
    if nseg == 1 and len(parts) == 1:
        arr = parts[0][1]
        if len(arr) == cap and arr.dtype == dtype:
            return np.ascontiguousarray(arr)
    # np.empty + explicit padding of only the UNFILLED tails: the data
    # slices are about to be overwritten anyway, so a full-buffer memset
    # (np.full) would touch every byte twice
    out = np.empty(nseg * cap, dtype=dtype)
    filled = {}
    for seg, arr in parts:
        n = len(arr)
        if n:
            out[seg * cap: seg * cap + n] = arr
        filled[seg] = max(filled.get(seg, 0), n)
    for seg in range(nseg):
        n = filled.get(seg, 0)
        if n < cap:
            out[seg * cap + n: (seg + 1) * cap] = fill
    return out


class PassPrefetcher:
    """Warm the next spill pass's block reads while the current pass's
    device program runs. All passes share the same committed files (row
    ranges slice AFTER the read), so warming is a cheap cache probe when
    the budget held and a real read-ahead when eviction emptied it.
    Prefetch must never fail or outlive the query: errors are swallowed,
    close() joins."""

    def __init__(self, executor, input_spec, snapshot):
        from greengage_tpu.runtime import interrupt

        self.executor = executor
        self.snapshot = snapshot
        # the spawning statement's interrupt context: _warm polls it
        # between units so a cancelled statement's prefetcher dies at the
        # next unit boundary instead of reading the whole next pass (and
        # close() below never outwaits a cancelled warm loop)
        self._ctx = interrupt.REGISTRY.current()
        # (table, plain storage columns) units; aux/virtual tables skipped
        self.units = []
        for table, cols, _cap, _direct, _prune, child_parts, _dyn \
                in input_spec:
            if table.startswith("@"):
                continue
            plain = [c for c in cols if not c.startswith("@")]
            for t in (child_parts if child_parts is not None else (table,)):
                self.units.append((t, plain))
        self.enabled = bool(getattr(executor.settings, "spill_prefetch",
                                    True)) and bool(self.units)
        self._thread: threading.Thread | None = None

    def _warm(self) -> None:
        try:
            store = self.executor.store
            reg = store.blockcache
            for table, cols in self.units:
                for seg in self.executor._local_segments():
                    if self._ctx is not None and self._ctx.cancelled:
                        return   # statement is dying: stop warming for it
                    # budget guard: a table bigger than the cache would
                    # only evict its own (and the running pass's) blocks —
                    # stop warming once the registry nears its limit
                    # instead of thrashing it
                    if reg.total_bytes >= 0.9 * reg.limit_bytes():
                        return
                    store.read_segment(table, seg, cols, self.snapshot)
        except Exception:
            pass   # a failed prefetch is only a lost warm-up

    def kick(self) -> None:
        if not self.enabled or (self._thread is not None
                                and self._thread.is_alive()):
            return
        self._thread = threading.Thread(target=self._warm, daemon=True,
                                        name="gg-spill-prefetch")
        self._thread.start()

    def close(self) -> None:
        """Join the warm thread, bounded. Runs on the statement thread —
        poll the statement's cancellation so a dying statement stops
        waiting after the warm loop's current unit instead of sitting
        out the full drain (lint_interrupts thread-join coverage)."""
        t = self._thread
        if t is None:
            return
        deadline = time.monotonic() + 60.0
        while t.is_alive() and time.monotonic() < deadline:
            if self._ctx is not None and self._ctx.cancelled:
                # _warm observes the same flag at its next unit boundary
                # and exits; one bounded join covers that last unit
                t.join(timeout=5.0)
                break
            t.join(timeout=0.25)
        self._thread = None
