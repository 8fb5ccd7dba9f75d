"""Host staging: the bufmgr/smgr read-ahead layer of the scan, and step
three of the executor's attempt (docs/ARCHITECTURE.md).

``Stager.stage`` is the one way a program's inputs reach the mesh: it takes
the per-dispatch context as arguments (parameter vector, row ranges, aux
tables) and returns the staged arrays with what staging found (prune
statistics, I/O counters, where the time went). The stage cache, the
two-table lookahead, the staged dtype and the in-place protocol live here
and nowhere else. The pieces:

  - a shared READ POOL (``pool(settings)``): every (table, segment,
    column) unit of a statement's input spec reads+decodes concurrently
    (``column_units`` says which columns travel together). The native
    codec, zlib, and file I/O all release the GIL, so the pool gets real
    parallelism; TableStore's caches and read-path self-heal are
    thread-safe under it. ``scan_threads`` sizes it (0 = auto).
  - IN-PLACE staging buffers: one preallocated ``[nseg * cap]`` host array
    per staged column, whose per-segment slots the read units fill on
    their own threads (the protocol is written down at
    ``Stager._submit``); what could not land there is copied in on the
    statement thread (``fill_buffer``, ``_fill_column``).
  - a spill-pass PREFETCHER (``PassPrefetcher``): while pass k's jitted
    program runs, a background thread warms pass k+1's cold block reads
    into the block cache (JAX async dispatch leaves the host idle there).
"""

from __future__ import annotations

import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import numpy as np

import jax

from greengage_tpu import expr as E
from greengage_tpu import types as T
from greengage_tpu.exec.compile import VALID_PREFIX, CompileResult
from greengage_tpu.parallel.mesh import replicated_sharding, seg_sharding
from greengage_tpu.runtime import interrupt
from greengage_tpu.runtime import memaccount
from greengage_tpu.runtime import trace as _trace
from greengage_tpu.runtime.faultinject import faults
from greengage_tpu.runtime.logger import counters
from greengage_tpu.storage import blockfile
from greengage_tpu.storage.blockcache import MISS

# per-statement I/O accounting reported in Result.stats["scan_io"] and the
# EXPLAIN ANALYZE host-data-path lines (counter deltas, never wall clocks,
# so tests can assert them deterministically)
SCAN_COUNTERS = ("scan_files_read", "scan_bytes_decoded", "scan_cache_hit",
                 "scan_cache_miss", "scan_cache_evict")
# how often the in-place protocol (Stager._submit) engages: the read units
# of `read` tables; those whose every column landed in its staging slot on
# the thread that ran the unit; those that were offered their slots and
# left the in-place path because the table has been written to (several
# data files a column; a deletion bitmap, which counts where both hold);
# and the `read` tables whose pushed zone-map predicates a bitmap switched
# off. Counters, Result.stats keys and (less the prefix) arguments of the
# `stage:<table>` span alike
STAGE_COUNTERS = ("stage_units", "stage_units_in_slot",
                  "stage_units_copy_files", "stage_units_copy_delmask",
                  "zone_prune_skipped_delmask")


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


def stage_dtype(schema, c) -> np.dtype:
    """The dtype a column STAGES as (may differ from storage)."""
    if c.startswith("@hp:"):
        return np.dtype(bool)         # host-evaluated predicate col
    if c.startswith("@rc:"):
        return np.dtype(np.int32)     # transient raw-dict codes
    if c.startswith(("@rp:", "@rw:")):
        return np.dtype(np.int64)     # packed raw prefix word
    if c.startswith("@rl:"):
        return np.dtype(np.int32)     # raw byte length
    col_s = schema.column(c)
    # raw TEXT stages int64 row surrogates, not the int32 dict-code
    # dtype (segment bits live above 40)
    return (np.dtype(np.int64)
            if col_s.type.kind == T.Kind.TEXT and col_s.encoding == "raw"
            else col_s.type.np_dtype)


def resolve_prune(prune, pvec):
    """Substitute hoisted-parameter operands in pushed zone-map prune
    predicates with the statement's CURRENT values (planner
    _param_value / sql/paramize.resolve_param_value): pruning stays
    value-exact while the compiled program stays value-generic."""
    if not prune or not any(isinstance(v, E.Expr) for _, _, v in prune):
        return prune
    from greengage_tpu.sql.paramize import resolve_param_value

    out = []
    for col, op, v in prune:
        if isinstance(v, E.Expr):
            if pvec is None:
                continue   # no vector bound: skip only this predicate
            val = resolve_param_value(v, pvec)
            v = (float(val) if isinstance(val, (float, np.floating))
                 else int(val))
        out.append((col, op, v))
    return tuple(out)


def _pad(arr: np.ndarray, cap: int, fill=0) -> np.ndarray:
    if len(arr) == cap:
        return arr
    out = np.full(cap, fill, dtype=arr.dtype)
    out[: len(arr)] = arr
    return out


def _land(st, u, per_seg) -> list:
    """Block until unit ``u`` of every staged segment of the read table
    ``st`` is done and its columns and masks are in ``per_seg``;
    -> [(segment, nrows, prune stats)]. A landed unit's futures are let
    go (``futs[u] = None`` says it has landed) and those that came in
    their slots are counted. A cancellation point a column; inside the
    wait the units poll the statement's context themselves."""
    interrupt.check_interrupts()
    futs = st["futs"]
    row, futs[u] = futs[u], None
    out = []
    for seg, fut in enumerate(row):
        if fut is None:
            continue
        c, v, n, pstat, in_slot, io = fut.result()
        per_seg[seg][0].update(c)
        per_seg[seg][1].update(v)
        st["in_slot"] += in_slot != "no"
        if io.off_slot:
            st["copy_" + io.off_slot] += 1
        st["prune_skipped"] |= io.prune_skipped
        out.append((seg, n, pstat))
    return out


@dataclass
class Staged:
    """What one ``Stager.stage`` call hands back."""
    inputs: list = field(default_factory=list)   # the program's arguments
    sid: int = -1             # the `stage` span, for the caller's annotate
    stage_ms: float = 0.0
    scan_io: dict = field(default_factory=dict)  # SCAN_COUNTERS deltas
    units: dict = field(                         # STAGE_COUNTERS, this call's
        default_factory=lambda: dict.fromkeys(STAGE_COUNTERS, 0))
    split: dict = field(default_factory=dict)    # stage_*_ms, read_*: spans
    zone_prune: dict = field(default_factory=dict)   # table: (kept, blocks)
    # runtime PartitionSelector results: child partitions kept / total
    dynamic_prune: dict = field(default_factory=dict)


class _Call:
    """One ``stage`` call's constants, as its read units need them."""

    def __init__(self, stager, snapshot):
        self.snapshot = snapshot
        self.local_segs = stager.local_segments()
        self.pool = pool(stager.settings)
        # the statement's interrupt context, captured HERE because read
        # units run on pool threads (interrupt.current() is thread-keyed):
        # each unit checks the flag before its read, so a multi-second
        # cold stage cancels mid-flight instead of at the next boundary
        self.ctx = interrupt.REGISTRY.current()
        # the statement's memory account travels the same way: pool
        # threads bind to it for the unit's duration, so block-cache
        # inserts inside the read attribute to the right owner tree
        self.acct = memaccount.ACCOUNTS.current()
        # and so does its trace: the registry is keyed by thread, so a
        # unit records its `read:<table>` span through this handle, under
        # the `stage` span the call runs inside
        self.trace = _trace.TRACES.current()
        self.sid = self.trace.top() if self.trace is not None else None


class Stager:
    """Stages a compiled program's inputs; one per Executor, shared by
    statement threads and the batch-serving stager (every call's context
    is its arguments and locals)."""

    def __init__(self, store, mesh, nseg: int, settings, multihost):
        self.store = store   # and its catalog: Database.refresh rebinds it
        self.mesh = mesh
        self.nseg = nseg
        self.settings = settings
        self.multihost = multihost    # parallel.multihost.MultihostRuntime
        # staged device inputs live in the store's byte-accounted LRU
        # registry (storage/blockcache.py): bounded within a manifest
        # version, evicted by recency against scan_cache_limit_mb
        self.stage_cache = store.blockcache.cache("stage")
        self._dyn_mu = threading.Lock()
        self._dyn_prune_cache: dict = {}

    def local_segments(self) -> set:
        if self.multihost is None:
            return set(range(self.nseg))
        if not self.multihost.local_segments:
            from greengage_tpu.parallel.multihost import local_segment_positions

            self.multihost.local_segments = local_segment_positions()
        return set(s for s in self.multihost.local_segments if s < self.nseg)

    def shapes(self, comp: CompileResult) -> list:
        """What ``stage`` would hand a classic program over base tables,
        as shapes: admission compiles from these before anything is
        staged (Executor._measure_unstaged)."""
        shard = seg_sharding(self.mesh)
        out = []
        for table, cols, cap, *_ in comp.input_spec:
            schema = self.store.catalog.get(table)
            for c in cols:
                dt = (np.dtype(bool) if c.startswith(VALID_PREFIX)
                      else stage_dtype(schema, c))
                out.append(jax.ShapeDtypeStruct((self.nseg * cap,), dt,
                                                sharding=shard))
            out.append(jax.ShapeDtypeStruct((self.nseg * cap,), bool,
                                            sharding=shard))
        return out + [
            jax.ShapeDtypeStruct((1,), dt,
                                 sharding=replicated_sharding(self.mesh))
            for dt in comp.param_dtypes]

    def stage(self, comp: CompileResult, snapshot, pvec=None, params=(),
              row_ranges=None, aux_tables=None) -> Staged:
        """Pipelined input staging (docs/PERF.md): hand the staging pool
        one read+decode unit per (table, segment, column), column-major,
        then consume them in spec order column by column: wait for a
        column's units, fill its preallocated [nseg*cap] buffer and issue
        its device transfer while the later columns (and the next
        table's) still decode on the pool — and, with JAX async dispatch,
        under the device program itself.

        ``pvec`` resolves parameter-valued prune predicates (None drops
        them); ``params`` are the host arrays of the program's parameter
        slots, put after the tables; ``row_ranges`` {table: (lo, hi)} and
        ``aux_tables`` {name: (cols, valids)} are a spill pass's."""
        out = Staged()
        io0 = {k: counters.get(k) for k in SCAN_COUNTERS}
        t0 = time.monotonic()
        with _trace.span("stage", cat="stage",
                         tables=len(comp.input_spec)) as out.sid:
            aux = aux_tables or {}
            plans = self._plan(comp, snapshot, pvec, row_ranges or {}, aux,
                               out)
            out.inputs = self._assemble(plans, snapshot, aux, out)
            # parameter slots, replicated (multi-host: every process binds
            # the same values from the same statement text, keeping the
            # lockstep invariant). A `put` like the tables': on a TPU this
            # small transfer queues behind the table transfers still in
            # flight, so it is where the statement thread waits for them
            # (PERF.md section 5)
            rep = replicated_sharding(self.mesh)
            out.inputs += [self._put(host, rep) for host in params]
        out.stage_ms = (time.monotonic() - t0) * 1e3
        out.scan_io = {k: counters.get(k) - io0[k] for k in SCAN_COUNTERS}
        _trace.annotate(out.sid, **out.scan_io)
        u = out.units
        if u["stage_units"]:
            counters.inc("stage_units", u["stage_units"])
            counters.inc("stage_units_in_slot", u["stage_units_in_slot"])
            counters.inc("stage_units_copy_files", u["stage_units_copy_files"])
            counters.inc("stage_units_copy_delmask",
                         u["stage_units_copy_delmask"])
            counters.inc("zone_prune_skipped_delmask",
                         u["zone_prune_skipped_delmask"])
        out.split = _stage_split(out.sid)
        return out

    def _plan(self, comp, snapshot, pvec, ranges, aux, out: Staged) -> list:
        """Plan phase: resolve per-table staging decisions ->
        [kind, table, cols, cap, key, prune, payload] in spec order."""
        # evict staged arrays + store cache entries from older manifest
        # versions (any write bumps the version, so stale device copies are
        # unreachable and only waste HBM — the dispatcher's
        # CdbComponentDatabases invalidation analog)
        version = snapshot.get("version", 0)
        dropped: dict = {}
        self.store.blockcache.invalidate_versions(version, dropped)
        # staged device inputs a write made unreachable: what the scans
        # after a refresh stage again
        counters.inc("stage_cache_dropped", dropped.get("stage", 0))
        plans = []
        claimed = set()
        for table, cols, cap, direct, prune, child_parts, dyn in comp.input_spec:
            # hoisted parameters resolve HERE — staging decisions (zone
            # maps, block indexes, dynamic partition pruning) see the
            # statement's current values, and the stage-cache key below
            # carries the resolved predicate so different values never
            # share a pruned staging
            prune = resolve_prune(prune, pvec)
            if dyn is not None and isinstance(dyn, tuple):
                dyn = (dyn[0], resolve_prune(dyn[1], pvec) or (), dyn[2])
            if table in aux:
                plans.append(("aux", table, cols, cap, None, None, None))
                continue
            if child_parts is not None and dyn is not None:
                # join-driven runtime partition elimination: evaluate the
                # build side's pushed filter on the host, keep only the
                # child partitions a surviving key value can land in
                # (deterministic per manifest version — multihost
                # processes compute the same set from shared storage)
                child_parts = self._dyn_pruned_parts(
                    table, child_parts, dyn, snapshot, out.dynamic_prune)
            key = (table, tuple(cols), cap, version, direct, prune,
                   child_parts, ranges.get(table))
            if table not in ranges:
                hit = self.stage_cache.get(key, MISS)
                if hit is not MISS:
                    plans.append(("hit", table, cols, cap, key, prune, hit))
                    continue
            if key in claimed:
                # same scan twice in ONE input spec (self-join): reuse the
                # first occurrence's staged arrays instead of reading and
                # transferring the identical inputs again
                plans.append(("dup", table, cols, cap, key, prune, None))
                continue
            claimed.add(key)   # first occurrence claims the key
            plans.append(("read", table, cols, cap, key, prune, {
                "units": column_units(
                    c for c in cols if not c.startswith(VALID_PREFIX)),
                "child_parts": child_parts, "direct": direct,
                "rng": ranges.get(table), "futs": None, "buffers": None}))
        return plans

    def _submit(self, p, call: _Call) -> None:
        """Hand one read table's units to the pool (once).

        THE IN-PLACE PROTOCOL, all of it. The rule: a read unit that is
        offered its staging slot fills it, on the thread that runs it;
        the statement thread builds the ``present`` mask and puts (the
        buffers come zeroed, so the tails past a segment's rows need no
        padding). (1) This function alone decides whether a table's units
        ARE offered their slots: a scan that fills every segment's slot
        from its start — no row range, no child partitions, no direct
        dispatch, every segment local — gets its [nseg*cap] buffers
        here, and each unit is offered its segment's slots as ``dest``;
        ranged/partitioned scans slice or concatenate after the read and
        keep the copy path, and so do scans that fill only SOME segments
        (a cached view of a partially-used buffer would pin far more
        memory than its byte accounting). (2) ``TableStore.read_segment``
        takes the offer column by column, whatever zone-map predicate is
        pushed: a column of one data file lands in its slot through
        ``read_file(out=)``, which decodes the (kept) blocks into the
        slot's prefix on a block-cache miss — the cached value is then a
        view of the slot, charged the whole slot it pins — and copies
        the cached array there on a hit, and either way returns a VIEW
        of the slot. What it observes in its input keeps an array of its
        own: several data files for the column, a deletion bitmap, a
        virtual '@' column, a slot of another dtype or too short; so do
        validity masks (a byte a row). (3) ``_read_unit`` says which
        happened (``in_slot`` on its `read:` span, STAGE_COUNTERS) and
        ``_fill_column`` learns it by identity (``arr.base is buf``) and
        copies only what is not already in place."""
        _, table, cols, cap, _key, prune, st = p
        if st["futs"] is not None:
            return
        buffers = None
        if st["rng"] is None and st["child_parts"] is None \
                and st["direct"] is None \
                and len(call.local_segs) == self.nseg:
            schema = self.store.catalog.get(table)
            # zeros, not empty: a buffer of this size is fresh zero pages
            # either way, and the slots' tails (cap is a power of two, a
            # tenth of a buffer at SF10) then need no padding at all
            buffers = {c: np.zeros(self.nseg * cap, stage_dtype(schema, c))
                       for unit in st["units"] for c in unit}
        # direct dispatch: only the owning segment's storage is
        # read/staged (cdbtargeteddispatch.c analog)
        segs = [seg for seg in range(self.nseg)
                if seg in call.local_segs
                and (st["direct"] is None or seg == st["direct"])]
        # column-major, in the order the assemble loop consumes the
        # columns: the first column's units of every segment are the
        # first to finish. Flat, from this thread: a unit never
        # submits to the pool it runs on.
        futs = []
        for unit in st["units"]:
            row = [None] * self.nseg
            for seg in segs:
                dest = ({c: buffers[c][seg * cap: (seg + 1) * cap]
                         for c in unit}
                        if buffers is not None else None)
                row[seg] = call.pool.submit(
                    self._read_unit, table, st["child_parts"], seg,
                    unit, call.snapshot, prune, st["rng"],
                    dest, call.ctx, call.acct, call.trace, call.sid)
            futs.append(row)
        st["buffers"] = buffers
        st["futs"] = futs
        st["read_units"] = len(futs) * len(segs)
        # of them (_land): landed in their slots; left the in-place path
        # over data files / a deletion bitmap; a bitmap switched pruning off
        st["in_slot"] = st["copy_files"] = st["copy_delmask"] = 0
        st["prune_skipped"] = False

    def _assemble(self, plans, snapshot, aux, out: Staged) -> list:
        """Assemble phase (spec order, deterministic): fill staging
        buffers in place and put each column on the mesh as soon as it
        completes. Read units go to the pool through a bounded LOOKAHEAD
        window (the table being assembled plus one ahead): later tables'
        reads overlap earlier tables' assembly and transfer WITHOUT
        holding every table's decoded columns in flight at once — peak
        host memory stays at ~two tables."""
        arrays = []
        shard = seg_sharding(self.mesh)
        version = snapshot.get("version", 0)
        call = _Call(self, snapshot)
        read_plans = [p for p in plans if p[0] == "read"]
        staged_local: dict = {}   # key -> (staged, pstats) THIS statement
        done_reads = 0
        for kind, table, cols, cap, key, prune, payload in plans:
            interrupt.check_interrupts()   # between per-table assemblies
            # one span per (table) staging unit — read+decode+assemble+
            # device-put for misses, a cache probe for hits; rows/bytes
            # land in the span args (the trace's data-movement accounting)
            with _trace.span("stage:" + table, cat="stage",
                             kind=kind) as _sp_t:
                if kind == "aux":
                    staged_aux = self._stage_aux(table, cols, cap,
                                                 aux[table], shard)
                    memaccount.charge(
                        "staging",
                        sum(int(getattr(a, "nbytes", 64))
                            for a in staged_aux), item=table)
                    arrays.extend(staged_aux)
                    continue
                if kind in ("hit", "dup"):
                    # a dup is eviction-immune within the statement: the
                    # first occurrence stored its result in staged_local
                    # whatever the cache budget did since
                    staged, pstats = (payload if kind == "hit"
                                      else staged_local[key])
                    arrays.extend(staged)
                    if pstats is not None:
                        out.zone_prune[table] = pstats
                    continue
                st = payload
                units = st["units"]
                # what has landed so far, a segment: [cols, valids, nrows]
                per_seg = [[{}, {}, 0] for _ in range(self.nseg)]
                # the statement thread's time in a read table is three
                # kinds of leaf span, exhaustively: `wait` (blocking on one
                # column's units; the first also hands this table's units
                # and the next's to the pool, and scan_threads = 1 runs
                # them inline there), then that column's `assemble` and
                # `put`, while the later columns still decode
                with _trace.span("wait", cat="stage"):
                    for j in range(done_reads, min(done_reads + 2,
                                                   len(read_plans))):
                        self._submit(read_plans[j], call)   # this + one ahead
                    futs, buffers = st["futs"], st["buffers"]
                    # every unit of a segment sees the same zone maps and
                    # row count: the first column's speak for the segment
                    kept = total_blocks = 0
                    for seg, n, pstat in _land(st, 0, per_seg):
                        per_seg[seg][2] = n
                        if pstat is not None:
                            kept += pstat[0]
                            total_blocks += pstat[1]
                if prune and total_blocks:
                    out.zone_prune[table] = (kept, total_blocks)
                unit_of = {c: u for u, unit in enumerate(units)
                           for c in unit}
                schema = self.store.catalog.get(table)
                staged = []
                for c in cols:
                    # a validity mask comes with the unit of its column
                    u = unit_of.get(c[len(VALID_PREFIX):]
                                    if c.startswith(VALID_PREFIX) else c)
                    if u is not None and futs[u] is not None:
                        with _trace.span("wait", cat="stage"):
                            _land(st, u, per_seg)
                    with _trace.span("assemble", cat="stage"):
                        host = self._fill_column(schema, c, cap, per_seg,
                                                 buffers)
                    staged.append(self._put(host, shard))
                with _trace.span("assemble", cat="stage"):
                    present = np.zeros(self.nseg * cap, dtype=bool)
                    for s, (_, _, n) in enumerate(per_seg):
                        present[s * cap: s * cap + n] = True
                staged.append(self._put(present, shard))
                staged_local[key] = (staged, out.zone_prune.get(table))
                nbytes = sum(int(getattr(a, "nbytes", 64)) for a in staged)
                memaccount.charge("staging", nbytes, item=table)
                _trace.annotate(_sp_t, rows=int(sum(n for _, _, n in per_seg)),
                                bytes=nbytes, segments=len(per_seg),
                                read_units=st["read_units"],
                                units_in_slot=st["in_slot"],
                                units_copy_files=st["copy_files"],
                                units_copy_delmask=st["copy_delmask"],
                                prune_skipped_delmask=st["prune_skipped"])
                for name, n in zip(STAGE_COUNTERS, (
                        st["read_units"], st["in_slot"], st["copy_files"],
                        st["copy_delmask"], int(st["prune_skipped"]))):
                    out.units[name] += n
                if st["rng"] is None:
                    self.stage_cache.put(
                        key, staged_local[key], nbytes=nbytes,
                        version=version)
                arrays.extend(staged)
                done_reads += 1
                # let go of the table's host copies HERE, as the tail of
                # its assembly: unmapping GBs of decoded blocks and
                # [nseg*cap] buffers costs this thread ~0.07 s a GB on the
                # chip's host (PERF.md section 5), which would otherwise
                # fall between the spans when stage returns — and until
                # then every table's copies stayed alive at once
                with _trace.span("assemble", cat="stage", release=True):
                    st["futs"] = st["buffers"] = None
                    per_seg = futs = buffers = host = present = None
        return arrays

    def _read_unit(self, table, child_parts, seg, storage_cols, snapshot,
                   prune, rng, dest=None, stmt_ctx=None, stmt_acct=None,
                   stmt_trace=None, parent_sid=None):
        """One pooled staging unit: one column of one segment, decoded
        (several where column_units keeps them together; + this
        thread's zone-prune stats). Runs concurrently with other units —
        the store's caches and read-path self-heal are thread-safe.
        ``dest`` carries this segment's staging-buffer slots; the last
        member of the result says whether every column landed there
        ("decode", or "copy" where a block-cache hit was copied in) or
        "no". ``stmt_ctx`` is the owning statement's
        interrupt context: each unit is a cancellation point, and the
        raise travels back to the statement thread via fut.result().
        ``stmt_acct`` binds this pool thread to the statement's memory
        account so block-cache inserts inside the read attribute right.
        ``stmt_trace`` is its trace: the unit records one `read:<table>`
        span there under ``parent_sid`` (the statement's `stage` span),
        carrying what THIS unit read and how long it spent in file reads,
        in CRC + decode and in copying hits into its slots
        (blockfile.ReadTally, bound to this thread)."""
        faults.check("cancel_in_staging", segment=seg)
        if stmt_ctx is not None:
            stmt_ctx.check()
        sid = (stmt_trace.begin("read:" + table, cat="stage",
                                parent=parent_sid, segment=seg,
                                column=",".join(storage_cols))
               if stmt_trace is not None else -1)
        in_slot = "no"
        with blockfile.tally() as io:
            try:
                with memaccount.ACCOUNTS.bind(stmt_acct):
                    c, v, n = self._read_segment_parts(
                        table, child_parts, seg, storage_cols, snapshot,
                        prune, dest=dest)
                if dest is not None and all(
                        getattr(c.get(k), "base", None) is slot.base
                        for k, slot in dest.items()):
                    in_slot = "copy" if io.slot_copies else "decode"
            finally:
                if sid >= 0:
                    stmt_trace.end(
                        sid, files=io.files, cache_hits=io.cache_hits,
                        bytes_read=io.bytes_read,
                        bytes_decoded=io.bytes_decoded,
                        io_ms=round(io.io_ns / 1e6, 3),
                        decode_ms=round(io.decode_ns / 1e6, 3),
                        copy_ms=round(io.copy_ns / 1e6, 3),
                        in_slot=in_slot,
                        **({"off_slot": io.off_slot} if io.off_slot else {}))
        if rng is not None:
            a, b = rng
            c = {k: arr[a:b] for k, arr in c.items()}
            v = {k: (arr[a:b] if arr is not None else None)
                 for k, arr in v.items()}
            n = max(min(n, b) - a, 0)
        return (c, v, n, (self.store.last_prune if prune else None), in_slot,
                io)

    def _fill_column(self, schema, c, cap, per_seg, buffers) -> np.ndarray:
        """One column's [nseg*cap] host buffer, zero past each segment's
        rows."""
        nseg = self.nseg
        if c.startswith(VALID_PREFIX):
            name = c[len(VALID_PREFIX):]
            return fill_buffer(
                nseg, cap, np.dtype(bool),
                ((s, vv[name] if vv.get(name) is not None
                  else np.ones(n, dtype=bool))
                 for s, (_, vv, n) in enumerate(per_seg)), False)
        dt = stage_dtype(schema, c)
        buf = buffers.get(c) if buffers is not None else None
        if buf is None:
            return fill_buffer(
                nseg, cap, dt,
                ((s, cc.get(c, np.zeros(0, dt)).astype(dt, copy=False))
                 for s, (cc, _, _) in enumerate(per_seg)), 0)
        # the buffer came zeroed (_submit): only rows are ever written
        for s, (cc, _, _) in enumerate(per_seg):
            arr = cc.get(c)
            if arr is not None and len(arr) \
                    and getattr(arr, "base", None) is not buf:
                buf[s * cap: s * cap + len(arr)] = arr
        return buf

    def _dyn_pruned_parts(self, table, child_parts, dyn, snapshot,
                          stats: dict) -> tuple:
        """-> child partitions surviving the build-side key-value probe
        (the execution-time PartitionSelector, nodePartitionSelector.c);
        (kept, total) goes into ``stats[table]``. Manifest-version cached;
        falls back to the full set on any irregularity (a missed prune is
        only a perf loss)."""
        ck = (table, child_parts, dyn, snapshot.get("version", 0))
        with self._dyn_mu:
            hit = self._dyn_prune_cache.get(ck)
        if hit is not None:
            stats[table] = (len(hit), len(child_parts))
            return hit
        dim_table, preds, key_col = dyn
        try:
            schema = self.store.catalog.get(table)
            dim_schema = self.store.catalog.get(dim_table)
            need = {key_col} | {c for c, _, _ in preds}
            from greengage_tpu.catalog.schema import PolicyKind

            segs = ([0] if dim_schema.policy.kind is PolicyKind.REPLICATED
                    else range(dim_schema.policy.numsegments))
            vals_parts = []
            for seg in segs:
                c, v, n = self.store.read_segment(
                    dim_table, seg, sorted(need), snapshot)
                m = np.ones(n, dtype=bool)
                for col, op, val in preds:
                    arr = c[col]
                    cv = v.get(col)
                    if cv is not None:
                        m &= np.asarray(cv, bool)
                    m &= {"=": arr == val, "<": arr < val, "<=": arr <= val,
                          ">": arr > val, ">=": arr >= val}[op]
                kv = v.get(key_col)
                if kv is not None:
                    m &= np.asarray(kv, bool)   # NULL keys never join
                vals_parts.append(c[key_col][m])
            values = np.unique(np.concatenate(vals_parts)) if vals_parts \
                else np.empty(0)
            keep_idx = set(schema.partitions_for_values(values))
            name_keep = {schema.partitions[i].storage_name(table)
                         for i in keep_idx}
            kept = tuple(p for p in child_parts if p in name_keep)
        except Exception:
            return child_parts   # never fail the query for a prune
        stats[table] = (len(kept), len(child_parts))
        with self._dyn_mu:
            if len(self._dyn_prune_cache) > 64:
                self._dyn_prune_cache.pop(next(iter(self._dyn_prune_cache)))
            self._dyn_prune_cache[ck] = kept
        return kept

    def _read_segment_parts(self, table, child_parts, seg, storage_cols,
                            snapshot, prune, dest=None):
        """Read one segment's rows — for a partitioned scan, the (pruned)
        child tables' rows concatenated in partition order. Zone-map
        pruning applies per child; block stats sum across children."""
        if child_parts is None:
            return self.store.read_segment(table, seg, storage_cols,
                                           snapshot, prune=prune, dest=dest)
        per = []
        kept = total = 0
        any_prune = False
        for child in child_parts:
            c, v, n = self.store.read_segment(child, seg, storage_cols,
                                              snapshot, prune=prune)
            per.append((c, v, n))
            st = self.store.last_prune
            if st is not None:
                any_prune = True
                kept += st[0]
                total += st[1]
        self.store.last_prune = (kept, total) if any_prune else None
        cols_out: dict = {}
        valids_out: dict = {}
        ntot = sum(n for _, _, n in per)
        for col in storage_cols:
            arrs = [c[col] for c, _, _ in per]
            cols_out[col] = (np.concatenate(arrs) if arrs
                             else np.empty(0, dtype=np.int64))
            if any(v.get(col) is not None for _, v, _ in per):
                valids_out[col] = np.concatenate([
                    (v[col] if v.get(col) is not None
                     else np.ones(n, dtype=bool))
                    for _, v, n in per])
        return cols_out, valids_out, ntot

    def _stage_aux(self, table, cols, cap, data, shard):
        """Stage an ephemeral host table ('@spill:' partial rows): rows
        split contiguously across segments, padded to cap."""
        aux_cols, aux_valids = data
        n = len(next(iter(aux_cols.values()))) if aux_cols else 0
        staged = []
        counts = [max(min(n, (s + 1) * cap) - s * cap, 0)
                  for s in range(self.nseg)]
        for c in cols:
            if c.startswith(VALID_PREFIX):
                name = c[len(VALID_PREFIX):]
                src = aux_valids.get(name)
                if src is None:
                    src = np.ones(n, dtype=bool)
                parts = [_pad(src[s * cap: s * cap + counts[s]], cap, False)
                         for s in range(self.nseg)]
            else:
                src = aux_cols[c]
                parts = [_pad(src[s * cap: s * cap + counts[s]], cap)
                         for s in range(self.nseg)]
            staged.append(self._put(np.concatenate(parts), shard))
        present = np.concatenate(
            [_pad(np.ones(cn, dtype=bool), cap, False) for cn in counts])
        staged.append(self._put(present, shard))
        return staged

    def _put(self, host: np.ndarray, shard):
        """Place a host array onto the mesh ([nseg*cap] by segment, or a
        replicated parameter slot). Multi-host: each
        process holds data only for its LOCAL segments (remote positions
        are zero padding) and contributes exactly its addressable shards
        via make_array_from_callback. The `put` span ends when that call
        returns, which need not be when the transfer has ended."""
        with _trace.span("put", cat="stage", bytes=int(host.nbytes)):
            if self.multihost is None:
                return jax.device_put(host, shard)

            def cb(index):
                sl = index[0]
                return host[sl.start or 0: sl.stop]

            return jax.make_array_from_callback(host.shape, shard, cb)


def _stage_split(stage_sid) -> dict:
    """Result.stats' split of one attempt's stage time: the durations of
    the leaf spans under its `stage` span summed by kind, and what its
    read units (`read:<table>`, on pool threads, so their times are
    thread-summed and may exceed the wall) took from storage."""
    tr = _trace.TRACES.current()
    spans = (tr.subtree(stage_sid)
             if tr is not None and stage_sid is not None else [])
    if not spans:
        return {}
    out = dict.fromkeys(
        ("stage_wait_ms", "stage_assemble_ms", "stage_put_ms",
         "stage_put_bytes", "stage_read_units", "read_io_ms",
         "read_decode_ms", "read_bytes"), 0)
    for s in spans:
        name, args = s["name"], s["args"]
        if name in ("wait", "assemble", "put"):
            out[f"stage_{name}_ms"] += s["dur"] or 0.0
            out["stage_put_bytes"] += args.get("bytes", 0)   # only `put`'s
        elif name.startswith("read:"):
            out["stage_read_units"] += 1
            out["read_io_ms"] += args.get("io_ms", 0.0)
            out["read_decode_ms"] += args.get("decode_ms", 0.0)
            out["read_bytes"] += args.get("bytes_read", 0)
    return {k: round(v, 3) for k, v in out.items()}


class PassPrefetcher:
    """Warm the next spill pass's block reads while the current pass's
    device program runs. All passes share the same committed files (row
    ranges slice AFTER the read), so warming is a cheap cache probe when
    the budget held and a real read-ahead when eviction emptied it.
    Prefetch must never fail or outlive the query: errors are swallowed,
    close() joins."""

    def __init__(self, stager: Stager, input_spec, snapshot):
        self.stager = stager
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
        self.enabled = bool(getattr(stager.settings, "spill_prefetch",
                                    True)) and bool(self.units)
        self._thread: threading.Thread | None = None

    def _warm(self) -> None:
        try:
            store = self.stager.store
            reg = store.blockcache
            for table, cols in self.units:
                for seg in self.stager.local_segments():
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
