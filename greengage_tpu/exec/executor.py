"""Executor: stage inputs, run the compiled SPMD program, gather, finalize.

The QD-side ExecutorStart/Run/End (src/backend/executor/execMain.c) plus
Gather Motion receive (nodeMotion.c:378) in one place:

  - stage: per-segment storage columns padded to static capacity and
    device_put with the seg sharding (the scan's tuple delivery)
  - run: the jitted shard_map program; overflow flags trigger a re-compile
    at the next size tier (spill/flow-control analog)
  - gather: device->host fetch of every segment's shard (Gather Motion);
    SEGMENT_GENERAL results read one segment only
  - finalize: merge-sort by the plan's merge keys, OFFSET/LIMIT trim,
    dictionary decode of TEXT outputs
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from dataclasses import dataclass

import numpy as np

import jax

from greengage_tpu import expr as E
from greengage_tpu import types as T
from greengage_tpu.exec import staging
from greengage_tpu.runtime import lockdebug
from greengage_tpu.exec.compile import (VALID_PREFIX, Compiler, CompileResult,
                                        _pow2)
from greengage_tpu.parallel.mesh import replicated_sharding, seg_sharding
from greengage_tpu.planner.locus import LocusKind
from greengage_tpu.runtime import interrupt
from greengage_tpu.runtime import memaccount
from greengage_tpu.runtime import overload as _overload
from greengage_tpu.runtime import trace as _trace
from greengage_tpu.runtime.faultinject import faults
from greengage_tpu.runtime.logger import (DEFAULT_BUCKETS_MB, counters,
                                          histograms)
from greengage_tpu.runtime.runaway import TRACKER
from greengage_tpu.storage import blockfile

# per-statement I/O accounting reported in Result.stats["scan_io"] and the
# EXPLAIN ANALYZE host-data-path lines (counter deltas, never wall clocks,
# so tests can assert them deterministically)
SCAN_COUNTERS = ("scan_files_read", "scan_bytes_decoded", "scan_cache_hit",
                 "scan_cache_miss", "scan_cache_evict")


def _span_ms(sid) -> float | None:
    """Duration of a closed span of the calling thread's trace."""
    tr = _trace.TRACES.current()
    spans = tr.subtree(sid) if tr is not None and sid is not None else []
    return spans[0]["dur"] if spans else None


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


class QueryError(RuntimeError):
    pass


class AdmissionError(QueryError):
    """Raised ONLY for the vmem admission rejection (est_bytes > limit) —
    the signal the spill machinery keys its escalation on."""
    pass


class BatchFallback(Exception):
    """A batched-serving window cannot run as one program (admission
    ceiling, overflow flags, unsignable shape): every member re-runs
    serially through the classic path, which owns retries and spill.
    Never surfaces to a client — it only routes execution."""
    pass


class OutOfDeviceMemory(QueryError):
    """The device allocator refused the program (XLA RESOURCE_EXHAUSTED)
    after admission let it through — the typed OOM the reference's
    memaccounting.c dumps an owner tree for. Carries the forensics the
    session writes to ``mem-<statement id>.json``: the per-statement
    accounting snapshot, the offending executable's memory analysis (when
    XLA reported one), and the admission-time estimate."""

    def __init__(self, message: str, snapshot: dict | None = None,
                 mem_analysis: dict | None = None, est_bytes: int = 0):
        super().__init__(message)
        self.snapshot = snapshot or {}
        self.mem_analysis = mem_analysis
        self.est_bytes = int(est_bytes)


def effective_limit_bytes(settings) -> int:
    """Per-query device-memory ceiling: the tighter of the hardware vmem
    guard and the resource queue's cap (queue-capped queries spill rather
    than fail, like workfile-bound queries under the reference's resource
    queues). 0 = unlimited."""
    limit = settings.vmem_protect_limit_mb * (1 << 20)
    qcap = int(getattr(settings, "resource_queue_memory_mb", 0)) << 20
    if qcap and (not limit or qcap < limit):
        limit = qcap
    from greengage_tpu.runtime.resgroup import current_memory_limit_mb

    gcap = current_memory_limit_mb() << 20   # thread's resource group share
    if gcap and (not limit or gcap < limit):
        limit = gcap
    return limit


@dataclass
class Result:
    columns: list[str]
    cols: dict[str, np.ndarray]
    valids: dict[str, np.ndarray | None]
    _order: list[str]
    wall_ms: float = 0.0
    plan_text: str = ""
    # per-query instrumentation (cdbexplain_recvExecStats analog)
    stats: dict = None

    def __len__(self):
        for c in self._order:
            return len(self.cols[c])
        return 0

    def rows(self) -> list[tuple]:
        n = len(self)
        out = []
        for i in range(n):
            row = []
            for cid in self._order:
                v = self.valids.get(cid)
                if v is not None and not v[i]:
                    row.append(None)
                else:
                    row.append(self.cols[cid][i])
            out.append(tuple(row))
        return out

    def to_pandas(self):
        import pandas as pd

        data = {}
        names = []
        seen: dict = {}
        for name in self.columns:   # dedupe: two count() outputs must not
            k = seen.get(name, 0)   # collapse into one DataFrame column
            seen[name] = k + 1
            names.append(name if k == 0 else f"{name}_{k}")
        for name, cid in zip(names, self._order):
            col = self.cols[cid]
            v = self.valids.get(cid)
            if v is not None:
                col = np.where(v, col, None) if col.dtype == object else \
                    pd.array(col, dtype="object")
                if not isinstance(col, np.ndarray):
                    col = np.asarray(self.cols[cid], dtype=object)
                    col[~v] = None
            data[name] = col
        return pd.DataFrame(data)


class EndpointBatch:
    """A completed mesh program whose per-segment output shards are held
    (on host) for endpoint-at-a-time retrieval; the backing store of one
    parallel retrieve cursor.

    Shards are COMPACTED to their live rows at construction: an open
    cursor pins memory proportional to its actual result, not to the
    program's static nseg x capacity padding (a selective cursor over a
    big table would otherwise pin the whole scan capacity until CLOSE)."""

    def __init__(self, comp, flat, snapshot, raw: bool, nseg: int):
        self.comp = comp
        self.snapshot = snapshot
        self.raw = raw
        # replicated below-gather locus: a single endpoint carries the
        # whole (identical) result
        rep = comp.gather_child_locus.kind in (LocusKind.SEGMENT_GENERAL,
                                               LocusKind.GENERAL)
        self.nendpoints = 1 if rep else nseg
        ncols = len(comp.out_cols)
        cap = comp.capacity
        sel = np.asarray(flat[2 * ncols]).reshape(nseg, cap)
        self.segs: list[tuple[dict, dict]] = []
        for k in range(self.nendpoints):
            m = np.asarray(sel[k], bool)
            cols, valids = {}, {}
            for i, c in enumerate(comp.out_cols):
                cols[c.id] = np.asarray(flat[2 * i]).reshape(nseg, cap)[k][m]
                valids[c.id] = np.asarray(
                    flat[2 * i + 1]).reshape(nseg, cap)[k][m]
            self.segs.append((cols, valids))


class Executor:
    def __init__(self, catalog, store, mesh, nseg: int, settings,
                 multihost=None):
        self.catalog = catalog
        self.store = store
        self.mesh = mesh
        self.nseg = nseg
        self.settings = settings
        self.multihost = multihost    # parallel.multihost.MultihostRuntime
        # planner/feedback.py store, wired by the owning Database: gives
        # admission a persisted measured footprint and cap hints for
        # shapes this PROCESS has never dispatched (restart / standby
        # promotion). Single-host only at every read site — feedback
        # state is per-process and must not steer lockstep branches.
        self.feedback = None
        # staged device inputs live in the store's byte-accounted LRU
        # registry (storage/blockcache.py): bounded within a manifest
        # version, evicted by recency against scan_cache_limit_mb
        self._stage_cache = store.blockcache.cache("stage")
        # compiled-program cache (the gang-reuse analog), REAL LRU:
        # (statement signature, shape signature, batch width bucket) ->
        # CompileResult. The shape signature (Compiler.shape_signature)
        # captures everything the trace reads — bucketed capacities,
        # dictionary fingerprints, consts digest, param dtypes — so a
        # manifest-version bump that stays inside every capacity bucket
        # and grows no dictionary REUSES the hot XLA executable instead
        # of recompiling. Bounded by the plan_cache_size GUC.
        #
        # _cache_mu guards ALL program-cache bookkeeping (_plan_cache,
        # _cap_hints, _sig_memo, _dyn_prune_cache): the
        # batch-serving stager mutates these concurrently with statement
        # threads (gg check races), and the old GIL-reliant try/KeyError
        # defenses only made lost updates quiet, not absent. RLock:
        # _cache_program -> _on_program_evicted nests. Critical sections
        # are dict ops only — never a compile, never device work.
        self._cache_mu = lockdebug.named(threading.RLock(),
                                         "executor._cache_mu")
        self._plan_cache: OrderedDict = lockdebug.shared(
            OrderedDict(), "executor._plan_cache")
        # runtime cardinality feedback (VERDICT r3 weak #3): the exact
        # counts the device reports for overflow-capable nodes (join
        # expansion totals, agg group counts, gather live rows) persist
        # per statement, so after DML bumps the manifest version the NEXT
        # compile sizes those capacities right instead of re-discovering
        # them through overflow-retry recompiles. cache_key -> {nid: cap},
        # LRU (recency = last record OR last use) under a fixed backstop
        # bound; the primary lifetime tie is _on_program_evicted
        self._cap_hints: OrderedDict = lockdebug.shared(
            OrderedDict(), "executor._cap_hints")
        # memoized shape signatures (see the dispatch loop in run());
        # insertion-order bounded — entries for dead versions age out
        self._sig_memo: OrderedDict = OrderedDict()
        # per-DISPATCH staging context (row ranges, aux tables, prune
        # stats): the serving stager stages batch k+1 WHILE a statement
        # thread stages its own classic dispatch on the same Executor, so
        # these travel per-thread — plain attributes were a cross-role
        # clobber (gg check races)
        self._tls = threading.local()

    # -- multihost spill-schedule parity (docs/PERF.md "Data movement") --
    # The tiered workfile's pass/bucket schedules are pure functions of
    # compiled estimates + settings, so every gang member computes the
    # same one. These hooks make that a VERIFIED invariant instead of a
    # hope: the coordinator arms recording per statement, every schedule
    # decision is noted (and broadcast one-way to the workers for
    # observability), workers ship the schedule they actually ran in
    # their completion ack, and the session compares. Single-host runs
    # never arm recording, so note() is a no-op there.
    def begin_spill_schedule(self) -> None:
        self._tls.spill_sched = []

    def note_spill_schedule(self, kind: str, **info) -> None:
        steps = getattr(self._tls, "spill_sched", None)
        if steps is None:
            return
        entry = {"kind": kind, **info}
        steps.append(entry)
        mh = self.multihost
        if mh is not None and getattr(mh, "is_coordinator", False):
            ch = getattr(mh, "channel", None)
            if ch is not None:
                try:
                    # one-way frame (workers' serve loop drops unknown
                    # ops): the schedule lands on every host's control
                    # log even if the statement later dies
                    ch.send({"op": "spill_schedule", **entry})
                except Exception:
                    pass   # observability must never fail the statement

    def collect_spill_schedule(self) -> list:
        steps = getattr(self._tls, "spill_sched", None)
        self._tls.spill_sched = None
        return steps or []

    # -- per-thread staging context (source-compatible properties) -----
    @property
    def _row_ranges(self):
        return getattr(self._tls, "row_ranges", {})

    @_row_ranges.setter
    def _row_ranges(self, value):
        self._tls.row_ranges = value

    @property
    def _aux_tables(self):
        return getattr(self._tls, "aux_tables", {})

    @_aux_tables.setter
    def _aux_tables(self, value):
        self._tls.aux_tables = value

    @property
    def _last_prune_stats(self):
        return getattr(self._tls, "last_prune_stats", {})

    @_last_prune_stats.setter
    def _last_prune_stats(self, value):
        self._tls.last_prune_stats = value

    @property
    def _last_dyn_stats(self):
        return getattr(self._tls, "last_dyn_stats", {})

    @_last_dyn_stats.setter
    def _last_dyn_stats(self, value):
        self._tls.last_dyn_stats = value

    # ------------------------------------------------------------------
    def run(self, plan, consts: dict, out_cols, cache_key=None,
            raw: bool = False, instrument: bool = False,
            scan_cap_override=None, row_ranges=None, aux_tables=None,
            allow_spill: bool = True, deferred: bool = False,
            no_direct: bool = False) -> Result:
        self._row_ranges = row_ranges or {}
        self._aux_tables = aux_tables or {}
        t0 = time.monotonic()
        snapshot = self.store.manifest.snapshot()
        version = snapshot.get("version", 0)
        with self._cache_mu:
            hints = dict(self._cap_hints.get(cache_key) or {})
            if hints:
                self._cap_hints.move_to_end(cache_key)
        if not hints and cache_key is not None and self.multihost is None \
                and self.feedback is not None:
            # persisted cap hints (feedback store): a restarted process
            # sizes overflow-capable capacities right on its FIRST
            # dispatch instead of re-discovering them via overflow-retry
            hints = dict(self.feedback.caps(cache_key))
        cap_overrides: dict = dict(hints)
        pack_disabled: set = set()
        TRACKER.enter()   # nested spill passes share the statement entry
        try:
            return self._run_tiers(
                plan, consts, out_cols, cache_key, raw, instrument,
                scan_cap_override, row_ranges, aux_tables, allow_spill,
                deferred, no_direct, t0, snapshot, version,
                hints, cap_overrides, pack_disabled)
        finally:
            TRACKER.release()

    def _run_tiers(self, plan, consts, out_cols, cache_key, raw, instrument,
                   scan_cap_override, row_ranges, aux_tables, allow_spill,
                   deferred, no_direct, t0, snapshot, version,
                   hints, cap_overrides, pack_disabled) -> Result:
        last_err = None
        tier = 0
        attempts = 0
        # hoisted-literal parameter vector (sql/paramize.py): values feed
        # the program as traced inputs and resolve pushed prune predicates
        pvec = (consts or {}).get("@params@")
        # tiers grow capacities; a key-packing bounds violation (stale
        # ANALYZE stats) instead re-runs the SAME tier unpacked, so the
        # attempt bound covers both kinds of retry
        while tier < self.settings.motion_retry_tiers \
                and attempts < self.settings.motion_retry_tiers + 4:
            attempts += 1
            # retry-tier boundary = a CHECK_FOR_INTERRUPTS site: a flag
            # set while the previous attempt ran (user cancel, statement
            # timeout, runaway cleaner) terminates the statement here
            interrupt.check_interrupts()
            # Feedback hints are deterministic inputs folded into the
            # shape signature (they size capacities); only RUNTIME
            # overrides (an overflow retry in flight) disable caching.
            ck = None
            sig_comp = None
            if cache_key is not None and cap_overrides == hints \
                    and not instrument and not scan_cap_override \
                    and not row_ranges and not aux_tables \
                    and not pack_disabled:
                # signature memo: the digest is a pure function of these
                # inputs (seg counts and dictionary growth always bump the
                # manifest version; the bound plan is version-keyed in the
                # session cache), so steady-state program-cache hits skip
                # the whole-plan signature walk
                mk = (cache_key, version, tier,
                      tuple(sorted(cap_overrides.items())), no_direct,
                      Compiler.codegen_settings_sig(self.settings))
                try:
                    sig, sig_comp = self._memo_signature(
                        mk,
                        lambda: Compiler(self.catalog, self.store,
                                         self.mesh, self.nseg, consts,
                                         self.settings, tier=tier,
                                         cap_overrides=cap_overrides,
                                         multihost=self.multihost is not None,
                                         no_direct=no_direct),
                        plan, snapshot)
                except Exception:
                    # unsignable shape (e.g. evicted transient raw
                    # dict): compile uncached; counted so a signature
                    # bug shows up as a visible reuse regression, not
                    # silence
                    counters.inc("program_cache_unsignable")
                    sig, sig_comp = None, None
                if sig is not None:
                    # trailing 0 = the unbatched program; batched serving
                    # keys its width buckets in the same LRU (run_batch)
                    ck = (cache_key, sig, 0)
            # fetch + recency bump in one _cache_mu section: a concurrent
            # statement's eviction can no longer interleave (the value
            # object stays alive once fetched either way)
            with self._cache_mu:
                comp = self._plan_cache.get(ck) if ck is not None else None
                was_cached = comp is not None
                if was_cached:
                    self._plan_cache.move_to_end(ck)
            compile_ms = 0.0
            if was_cached:
                counters.inc("program_cache_hit")
            else:
                if ck is not None:
                    counters.inc("program_cache_miss")
                t_comp = time.monotonic()
                with _trace.span("compile", tier=tier, cached=False):
                    if sig_comp is not None:
                        # reuse the signature walk's Compiler (same args by
                        # construction on this branch: the cacheable gate
                        # above pins instrument/overrides/aux off)
                        comp = sig_comp.compile(plan)
                    else:
                        comp = Compiler(self.catalog, self.store, self.mesh,
                                        self.nseg, consts, self.settings,
                                        tier=tier, cap_overrides=cap_overrides,
                                        instrument=instrument,
                                        multihost=self.multihost is not None,
                                        scan_cap_override=scan_cap_override,
                                        aux_tables=aux_tables,
                                        pack_disabled=pack_disabled,
                                        no_direct=no_direct).compile(plan)
                compile_ms = (time.monotonic() - t_comp) * 1e3
                if ck is not None:
                    # keep the compiled SPMD program for repeated dispatch
                    # of the same statement shape; LRU-bounded (each entry
                    # pins an XLA executable), with cap-hint bookkeeping
                    # evicted alongside the last program of a statement
                    # (unbounded-growth fix, ISSUE 5)
                    self._cache_program(ck, comp)
            limit = effective_limit_bytes(self.settings)
            if self.multihost is None:
                # memory-pressure brownout (runtime/overload.py): scale
                # the admission ceiling down so borderline statements
                # demote to the spill tier instead of racing a pressured
                # allocator. Single-host only — the factor is
                # process-local state and would desync the multihost
                # lockstep spill decision (est_bytes + settings only)
                limit = _overload.CONTROLLER.scaled_vmem(limit)
            # admission charge: the MEASURED per-segment executable
            # footprint when the executable is warm and the backend
            # reports real temps, else the compile-time estimate
            # (_admission_bytes) — four PRs of capacity bucketing finally
            # admit against ground truth on silicon
            admit_bytes, admit_measured = self._admission_bytes(
                comp, cache_key)
            if limit and admit_bytes > limit and not admit_measured \
                    and self._measure_unstaged(comp):
                # the ESTIMATE was about to refuse or spill a statement no
                # one has measured: it sums every plan node's batch as if
                # all were alive at once, and XLA knows better. Ask it
                # (one compile, which the dispatch then reuses) and let
                # the measurement decide.
                admit_bytes, admit_measured = self._admission_bytes(
                    comp, cache_key)
            if limit and admit_bytes > limit:
                if deferred:
                    raise QueryError(
                        f"parallel retrieve cursor would hold ~"
                        f"{admit_bytes >> 20} MB per segment, above the "
                        f"{limit >> 20} MB memory ceiling; cursors pin the "
                        "whole result and cannot spill")
                if allow_spill:
                    # host-offload spill (exec/spill.py): partition a
                    # probe-linear (or inner-join build) table into passes
                    # that fit, merge the captured partial states /
                    # deduped keys on a final pass. Multihost-safe: the
                    # pass decision is deterministic (est_bytes +
                    # settings) and every process gathers identical
                    # replicated results, so workers take the same
                    # branches in lockstep.
                    from greengage_tpu.exec import spill

                    try:
                        return self._spill_fallback(plan, consts, out_cols,
                                                    raw, instrument)
                    except spill.NotSpillable:
                        raise QueryError(
                            f"query would allocate ~"
                            f"{admit_bytes >> 20} MB "
                            f"per segment, above vmem_protect_limit_mb="
                            f"{self.settings.vmem_protect_limit_mb}, and "
                            "its shape is not spillable (no "
                            "partial-aggregate cut or sort over a "
                            "single-scan probe table)")
                raise AdmissionError(
                    f"query would allocate ~{admit_bytes >> 20} MB per "
                    f"segment, above the {limit >> 20} MB memory ceiling "
                    "(vmem protection / resource queue; raise the limit or "
                    "reduce the data)")
            # mid-flight enforcement (runaway_cleaner.c analog): ledger
            # what this statement will ACTUALLY hold (post-spill-decision
            # estimate), run the red-zone scan, and take any cancellation
            # aimed at us — a tier or spill-pass boundary is the XLA
            # CHECK_FOR_INTERRUPTS. Multihost: DISABLED — a per-process
            # tracker cancels nondeterministically across the mesh, and a
            # one-sided cancel desyncs the lockstep collectives (the
            # plan-hash invariant, parallel/multihost.py); the reference's
            # cleaner is likewise per-host vmem, not cluster-coordinated
            if self.multihost is None:
                # the cleaner prices victims by the same measured-when-warm
                # bytes admission charges — an over-estimated statement no
                # longer draws the red-zone cancellation for HBM it never
                # holds
                TRACKER.reprice(
                    admit_bytes,
                    int(getattr(self.settings,
                                "vmem_global_limit_mb", 0)) << 20,
                    float(getattr(self.settings, "runaway_red_zone", 0.9)),
                    measured=admit_measured)
                TRACKER.check()
            # host-data-path breakdown (EXPLAIN ANALYZE + bench microbench):
            # staging wall vs device compute vs result fetch, plus the scan
            # I/O counter deltas this statement caused
            io0 = {k: counters.get(k) for k in SCAN_COUNTERS}
            t_stage = time.monotonic()
            with _trace.span("stage", cat="stage",
                             tables=len(comp.input_spec)) as _sp_stage:
                inputs = self._stage(comp, snapshot, pvec)
                if comp.param_dtypes:
                    inputs = list(inputs) + [
                        self._put_param(np.asarray([v], dtype=dt))
                        for v, dt in zip(pvec.values, comp.param_dtypes)]
            t_compute = time.monotonic()
            stage_ms = (t_compute - t_stage) * 1e3
            scan_io = {k: counters.get(k) - io0[k] for k in SCAN_COUNTERS}
            _trace.annotate(_sp_stage, **scan_io)
            stage_split = _stage_split(_sp_stage)
            # last cancellation point before dispatch: once the program
            # is on the device it runs to this boundary (the documented
            # semantic — XLA programs cannot be preempted mid-flight)
            faults.check("cancel_before_dispatch")
            interrupt.check_interrupts()
            # measured memory accounting: AOT-compile once, attach XLA's
            # memory_analysis to the cached executable (warm hits reuse
            # it — zero re-analysis), and record the device owner on the
            # statement's account before the allocator commits to it
            self._ensure_mem_analysis(comp, inputs)
            if self.multihost is None and self.feedback is not None \
                    and cache_key is not None and comp.mem_analysis:
                _matot = (comp.mem_analysis["temp_bytes"]
                          + comp.mem_analysis.get("argument_bytes", 0)
                          + comp.mem_analysis.get("output_bytes", 0))
                # warm-shape calibration gauge: once the feedback store
                # predicts this shape's footprint (second execution on),
                # report the error of the PREDICTION, not of the planner
                # estimate — this is what collapses toward 0 warm
                _pred = self.feedback.measured_bytes(cache_key)
                if _pred:
                    counters.set("mem_est_error_pct", int(round(
                        100.0 * (_matot - _pred) / _pred)))
                self.feedback.note_measured(
                    cache_key, _matot,
                    comp.est_bytes * self._segments_per_device())
            _acct = memaccount.ACCOUNTS.current()
            if _acct is not None:
                _acct.set_device(comp.mem_analysis, comp.est_bytes)
            try:
                with _trace.span("dispatch", cat="device", tier=tier,
                                 est_bytes=comp.est_bytes):
                    if faults.check("device_oom"):
                        # faked allocator failure ('skip' type): the OOM
                        # classification/demotion path without needing a
                        # real 16 GB exhaustion in CI
                        raise RuntimeError(
                            "RESOURCE_EXHAUSTED: Out of memory while "
                            f"trying to allocate {comp.est_bytes} bytes "
                            "(fault injected: device_oom)")
                    flat = (comp.aot_fn or comp.device_fn)(*inputs)
                    # resolve async dispatch here so compute_ms is the
                    # device program and a device failure surfaces at the
                    # dispatch, not in device_get
                    jax.block_until_ready(flat)
            except Exception as e:
                if memaccount.is_oom_error(e):
                    # OOM forensics + demotion (memaccounting.c's
                    # RESOURCE_EXHAUSTED dump): never a bare XLA
                    # traceback for an allocator refusal
                    return self._handle_oom(
                        e, comp, plan, consts, out_cols, raw,
                        instrument, allow_spill, deferred, tier)
                raise
            t_fetch = time.monotonic()
            compute_ms = (t_fetch - t_compute) * 1e3
            # ONE device->host fetch for every output (small results pay
            # per-transfer latency, not per-byte cost)
            with _trace.span("fetch", cat="device") as _sp_f:
                flat = jax.device_get(list(flat))
            fetch_ms = (time.monotonic() - t_fetch) * 1e3
            _trace.annotate(_sp_f, bytes=int(sum(
                getattr(a, "nbytes", 0) for a in flat)))
            ncols = len(comp.out_cols)
            nflags = len(comp.flag_names)
            flags = dict(zip(comp.flag_names,
                             flat[2 * ncols + 1: 2 * ncols + 1 + nflags]))
            metrics = dict(zip(comp.metric_names,
                               flat[2 * ncols + 1 + nflags:]))
            dup = [k for k, v in flags.items() if k.startswith("join_dup") and v.any()]
            if dup:
                raise QueryError(
                    "hash join build side has duplicate keys; only unique-key "
                    "(PK-FK) hash joins are supported in this version")
            overflow = [k for k, v in flags.items()
                        if not k.startswith("join_dup") and v.any()]
            if not overflow:
                # cardinality feedback: when this statement paid an
                # overflow retry, persist the EXACT counts the device
                # reported so its next compile (post-DML replan, restart)
                # sizes capacities right immediately. A statement whose
                # estimates sufficed records nothing: a hint would only
                # re-size the program just cached and make the very next
                # run compile again (minutes for a join on the TPU).
                # Metrics are device-reduced, so multihost processes
                # record identical hints and stay in lockstep
                if cache_key is not None and comp.flag_caps and attempts > 1:
                    with self._cache_mu:
                        rec = self._cap_hints.setdefault(cache_key, {})
                        self._cap_hints.move_to_end(cache_key)
                        for _f, (nid, metric) in comp.flag_caps.items():
                            if metric in metrics:
                                need = (int(metrics[metric].flat[0])
                                        if self.multihost
                                        else int(np.max(metrics[metric])))
                                # pow2 bucket: small data drift re-records
                                # the SAME hint, so hint-sized programs
                                # keep their executable-cache entry
                                # across DML
                                rec[nid] = _pow2(need + max(need // 16, 64))
                        while len(self._cap_hints) > 512:
                            self._cap_hints.popitem(last=False)
                    if self.multihost is None and self.feedback is not None:
                        # mirror into the feedback store so a restarted
                        # process inherits the sizing (see run() seeding)
                        self.feedback.note_caps(cache_key, dict(rec))
                if deferred:
                    # parallel retrieve cursor: the program already ran and
                    # every segment's shard is on the host — finalization
                    # happens per-endpoint at RETRIEVE time
                    return EndpointBatch(comp, flat, snapshot, raw, self.nseg)
                with _trace.span("finalize", cat="host") as _sp_fin:
                    res = self._finalize(comp, flat, snapshot, raw=raw)
                res.wall_ms = (time.monotonic() - t0) * 1e3
                finalize_ms = _span_ms(_sp_fin)
                if not was_cached:
                    # the first dispatch of a fresh program carries the
                    # XLA compile; fold it into the statement's compile
                    # cost (EXPLAIN ANALYZE "Plan cache" line, bench)
                    compile_ms += compute_ms
                    counters.inc("compile_ms", int(compile_ms))
                for _mid, _cap in comp.agg_caps.items():
                    # how full the sort-based aggregates' group tables ran
                    counters.inc("agg_sort_groups", int(np.max(metrics[_mid])))
                    counters.inc("agg_sort_capacity", int(_cap))
                res.stats = {
                    "tiers_used": tier + 1,
                    "compiled": not was_cached,
                    # 0: admitted whole; the spill paths overwrite it with
                    # the passes they ran (_spill_fallback)
                    "spill_passes": 0,
                    "compile_ms": round(compile_ms, 1),
                    # host-data-path breakdown of the SUCCESSFUL attempt
                    "stage_ms": round(stage_ms, 2),
                    "compute_ms": round(compute_ms, 2),
                    "fetch_ms": round(fetch_ms, 2),
                    # where stage_ms went, as sums of the trace's own span
                    # durations (a stat and `gg trace` cannot disagree);
                    # absent when the statement is not traced
                    **stage_split,
                    **({} if finalize_ms is None
                       else {"finalize_ms": finalize_ms}),
                    "scan_io": scan_io,
                    "segments": self.nseg,
                    # FTS/topology version the dispatch was bound against
                    # (bumped by mesh re-formation and mirror promotion;
                    # pjit resolves the mesh at call site, so a cached
                    # executable re-binds to the current topology without
                    # recompiling)
                    "topology_version": getattr(
                        getattr(self.catalog, "segments", None),
                        "version", 0),
                    "scan_tables": [t for t, *_ in comp.input_spec],
                    "direct_dispatch": {t: d for t, _, _, d, *_ in comp.input_spec
                                        if d is not None},
                    "partitions": {t: len(p) for t, _, _, _, _, p, _
                                   in comp.input_spec if p is not None},
                    "zone_prune": dict(getattr(self, "_last_prune_stats", {})),
                    # runtime PartitionSelector results: child partitions
                    # kept / total after the build-side key-value probe
                    "dynamic_prune": dict(getattr(self, "_last_dyn_stats", {})),
                    "below_gather_capacity": comp.capacity,
                    "rows_out": len(res),
                    # per-node row counters SUM across segments; capacity
                    # metrics report the per-segment max (multi-host:
                    # already device-reduced + replicated)
                    "metrics": {k: (int(v.flat[0]) if self.multihost
                                    else int(np.sum(v)) if k.startswith("nrows_")
                                    else int(np.max(v)))
                                for k, v in metrics.items()},
                    # nrows_* metrics are already psum-reduced on device
                    # under multihost (every process holds the cluster
                    # total replicated), so host-side summing there would
                    # over-count by the process count
                    "node_rows": {comp.node_rows[k]:
                                  (int(v.flat[0]) if self.multihost
                                   else int(np.sum(v)))
                                  for k, v in metrics.items()
                                  if k in comp.node_rows},
                    # measured memory accounting (docs/OBSERVABILITY.md):
                    # what admission charged, what XLA measured for the
                    # executable, and the statement's owner totals so far
                    "mem": self._mem_stats(comp, admit_bytes,
                                           admit_measured),
                }
                if instrument:
                    # per-node Memory annotation source (EXPLAIN ANALYZE)
                    res.stats["node_est_bytes"] = dict(comp.node_est_bytes)
                # latency histograms (the gpperfmon timing surface):
                # per-phase host-data-path distributions, exposed as
                # Prometheus histograms via `gg metrics`
                histograms.observe("stage_ms", stage_ms)
                histograms.observe("dispatch_ms", compute_ms)
                histograms.observe("fetch_ms", fetch_ms)
                if not was_cached:
                    # compile_latency_ms, NOT compile_ms: the legacy
                    # total-ms counter already owns that name and one
                    # exposition name cannot carry two TYPEs
                    histograms.observe("compile_latency_ms", compile_ms)
                return res
            # size the retry from exact cardinalities where the device
            # reported them (join expansion totals)
            pack_over = [f for f in overflow if f.startswith("pack_overflow")]
            capacity_over = [f for f in overflow
                             if not f.startswith("pack_overflow")]
            compact_over = [f for f in capacity_over
                            if f.startswith("compact_overflow")]
            if compact_over:
                # a compaction that dropped rows starved everything above
                # it: the counts those operators report are of a truncated
                # batch. Widen the compactions alone and look again.
                capacity_over = compact_over
            for fname in pack_over:
                pack_disabled.add(comp.flag_packs[fname])
            for fname in capacity_over:
                hint = comp.flag_caps.get(fname)
                if hint is not None:
                    plan_id, metric = hint
                    need = (int(metrics[metric].flat[0]) if self.multihost
                            else int(np.max(metrics[metric])))
                    cap_overrides[plan_id] = need + max(need // 16, 64)
            # a compaction overflow (before the Gather, or of a build side
            # or a sort-aggregate's input) carries its exact live count in
            # the cap override — re-run the SAME tier with just that slice
            # widened; bumping the tier would needlessly 4x every other
            # node and disable tier-0 direct joins (advisor r3)
            if [f for f in capacity_over
                    if not f.startswith(("gather_compact_overflow",
                                         "compact_overflow"))]:
                tier += 1
            last_err = f"capacity overflow in {overflow} at tier {tier}"
        raise QueryError(f"query exceeded capacity tiers: {last_err}")

    def finalize_endpoint(self, batch: "EndpointBatch", seg: int) -> Result:
        """RETRIEVE body: decode ONE segment's compacted shard of a
        deferred run (the retrieve-session path, reference: src/backend/
        cdb/endpoint/cdbendpointretrieve.c — there a direct segment
        connection, here a host-side per-shard decode)."""
        cols, valids = batch.segs[seg]
        # shallow dict copies: _present reassigns dict slots (merge/limit)
        return self._present(batch.comp, dict(cols), dict(valids),
                             batch.snapshot, batch.raw)

    def run_single(self, plan, consts, out_cols, raw=False,
                   scan_cap_override=None, row_ranges=None, aux_tables=None,
                   no_direct=False, instrument=False):
        """One spill pass: no recursive spilling, no plan caching.
        ``instrument`` flows through so EXPLAIN ANALYZE of a spilling
        statement still collects per-node row counts (summed across
        passes by the spill driver)."""
        return self.run(plan, consts, out_cols, cache_key=None, raw=raw,
                        scan_cap_override=scan_cap_override,
                        row_ranges=row_ranges, aux_tables=aux_tables,
                        allow_spill=False, no_direct=no_direct,
                        instrument=instrument)

    # ---- program-cache bookkeeping shared by the classic dispatch
    # ---- loop and the batched-serving path ---------------------------
    def _memo_signature(self, mk, make_compiler, plan, snapshot):
        """Memoized shape-signature walk -> (sig, walker Compiler or
        None when the memo hit). An unsignable shape raises through —
        callers choose their fallback (uncached compile / BatchFallback).
        The walker is returned so a compile on the miss path can reuse
        its scan collection instead of re-walking."""
        with self._cache_mu:
            sig = self._sig_memo.get(mk)
        if sig is not None:
            return sig, None
        comp = make_compiler()
        # the signature walk itself runs unlocked (it reads plan/manifest
        # state, not the memo); only the memo insert is serialized
        sig = comp.shape_signature(plan, snapshot)
        with self._cache_mu:
            self._sig_memo[mk] = sig
            while len(self._sig_memo) > 2048:
                self._sig_memo.popitem(last=False)
        return sig, comp

    def _cache_program(self, ck, comp) -> None:
        """Insert a compiled program into the bounded LRU; evictions
        drop their statement's cap-hint bookkeeping via
        _on_program_evicted (one policy for every caller)."""
        with self._cache_mu:
            self._plan_cache[ck] = comp
            limit_n = max(int(getattr(self.settings,
                                      "plan_cache_size", 128)), 1)
            while len(self._plan_cache) > limit_n:
                old_k, _old = self._plan_cache.popitem(last=False)
                self._on_program_evicted(old_k)

    # ---- vectorized serving (exec/batchserve.py) ---------------------
    # One XLA dispatch serves a whole admission window of same-shape
    # statements: their hoisted parameter vectors stack along a leading
    # member axis and the width-bucketed batched program (compile.py
    # batch_width) runs once over the shared staged inputs. Split into
    # prepare (compile/admit/stage) and dispatch (device) halves so the
    # serving pipeline can stage batch k+1 while batch k runs on device.

    def prepare_batch(self, plan, consts, out_cols, cache_key, pvec_rows):
        """Compile-or-reuse the width-bucketed batched program, admit it,
        and stage its (shared) table inputs plus the stacked parameter
        arrays. -> (comp, inputs, snapshot, compiled: bool). Raises
        BatchFallback when the batch cannot run as one program (admission
        ceiling, unsignable shape) — members then re-run serially."""
        width = len(pvec_rows)
        bucket = _pow2(max(width, 1))
        snapshot = self.store.manifest.snapshot()
        version = snapshot.get("version", 0)
        with self._cache_mu:
            hints = dict(self._cap_hints.get(cache_key) or {})
        mk = (cache_key, version, 0, tuple(sorted(hints.items())),
              False, Compiler.codegen_settings_sig(self.settings),
              "batch")
        try:
            sig, sig_comp = self._memo_signature(
                mk,
                lambda: Compiler(self.catalog, self.store, self.mesh,
                                 self.nseg, consts, self.settings,
                                 tier=0, cap_overrides=dict(hints),
                                 batch_width=bucket),
                plan, snapshot)
        except Exception:
            counters.inc("program_cache_unsignable")
            raise BatchFallback("unsignable statement shape")
        ck = (cache_key, sig, bucket)
        with self._cache_mu:
            comp = self._plan_cache.get(ck)
            was_cached = comp is not None
            if was_cached:
                self._plan_cache.move_to_end(ck)
        if was_cached:
            counters.inc("program_cache_hit")
        else:
            counters.inc("program_cache_miss")
            t_comp = time.monotonic()
            with _trace.span("compile", cat="exec", batch_width=bucket,
                             cached=False):
                if sig_comp is None:
                    sig_comp = Compiler(self.catalog, self.store, self.mesh,
                                        self.nseg, consts, self.settings,
                                        tier=0, cap_overrides=dict(hints),
                                        batch_width=bucket)
                comp = sig_comp.compile(plan)
            counters.inc("compile_ms",
                         int((time.monotonic() - t_comp) * 1e3))
            self._cache_program(ck, comp)
        # admission: est_bytes is already width-scaled (compile.py); the
        # measured footprint of a warm bucket takes over once the AOT
        # analysis ran — PR-10's ground truth bounding the batch width
        limit = effective_limit_bytes(self.settings)
        if cache_key is not None:
            # width-bucket-qualified feedback key: est/measured bytes are
            # width-scaled, so each bucket calibrates independently
            comp.fb_key = f"{cache_key}@w{bucket}"
        admit_bytes, _measured = self._admission_bytes(comp, comp.fb_key)
        if limit and admit_bytes > limit:
            raise BatchFallback(
                f"batched program would hold ~{admit_bytes >> 20} MB "
                f"per segment at width {bucket}, above the "
                f"{limit >> 20} MB ceiling")
        # staging: identical to the classic single-statement stage except
        # that parameter-valued prune predicates are DROPPED (pvec=None):
        # zone-map pruning by one member's values would starve its
        # batch-mates of blocks their rows live in. Value-pinned prune
        # predicates are shared by every member and stay active.
        self._row_ranges = {}
        self._aux_tables = {}
        with _trace.span("stage", cat="stage",
                         tables=len(comp.input_spec)) as _sp:
            inputs = list(self._stage(comp, snapshot, None))
            padded = list(pvec_rows) \
                + [pvec_rows[-1]] * (bucket - width)
            for slot, dt in enumerate(comp.param_dtypes):
                host = np.asarray([[pv.values[slot]] for pv in padded],
                                  dtype=dt)
                inputs.append(self._put_param(host))
        _trace.annotate(_sp, batch_width=width, batch_bucket=bucket)
        return comp, inputs, snapshot, not was_cached

    def dispatch_batch(self, comp: CompileResult, inputs) -> list:
        """Run a prepared batched program and fetch every output to host.
        The serving pipeline's device stage — runs on the dispatcher
        thread with NO statement context, so a member's cancellation can
        never abort its batch-mates (members are masked at demux)."""
        self._ensure_mem_analysis(comp, inputs)
        if comp.fb_key is not None and self.multihost is None \
                and self.feedback is not None and comp.mem_analysis:
            self.feedback.note_measured(
                comp.fb_key,
                comp.mem_analysis["temp_bytes"]
                + comp.mem_analysis.get("argument_bytes", 0)
                + comp.mem_analysis.get("output_bytes", 0),
                comp.est_bytes * self._segments_per_device())
        with _trace.span("dispatch", cat="device",
                         batch_width=comp.batch_width,
                         est_bytes=comp.est_bytes):
            faults.check("batch_dispatch")
            flat = (comp.aot_fn or comp.device_fn)(*inputs)
            jax.block_until_ready(flat)
        with _trace.span("fetch", cat="device") as _sp:
            flat = jax.device_get(list(flat))
        _trace.annotate(_sp, bytes=int(sum(
            getattr(a, "nbytes", 0) for a in flat)))
        return flat

    def batch_overflowed(self, comp: CompileResult, flat) -> list[str]:
        """Flag names any member tripped — capacity overflow, packing
        bounds, duplicate join keys. A batched program never retries in
        place (per-member capacity needs differ); any flag sends every
        member down the serial path, whose tier machinery handles it."""
        ncols_part = 2 * len(comp.out_cols) + 1
        out = []
        for j, name in enumerate(comp.flag_names):
            if np.asarray(flat[ncols_part + j]).any():
                out.append(name)
        return out

    def demux_batch(self, comp: CompileResult, flat, member: int,
                    snapshot) -> Result:
        """One member's Result from a fetched batched output: slice its
        row along the leading member axis and finalize exactly like a
        classic dispatch (merge keys, host LIMIT, TEXT decode)."""
        ncols_part = 2 * len(comp.out_cols) + 1
        member_flat = [np.asarray(flat[i])[member]
                       for i in range(ncols_part)]
        with _trace.span("finalize", cat="host", member=member):
            return self._finalize(comp, member_flat, snapshot, raw=False)

    def run_batch(self, plan, consts, out_cols, cache_key,
                  pvec_rows) -> list[Result]:
        """Synchronous prepare+dispatch+demux of one batch (the test and
        fallback surface; the serving pipeline calls the halves from its
        own stage/dispatch threads). Raises BatchFallback when the batch
        must be served serially."""
        comp, inputs, snapshot, compiled = self.prepare_batch(
            plan, consts, out_cols, cache_key, pvec_rows)
        flat = self.dispatch_batch(comp, inputs)
        over = self.batch_overflowed(comp, flat)
        if over:
            raise BatchFallback(f"overflow flags {over} at width "
                                f"{len(pvec_rows)}")
        out = []
        for m in range(len(pvec_rows)):
            res = self.demux_batch(comp, flat, m, snapshot)
            res.stats = {"batched": True, "batch_width": len(pvec_rows),
                         "batch_bucket": comp.batch_width,
                         "compiled": compiled, "segments": self.nseg}
            out.append(res)
        return out

    # ---- measured memory accounting (runtime/memaccount.py) ----------
    def _ensure_mem_analysis(self, comp: CompileResult, inputs) -> None:
        """First dispatch of a program: AOT-compile it (lower().compile())
        and attach XLA's memory_analysis — temp/argument/output/generated-
        code bytes — to the cached CompileResult. Dispatch then goes
        through the AOT executable, so the program still compiles exactly
        once (the AOT call path measures no slower than the jit wrapper),
        and every warm program-cache hit reuses both the executable and
        the analysis: ``mem_analysis_runs`` counts analyses, and tests
        assert a warm hit adds zero."""
        if comp.mem_failed or comp.aot_fn is not None \
                or not bool(getattr(self.settings,
                                    "mem_accounting_enabled", True)):
            return
        if self.multihost is not None:
            # multihost keeps the plain jit path: an AOT executable pins
            # the compile-time device assignment, and the PR-6 topology
            # re-formation contract depends on pjit re-binding cached
            # executables to the CURRENT mesh at call site; per-process
            # analysis state would also leak into admission and desync
            # the lockstep branch decisions (see _admission_bytes)
            return
        # serialize the first analysis per program: two server threads
        # cold-dispatching the same cached CompileResult must not both
        # pay the XLA compile; the loser of the race waits and reuses
        with comp.mem_lock:
            if comp.mem_failed or comp.aot_fn is not None:
                return
            try:
                comp.aot_fn = comp.device_fn.lower(*inputs).compile()
            except Exception:
                # a shape/backend the AOT path can't lower: latch off and
                # fall back to the jit path, which re-raises real errors
                # at the dispatch
                comp.mem_failed = True
                return
            try:
                ma = comp.aot_fn.memory_analysis()
                comp.mem_analysis = {
                    "argument_bytes": int(
                        getattr(ma, "argument_size_in_bytes", 0)),
                    "output_bytes": int(
                        getattr(ma, "output_size_in_bytes", 0)),
                    "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
                    "generated_code_bytes": int(
                        getattr(ma, "generated_code_size_in_bytes", 0)),
                    "alias_bytes": int(
                        getattr(ma, "alias_size_in_bytes", 0)),
                }
            except Exception:
                comp.mem_failed = True   # executable stays dispatchable
                return
            counters.inc("mem_analysis_runs")
            total = (comp.mem_analysis["argument_bytes"]
                     + comp.mem_analysis["output_bytes"]
                     + comp.mem_analysis["temp_bytes"])
            histograms.observe("executable_mem_mb", total / 1e6,
                               buckets=DEFAULT_BUCKETS_MB)
            # estimate-vs-measured calibration gauge: the analysis is
            # per DEVICE (one SPMD module), so compare against the
            # estimate for the segments that device hosts
            est_dev = comp.est_bytes * self._segments_per_device()
            if est_dev > 0:
                counters.set("mem_est_error_pct", int(round(
                    100.0 * (total - est_dev) / est_dev)))

    def _measure_unstaged(self, comp: CompileResult) -> bool:
        """Compile ``comp`` from the shapes of its inputs alone, so that
        admission can read XLA's memory analysis before anything is staged.
        Only where a measurement could govern (``_admission_bytes``: one
        host, a backend with a real allocator) and only for a program of
        base-table scans. -> whether ``comp.mem_analysis`` now holds one."""
        if comp.mem_analysis is not None:
            return True
        if self.multihost is not None or comp.batch_width \
                or memaccount.device_memory_stats() is None \
                or any(t in getattr(self, "_aux_tables", {})
                       for t, *_ in comp.input_spec):
            return False
        shard = seg_sharding(self.mesh)
        shapes = []
        for table, cols, cap, *_ in comp.input_spec:
            schema = self.catalog.get(table)
            for c in cols:
                dt = (np.dtype(bool) if c.startswith(VALID_PREFIX)
                      else self._stage_dtype(schema, c))
                shapes.append(jax.ShapeDtypeStruct((self.nseg * cap,), dt,
                                                   sharding=shard))
            shapes.append(jax.ShapeDtypeStruct((self.nseg * cap,), bool,
                                               sharding=shard))
        shapes += [jax.ShapeDtypeStruct((1,), dt,
                                        sharding=replicated_sharding(self.mesh))
                   for dt in comp.param_dtypes]
        with _trace.span("compile", cat="plan", unstaged=True):
            self._ensure_mem_analysis(comp, shapes)
        return comp.mem_analysis is not None

    def _admission_bytes(self, comp: CompileResult,
                         cache_key=None) -> tuple[int, bool]:
        """Bytes the admission check and runaway ledger charge for this
        program -> (bytes, measured?). Prefers the measured per-segment
        executable footprint once the executable is warm AND the backend
        has a real device allocator (memory_stats() reports one — TPU/
        GPU); falls back to the feedback store's persisted measurement of
        the same statement shape when THIS process hasn't analyzed it yet
        (restart, standby promotion). The CPU backend's memory_analysis
        covers host buffers that no HBM limit governs, so estimates keep
        governing there — and the vmem GUC semantics the spill tests pin
        stay estimate-driven."""
        ma = comp.mem_analysis
        # multihost NEVER prefers measured bytes: comp.mem_analysis is
        # per-process state (one worker's transient AOT failure would
        # flip only ITS admission/spill branch and desync the lockstep
        # collectives) — the spill decision must stay a pure function of
        # est_bytes + settings, the PR-3 determinism contract
        if ma and self.multihost is None \
                and bool(getattr(self.settings,
                                 "mem_accounting_enabled", True)) \
                and ma.get("temp_bytes", 0) > 0 \
                and memaccount.device_memory_stats() is not None:
            # memory_analysis describes the per-DEVICE SPMD module (one
            # device's shard of every buffer): scale to per-segment by
            # the segments each device hosts, not by nseg — on a 1-chip
            # backend all nseg segments share the device
            measured = (ma["temp_bytes"] + ma.get("argument_bytes", 0)
                        + ma.get("output_bytes", 0)) \
                // self._segments_per_device()
            if measured > 0:
                counters.inc("admission_measured_total")
                return measured, True
        if ma is None and cache_key is not None and self.multihost is None \
                and self.feedback is not None \
                and bool(getattr(self.settings,
                                 "mem_accounting_enabled", True)) \
                and memaccount.device_memory_stats() is not None:
            # a prior execution (possibly an earlier PROCESS — the store
            # persists beside the catalog) measured this shape: a cold
            # program still admits against ground truth
            mtot = self.feedback.measured_bytes(cache_key)
            if mtot:
                per_seg = int(mtot) // self._segments_per_device()
                if per_seg > 0:
                    counters.inc("admission_measured_total")
                    counters.inc("admission_measured_feedback_total")
                    return per_seg, True
        counters.inc("admission_estimated_total")
        return comp.est_bytes, False

    def _segments_per_device(self) -> int:
        ndev = max(int(getattr(getattr(self.mesh, "devices", None),
                               "size", 1) or 1), 1)
        return max(self.nseg // ndev, 1)

    def _mem_stats(self, comp: CompileResult, admit_bytes: int,
                   admit_measured: bool) -> dict:
        """The Result.stats['mem'] block: estimate vs measurement vs live
        device watermark (EXPLAIN ANALYZE's Memory lines + bench)."""
        out = {
            "est_bytes": int(comp.est_bytes),
            "admitted_bytes": int(admit_bytes),
            "admitted_by": "measured" if admit_measured else "estimate",
            "measured": (dict(comp.mem_analysis)
                         if comp.mem_analysis else None),
        }
        dstats = memaccount.device_memory_stats()
        if dstats is not None:
            out["device_bytes_in_use"] = int(dstats.get("bytes_in_use", 0))
            out["device_peak_bytes_in_use"] = int(
                dstats.get("peak_bytes_in_use", 0))
        acct = memaccount.ACCOUNTS.current()
        if acct is not None:
            out["owners"] = acct.owner_totals()
        return out

    def _spill_fallback(self, plan, consts, out_cols, raw, instrument):
        """Host-offload spill paths, shared by the admission rejection
        and the OOM demotion: partial-aggregate passes first, then
        window-partition passes over the PARTITION BY hash space, then
        the external-merge sort. Raises spill.NotSpillable through when
        no shape applies."""
        from greengage_tpu.exec import spill

        try:
            res, npasses = spill.spill_run(
                self, plan, consts, out_cols, raw, instrument=instrument)
        except spill.NotSpillable:
            try:
                # window-partition spill (exec/spill.py spill_window_run):
                # whole partitions per hash bucket, exact results
                res, npasses = spill.spill_window_run(
                    self, plan, consts, out_cols, raw,
                    instrument=instrument)
            except spill.NotSpillable:
                # external-merge sort spill (tuplesort role): ORDER BY
                # results merge on the host from per-pass device-sorted
                # runs
                res, npasses = spill.spill_sort_run(
                    self, plan, consts, out_cols, raw,
                    instrument=instrument)
        res.stats = dict(res.stats or {})
        res.stats["spill_passes"] = npasses
        return res

    def _handle_oom(self, e, comp, plan, consts, out_cols, raw, instrument,
                    allow_spill, deferred, tier):
        """A dispatched program hit RESOURCE_EXHAUSTED: build the typed
        OutOfDeviceMemory (accounting snapshot + the executable's memory
        analysis — the memaccounting.c OOM dump payload), then demote to
        the spill path ONCE when allowed (oom_spill_retry) before
        surfacing. Multihost never demotes: a one-sided runtime OOM is
        not a deterministic input, and a lone process entering the spill
        regime would desync the lockstep collectives."""
        counters.inc("oom_events")
        acct = memaccount.ACCOUNTS.current()
        snap = acct.snapshot() if acct is not None else {}
        snap["device_stats"] = memaccount.device_memory_stats()
        oom = OutOfDeviceMemory(
            f"out of device memory dispatching at tier {tier} "
            f"(estimated ~{comp.est_bytes >> 20} MB/segment): {e}",
            snapshot=snap, mem_analysis=comp.mem_analysis,
            est_bytes=comp.est_bytes)
        if allow_spill and not deferred and self.multihost is None \
                and bool(getattr(self.settings, "oom_spill_retry", True)):
            from greengage_tpu.exec import spill

            try:
                res = self._spill_fallback(plan, consts, out_cols, raw,
                                           instrument)
            except spill.NotSpillable:
                raise oom from e
            counters.inc("oom_spill_retries")
            res.stats["oom_demoted"] = True
            return res
        raise oom from e

    # ------------------------------------------------------------------
    def _local_segments(self):
        if self.multihost is None:
            return set(range(self.nseg))
        if not self.multihost.local_segments:
            from greengage_tpu.parallel.multihost import local_segment_positions

            self.multihost.local_segments = local_segment_positions()
        return set(s for s in self.multihost.local_segments if s < self.nseg)

    def _on_program_evicted(self, key) -> None:
        """A compiled program left the LRU: when it was the LAST program
        of its statement, drop the statement's cap-hint bookkeeping too —
        its lifetime is tied to the plan cache (unbounded-growth fix,
        ISSUE 5)."""
        cache_key = key[0]
        # callers hold _cache_mu (RLock): the membership scan and the
        # cap-hint drop are one atomic step
        with self._cache_mu:
            if any(k[0] == cache_key for k in list(self._plan_cache)):
                return
            self._cap_hints.pop(cache_key, None)

    def invalidate_table(self, table: str) -> None:
        """Drop compiled programs scanning ``table`` (DROP TABLE / DROP
        PARTITION): a same-named recreated table could otherwise alias a
        stale executable whose shape signature coincides."""
        base = table.split("#", 1)[0]
        with self._cache_mu:
            stale = [k for k, c in list(self._plan_cache.items())
                     if any(t == table or t.split("#", 1)[0] == base
                            for t, *_ in c.input_spec)]
            for k in stale:
                self._plan_cache.pop(k, None)
            for k in stale:
                self._on_program_evicted(k)

    @staticmethod
    def _resolve_prune(prune, pvec):
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

    def _put_param(self, host: np.ndarray):
        """Place one parameter scalar on the mesh, replicated (multi-host:
        every process binds the same values from the same statement text,
        keeping the lockstep invariant)."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        sh = NamedSharding(self.mesh, P())
        # a `put` like the tables': on a TPU this small transfer queues
        # behind the table transfers still in flight, so it is where the
        # statement thread waits for them (PERF.md section 5)
        with _trace.span("put", cat="stage", bytes=int(host.nbytes)):
            if self.multihost is None:
                return jax.device_put(host, sh)
            return jax.make_array_from_callback(host.shape, sh,
                                                lambda idx: host[idx])

    def _stage(self, comp: CompileResult, snapshot, pvec=None) -> list:
        """Pipelined input staging (exec/staging.py, docs/PERF.md): hand
        the staging pool one read+decode unit per (table, segment, column),
        column-major, then consume them in spec order column by column:
        wait for a column's units, fill its preallocated [nseg*cap] buffer
        and issue its device transfer while the later columns (and the
        next table's) still decode on the pool — and, with JAX async
        dispatch, under the device program itself."""
        arrays = []
        shard = seg_sharding(self.mesh)
        local_segs = self._local_segments()
        # evict staged arrays + store cache entries from older manifest
        # versions (any write bumps the version, so stale device copies are
        # unreachable and only waste HBM — the dispatcher's
        # CdbComponentDatabases invalidation analog)
        version = snapshot.get("version", 0)
        self.store.blockcache.invalidate_versions(version)
        self._last_prune_stats = {}
        self._last_dyn_stats = {}
        aux = getattr(self, "_aux_tables", {})
        ranges = getattr(self, "_row_ranges", {})
        rpool = staging.pool(self.settings)
        # the statement's interrupt context, captured HERE because read
        # units run on pool threads (interrupt.current() is thread-keyed):
        # each unit checks the flag before its read, so a multi-second
        # cold stage cancels mid-flight instead of at the next boundary
        stmt_ctx = interrupt.REGISTRY.current()
        # the statement's memory account travels the same way: pool
        # threads bind to it for the unit's duration, so block-cache
        # inserts inside the read attribute to the right owner tree
        stmt_acct = memaccount.ACCOUNTS.current()
        # and so does its trace: the registry is keyed by thread, so a
        # unit records its `read:<table>` span through this handle, under
        # the `stage` span this call runs inside
        stmt_trace = _trace.TRACES.current()
        stage_sid = stmt_trace.top() if stmt_trace is not None else None

        # plan phase: resolve per-table staging decisions. Read units are
        # submitted through a bounded LOOKAHEAD window (the table being
        # assembled plus one ahead): later tables' reads overlap earlier
        # tables' assembly and transfer WITHOUT holding every table's
        # decoded columns in flight at once — peak host memory stays at
        # ~two tables, like the old serial loop's one.
        plans = []   # [kind, table, cols, cap, key, prune, payload]
        staged_local: dict = {}   # key -> (staged, pstats) THIS statement
        for table, cols, cap, direct, prune, child_parts, dyn in comp.input_spec:
            # hoisted parameters resolve HERE — staging decisions (zone
            # maps, block indexes, dynamic partition pruning) see the
            # statement's current values, and the stage-cache key below
            # carries the resolved predicate so different values never
            # share a pruned staging
            prune = self._resolve_prune(prune, pvec)
            if dyn is not None and isinstance(dyn, tuple):
                dyn = (dyn[0], self._resolve_prune(dyn[1], pvec) or (),
                       dyn[2])
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
                    table, child_parts, dyn, snapshot)
            key = (table, tuple(cols), cap, version, direct, prune,
                   child_parts, ranges.get(table))
            if table not in ranges:
                hit = self._stage_cache.get(key, staging.MISS)
                if hit is not staging.MISS:
                    plans.append(("hit", table, cols, cap, key, prune, hit))
                    continue
            if key in staged_local:
                # same scan twice in ONE input spec (self-join): reuse the
                # first occurrence's staged arrays instead of reading and
                # transferring the identical inputs again
                plans.append(("dup", table, cols, cap, key, prune, None))
                continue
            staged_local[key] = None   # first occurrence claims the key
            plans.append(("read", table, cols, cap, key, prune, {
                "units": staging.column_units(
                    c for c in cols if not c.startswith(VALID_PREFIX)),
                "child_parts": child_parts, "direct": direct,
                "rng": ranges.get(table), "futs": None, "buffers": None}))

        read_plans = [p for p in plans if p[0] == "read"]

        def _submit(p):
            _, table, cols, cap, _key, prune, st = p
            if st["futs"] is not None:
                return
            # preallocate the [nseg*cap] staging buffers so eligible
            # columns decode straight into their slots inside the pool
            # (read_segment's in-place fast path); ranged/partitioned
            # scans slice after the read and keep the copy path, and so
            # do scans that fill only SOME segments (direct dispatch,
            # multihost remotes) — a cached view of a partially-used
            # buffer would pin far more memory than its byte accounting
            buffers = None
            if st["rng"] is None and st["child_parts"] is None \
                    and st["direct"] is None \
                    and len(local_segs) == self.nseg:
                schema = self.catalog.get(table)
                buffers = {c: np.empty(self.nseg * cap,
                                       self._stage_dtype(schema, c))
                           for unit in st["units"] for c in unit}
            # direct dispatch: only the owning segment's storage is
            # read/staged (cdbtargeteddispatch.c analog)
            segs = [seg for seg in range(self.nseg)
                    if seg in local_segs
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
                    row[seg] = rpool.submit(
                        self._read_unit, table, st["child_parts"], seg,
                        unit, snapshot, prune, st["rng"],
                        dest, stmt_ctx, stmt_acct, stmt_trace, stage_sid)
                futs.append(row)
            st["buffers"] = buffers
            st["futs"] = futs
            st["read_units"] = len(futs) * len(segs)

        # assemble phase (spec order, deterministic): fill staging buffers
        # in place and put each column on the mesh as soon as it completes
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
                if kind == "hit":
                    staged, pstats = payload
                    arrays.extend(staged)
                    if pstats is not None:
                        self._last_prune_stats[table] = pstats
                    continue
                if kind == "dup":
                    # eviction-immune within the statement: the first
                    # occurrence stored its result here whatever the cache
                    # budget did since
                    staged, pstats = staged_local[key]
                    arrays.extend(staged)
                    if pstats is not None:
                        self._last_prune_stats[table] = pstats
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
                        _submit(read_plans[j])   # this table + one ahead
                    futs, buffers = st["futs"], st["buffers"]
                    # every unit of a segment sees the same zone maps and
                    # row count: the first column's speak for the segment
                    kept = total_blocks = 0
                    for seg, n, pstat in self._land(futs, 0, per_seg):
                        per_seg[seg][2] = n
                        if pstat is not None:
                            kept += pstat[0]
                            total_blocks += pstat[1]
                if prune and total_blocks:
                    self._last_prune_stats[table] = (kept, total_blocks)
                unit_of = {c: u for u, unit in enumerate(units)
                           for c in unit}
                schema = self.catalog.get(table)
                staged = []
                for c in cols:
                    # a validity mask comes with the unit of its column
                    u = unit_of.get(c[len(VALID_PREFIX):]
                                    if c.startswith(VALID_PREFIX) else c)
                    if u is not None and futs[u] is not None:
                        with _trace.span("wait", cat="stage"):
                            self._land(futs, u, per_seg)
                    with _trace.span("assemble", cat="stage"):
                        host = self._fill_column(schema, c, cap, per_seg,
                                                 buffers)
                    staged.append(self._put(host, shard, cap))
                with _trace.span("assemble", cat="stage"):
                    present = staging.fill_buffer(
                        self.nseg, cap, np.dtype(bool),
                        ((s, np.ones(n, dtype=bool))
                         for s, (_, _, n) in enumerate(per_seg)), False)
                staged.append(self._put(present, shard, cap))
                staged_local[key] = (staged,
                                     self._last_prune_stats.get(table))
                nbytes = sum(int(getattr(a, "nbytes", 64)) for a in staged)
                memaccount.charge("staging", nbytes, item=table)
                _trace.annotate(_sp_t, rows=int(sum(n for _, _, n in per_seg)),
                                bytes=nbytes, segments=len(per_seg),
                                read_units=st["read_units"])
                if st["rng"] is None:
                    self._stage_cache.put(
                        key, (staged, self._last_prune_stats.get(table)),
                        nbytes=nbytes, version=version)
                arrays.extend(staged)
                done_reads += 1
                # let go of the table's host copies HERE, as the tail of
                # its assembly: unmapping GBs of decoded blocks and
                # [nseg*cap] buffers costs this thread ~0.07 s a GB on the
                # chip's host (PERF.md section 5), which would otherwise
                # fall between the spans when _stage returns — and until
                # then every table's copies stayed alive at once
                with _trace.span("assemble", cat="stage", release=True):
                    st["futs"] = st["buffers"] = None
                    per_seg = futs = buffers = host = present = None
        return arrays

    @staticmethod
    def _land(futs, u, per_seg) -> list:
        """Block until unit ``u`` of every staged segment is done and its
        columns and masks are in ``per_seg``; -> [(segment, nrows, prune
        stats)]. A landed unit's futures are let go (``futs[u] = None``
        says it has landed). A cancellation point a column; inside the
        wait the units poll the statement's context themselves."""
        interrupt.check_interrupts()
        row, futs[u] = futs[u], None
        out = []
        for seg, fut in enumerate(row):
            if fut is None:
                continue
            c, v, n, pstat = fut.result()
            per_seg[seg][0].update(c)
            per_seg[seg][1].update(v)
            out.append((seg, n, pstat))
        return out

    def _read_unit(self, table, child_parts, seg, storage_cols, snapshot,
                   prune, rng, dest=None, stmt_ctx=None, stmt_acct=None,
                   stmt_trace=None, parent_sid=None):
        """One pooled staging unit: one column of one segment, decoded
        (several where staging.column_units keeps them together; + this
        thread's zone-prune stats). Runs concurrently with other units —
        the store's caches and read-path self-heal are thread-safe.
        ``dest`` carries this segment's staging-buffer slots for the
        in-place decode fast path. ``stmt_ctx`` is the owning statement's
        interrupt context: each unit is a cancellation point, and the
        raise travels back to the statement thread via fut.result().
        ``stmt_acct`` binds this pool thread to the statement's memory
        account so block-cache inserts inside the read attribute right.
        ``stmt_trace`` is its trace: the unit records one `read:<table>`
        span there under ``parent_sid`` (the statement's `stage` span),
        carrying what THIS unit read and how long it spent in file reads
        and in CRC + decode (blockfile.ReadTally, bound to this thread)."""
        faults.check("cancel_in_staging", segment=seg)
        if stmt_ctx is not None:
            stmt_ctx.check()
        sid = (stmt_trace.begin("read:" + table, cat="stage",
                                parent=parent_sid, segment=seg,
                                column=",".join(storage_cols))
               if stmt_trace is not None else -1)
        with blockfile.tally() as io:
            try:
                with memaccount.ACCOUNTS.bind(stmt_acct):
                    c, v, n = self._read_segment_parts(
                        table, child_parts, seg, storage_cols, snapshot,
                        prune, dest=dest)
            finally:
                if sid >= 0:
                    stmt_trace.end(
                        sid, files=io.files, cache_hits=io.cache_hits,
                        bytes_read=io.bytes_read,
                        bytes_decoded=io.bytes_decoded,
                        io_ms=round(io.io_ns / 1e6, 3),
                        decode_ms=round(io.decode_ns / 1e6, 3))
        if rng is not None:
            a, b = rng
            c = {k: arr[a:b] for k, arr in c.items()}
            v = {k: (arr[a:b] if arr is not None else None)
                 for k, arr in v.items()}
            n = max(min(n, b) - a, 0)
        return c, v, n, (self.store.last_prune if prune else None)

    @staticmethod
    def _stage_dtype(schema, c) -> np.dtype:
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
                if col_s.type.kind == T.Kind.TEXT
                and col_s.encoding == "raw"
                else col_s.type.np_dtype)

    def _fill_column(self, schema, c, cap, per_seg, buffers) -> np.ndarray:
        """One column's [nseg*cap] host buffer, padded."""
        nseg = self.nseg
        if c.startswith(VALID_PREFIX):
            name = c[len(VALID_PREFIX):]
            return staging.fill_buffer(
                nseg, cap, np.dtype(bool),
                ((s, vv[name] if vv.get(name) is not None
                  else np.ones(n, dtype=bool))
                 for s, (_, vv, n) in enumerate(per_seg)), False)
        dt = self._stage_dtype(schema, c)
        buf = buffers.get(c) if buffers is not None else None
        if buf is None:
            return staging.fill_buffer(
                nseg, cap, dt,
                ((s, cc.get(c, np.zeros(0, dt)).astype(dt, copy=False))
                 for s, (cc, _, _) in enumerate(per_seg)), 0)
        for s, (cc, _, _) in enumerate(per_seg):
            arr = cc.get(c)
            n = 0 if arr is None else len(arr)
            if n and getattr(arr, "base", None) is not buf:
                buf[s * cap: s * cap + n] = arr
            if n < cap:
                buf[s * cap + n: (s + 1) * cap] = 0
        return buf

    def _dyn_pruned_parts(self, table, child_parts, dyn, snapshot) -> tuple:
        """-> child partitions surviving the build-side key-value probe
        (the execution-time PartitionSelector, nodePartitionSelector.c).
        Manifest-version cached; falls back to the full set on any
        irregularity (a missed prune is only a perf loss)."""
        version = snapshot.get("version", 0)
        ck = (table, child_parts, dyn, version)
        with self._cache_mu:
            cache = getattr(self, "_dyn_prune_cache", None)
            if cache is None:
                cache = self._dyn_prune_cache = {}
            hit = cache.get(ck)
        if hit is not None:
            self._last_dyn_stats[table] = (len(hit), len(child_parts))
            return hit
        dim_table, preds, key_col = dyn
        try:
            schema = self.catalog.get(table)
            dim_schema = self.catalog.get(dim_table)
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
        self._last_dyn_stats[table] = (len(kept), len(child_parts))
        with self._cache_mu:
            if len(cache) > 64:
                cache.pop(next(iter(cache)))
            cache[ck] = kept
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
            staged.append(self._put(np.concatenate(parts), shard, cap))
        present = np.concatenate(
            [_pad(np.ones(cn, dtype=bool), cap, False) for cn in counts])
        staged.append(self._put(present, shard, cap))
        return staged

    def _put(self, host: np.ndarray, shard, cap: int):
        """Place a [nseg*cap] host array onto the mesh. Multi-host: each
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

    # ------------------------------------------------------------------
    def _finalize(self, comp: CompileResult, flat, snapshot,
                  seg_slice=None, raw: bool = False) -> Result:
        # raw is an explicit parameter, never instance state: a lock-free
        # RETRIEVE finalizing concurrently with a DML's raw-mode run must
        # not flip the other call's decode behavior
        ncols = len(comp.out_cols)
        cap = comp.capacity
        sel = flat[2 * ncols].reshape(self.nseg, cap)
        cols_np = {}
        valids_np = {}
        if seg_slice is None:
            if comp.gather_child_locus.kind in (LocusKind.SEGMENT_GENERAL,
                                                LocusKind.GENERAL):
                seg_slice = [0]  # replicated: one copy suffices
            else:
                seg_slice = range(self.nseg)
        mask = np.concatenate([sel[s] for s in seg_slice])
        for i, c in enumerate(comp.out_cols):
            data = flat[2 * i].reshape(self.nseg, cap)
            valid = flat[2 * i + 1].reshape(self.nseg, cap)
            cols_np[c.id] = np.concatenate([data[s] for s in seg_slice])[mask]
            valids_np[c.id] = np.concatenate([valid[s] for s in seg_slice])[mask]
        return self._present(comp, cols_np, valids_np, snapshot, raw)

    def _present(self, comp: CompileResult, cols_np, valids_np, snapshot,
                 raw: bool) -> Result:
        """Host-side presentation of extracted row data: merge-sorted
        receive, host LIMIT, TEXT/decimal/date decode, Result assembly."""
        # host merge of per-segment sorted runs (Merge Receive analog)
        if comp.merge_keys:
            order = _host_sort_order(cols_np, valids_np, comp.merge_keys, self.store)
            for k in cols_np:
                cols_np[k] = cols_np[k][order]
                valids_np[k] = valids_np[k][order]
        if comp.host_limit is not None:
            limit, offset = comp.host_limit
            end = None if limit is None else offset + limit
            for k in cols_np:
                cols_np[k] = cols_np[k][offset:end]
                valids_np[k] = valids_np[k][offset:end]

        # decode TEXT + decimals for presentation (raw mode keeps storage
        # representation for DML republish paths)
        out_cols = {}
        out_valids = {}
        for c in comp.out_cols:
            data = cols_np[c.id]
            valid = valids_np[c.id]
            if raw or getattr(c, "hidden", False):
                out_cols[c.id] = data
                out_valids[c.id] = None if valid.all() else valid
                continue
            if c.type.kind is T.Kind.TEXT and getattr(c, "raw_ref", None) is not None:
                # raw TEXT: device carried row surrogates; decode from the
                # byte-blob storage now. NULL/padded rows carry garbage
                # surrogates — never dereference them.
                vals = np.empty(len(data), dtype=object)
                m = np.asarray(valid, bool)
                decoded = self.store.fetch_raw(
                    c.raw_ref[0], c.raw_ref[1], data[m], snapshot)
                if getattr(c, "raw_chain", None):
                    from greengage_tpu.utils import strfuncs

                    decoded = np.array(
                        [strfuncs.apply_chain(s, c.raw_chain)
                         for s in decoded], dtype=object)
                vals[m] = decoded
                out_cols[c.id] = vals
            elif c.type.kind is T.Kind.TEXT and c.dict_ref is not None:
                d = self.store.dictionary(*c.dict_ref)
                vals = np.array(
                    [d.values[x] if 0 <= x < len(d) else None for x in data], dtype=object)
                out_cols[c.id] = vals
            elif c.type.kind is T.Kind.DECIMAL:
                out_cols[c.id] = data / (10.0 ** c.type.scale)
            elif c.type.kind is T.Kind.DATE:
                out_cols[c.id] = (np.datetime64("1970-01-01", "D")
                                  + data.astype("timedelta64[D]"))
            else:
                out_cols[c.id] = data
            out_valids[c.id] = None if valid.all() else valid
        visible = [c for c in comp.out_cols if not getattr(c, "hidden", False)]
        return Result(
            columns=[c.name for c in visible],
            cols=out_cols,
            valids=out_valids,
            _order=[c.id for c in visible],
        )


def _pad(arr: np.ndarray, cap: int, fill=0) -> np.ndarray:
    if len(arr) == cap:
        return arr
    out = np.full(cap, fill, dtype=arr.dtype)
    out[: len(arr)] = arr
    return out


def _host_sort_order(cols, valids, merge_keys, store) -> np.ndarray:
    """Stable numpy lexsort matching ops/sort.py semantics."""
    from greengage_tpu import expr as E

    keys = []  # mirror of ops/sort._order_encode, in numpy
    for e, desc, nulls_first in merge_keys:
        if not isinstance(e, E.ColRef):
            raise QueryError("merge sort key must be an output column")
        v = cols[e.name]
        valid = valids.get(e.name)
        if e.type.kind is T.Kind.TEXT:
            dref = getattr(e, "_dict_ref", None)
            if dref is not None:
                dic = store.dictionary(*dref)
                rank = np.argsort(np.argsort(dic.values, kind="stable"), kind="stable")
                ints = np.concatenate([rank.astype(np.int64), [np.int64(-1)]])[v]
            else:
                ints = v.astype(np.int64)
            enc = ints.view(np.uint64) ^ (np.uint64(1) << np.uint64(63))
        elif e.type.kind is T.Kind.FLOAT64:
            bits = np.ascontiguousarray(v, dtype=np.float64).view(np.uint64)
            enc = np.where(bits >> np.uint64(63) == 1, ~bits,
                           bits | np.uint64(1) << np.uint64(63))
        else:
            enc = v.astype(np.int64).view(np.uint64) ^ (np.uint64(1) << np.uint64(63))
        if desc:
            enc = ~enc
        nf = nulls_first if nulls_first is not None else desc
        if valid is not None:
            nullkey = np.where(valid, 0, -1 if nf else 1).astype(np.int8)
            enc = np.where(valid, enc, np.uint64(0))
        else:
            nullkey = np.zeros(len(enc), dtype=np.int8)
        keys.append((nullkey, enc))
    lex = []
    for nullkey, enc in reversed(keys):
        lex.append(enc)
        lex.append(nullkey)
    if not lex:
        return np.arange(len(next(iter(cols.values()))))
    return np.lexsort(lex)
