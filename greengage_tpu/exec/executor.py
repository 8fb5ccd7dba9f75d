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

import time
from dataclasses import dataclass, field

import numpy as np

import jax

from greengage_tpu import types as T
from greengage_tpu.exec import spill
from greengage_tpu.exec.compile import (SEMI_COUNTERS, SUMMED_METRICS,
                                        CompileResult)
from greengage_tpu.exec.programs import ProgramCache, Unsignable
from greengage_tpu.exec.staging import Staged, Stager
from greengage_tpu.planner.locus import LocusKind
from greengage_tpu.runtime import interrupt
from greengage_tpu.runtime import memaccount
from greengage_tpu.runtime import overload as _overload
from greengage_tpu.runtime import trace as _trace
from greengage_tpu.runtime.faultinject import faults
from greengage_tpu.runtime.logger import (DEFAULT_BUCKETS_MB, counters,
                                          histograms)
from greengage_tpu.runtime.runaway import TRACKER


def _span_ms(sid) -> float | None:
    """Duration of a closed span of the calling thread's trace."""
    tr = _trace.TRACES.current()
    spans = tr.subtree(sid) if tr is not None and sid is not None else []
    return spans[0]["dur"] if spans else None


class QueryError(RuntimeError):
    pass


class AdmissionError(QueryError):
    """Raised ONLY for the vmem admission rejection (est_bytes > limit) —
    the signal the spill machinery keys its escalation on."""
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


@dataclass
class _Statement:
    """One Executor.run: what its attempts share."""
    plan: object
    consts: dict
    out_cols: list
    cache_key: object = None
    raw: bool = False
    instrument: bool = False
    aux_tables: dict | None = None
    deferred: bool = False
    # a spill pass (run_single) scans a slice; it is never cached, never
    # spills again, and forces the general hash join (a direct-addressed
    # build allocates its FULL key domain however small the chunk)
    scan_cap_override: dict | None = None
    row_ranges: dict | None = None
    spill_pass: bool = False
    # filled by _run_tiers
    t0: float = 0.0
    snapshot: dict | None = None
    hints: dict = field(default_factory=dict)
    cap_overrides: dict = field(default_factory=dict)   # grown by retries
    pack_disabled: set = field(default_factory=set)
    expand_retries: int = 0   # attempts a multi join's expansion overflowed


@dataclass
class _Attempt:
    """One pass of the tier loop, as the statistics report it."""
    tier: int
    comp: CompileResult
    was_cached: bool
    compile_ms: float
    admit_bytes: int = 0
    admit_measured: bool = False
    staged: Staged | None = None
    compute_ms: float = 0.0
    fetch_ms: float = 0.0


class Executor:
    """An attempt is four steps, each written once (docs/ARCHITECTURE.md):
    find or compile the program (``programs``), admit it, stage its inputs
    (``stager``), dispatch. ``run`` loops over attempts; batched serving
    (exec/batchserve.py) calls the same four."""

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
        # promotion). Read through _feedback(): single-host only.
        self.feedback = None
        self.programs = ProgramCache(store, mesh, nseg, settings,
                                     multihost is not None)
        self.stager = Stager(store, mesh, nseg, settings, multihost)
        self.spill_schedule = spill.ScheduleRecorder(multihost)

    def _feedback(self):
        """The feedback store where it may steer: its state is
        per-process and must not decide lockstep branches of a gang."""
        return self.feedback if self.multihost is None else None

    # ------------------------------------------------------------------
    def run(self, plan, consts: dict, out_cols, cache_key=None,
            raw: bool = False, instrument: bool = False, aux_tables=None,
            deferred: bool = False) -> Result:
        return self._run_tiers(_Statement(
            plan, consts, out_cols, cache_key, raw=raw, instrument=instrument,
            aux_tables=aux_tables, deferred=deferred))

    def run_single(self, plan, consts, out_cols, raw=False, instrument=False,
                   aux_tables=None, scan_cap_override=None, row_ranges=None):
        """One spill pass: no recursive spilling, no plan caching.
        ``instrument`` flows through so EXPLAIN ANALYZE of a spilling
        statement still collects per-node row counts (summed across
        passes by the spill driver)."""
        return self._run_tiers(_Statement(
            plan, consts, out_cols, raw=raw, instrument=instrument,
            aux_tables=aux_tables, scan_cap_override=scan_cap_override,
            row_ranges=row_ranges, spill_pass=True))

    def _run_tiers(self, st: _Statement) -> Result:
        """The attempts of one statement: find or compile, admit, stage,
        dispatch, classify the flags, grow the capacities. Tiers grow
        capacities; a key-packing bounds violation (stale ANALYZE stats)
        instead re-runs the SAME tier unpacked, so the attempt bound
        covers both kinds of retry."""
        st.t0 = time.monotonic()
        st.snapshot = self.store.manifest.snapshot()
        st.hints = self.programs.hints(st.cache_key, self._feedback())
        st.cap_overrides = dict(st.hints)
        # hoisted-literal parameter vector (sql/paramize.py): values feed
        # the program as traced inputs and resolve pushed prune predicates
        pvec = (st.consts or {}).get("@params@")
        retry_tiers = self.settings.motion_retry_tiers
        tier = attempts = 0
        last_err = None
        TRACKER.enter()   # nested spill passes share the statement entry
        try:
            while tier < retry_tiers and attempts < retry_tiers + 4:
                attempts += 1
                # retry-tier boundary = a CHECK_FOR_INTERRUPTS site: a flag
                # set while the previous attempt ran (user cancel, statement
                # timeout, runaway cleaner) terminates the statement here
                interrupt.check_interrupts()
                # hints are deterministic inputs folded into the shape
                # signature (they size capacities); only RUNTIME overrides
                # (an overflow retry in flight) and a pass's slice disable
                # caching
                cacheable = st.cap_overrides == st.hints and not st.row_ranges
                at = _Attempt(tier, *self._program(
                    st, st.cache_key if cacheable else None, tier))
                spilled = self._admit(st, at)
                if spilled is not None:
                    return spilled
                at.staged = self.stager.stage(
                    at.comp, st.snapshot, pvec, _param_hosts(at.comp, pvec),
                    st.row_ranges, st.aux_tables)
                t_compute = time.monotonic()
                # last cancellation point before dispatch: once the program
                # is on the device it runs to this boundary (the documented
                # semantic — XLA programs cannot be preempted mid-flight)
                faults.check("cancel_before_dispatch")
                interrupt.check_interrupts()
                try:
                    flat, t_fetch, t_end = self.dispatch(
                        at.comp, at.staged.inputs, st.cache_key,
                        _device_oom_fault, tier=tier)
                except Exception as e:
                    if memaccount.is_oom_error(e):
                        # OOM forensics + demotion (memaccounting.c's
                        # RESOURCE_EXHAUSTED dump): never a bare XLA
                        # traceback for an allocator refusal
                        return self._handle_oom(e, st, at)
                    raise
                at.compute_ms = (t_fetch - t_compute) * 1e3
                at.fetch_ms = (t_end - t_fetch) * 1e3
                overflow, metrics = _flags_and_metrics(at.comp, flat)
                if not overflow:
                    if st.cache_key is not None and at.comp.flag_caps \
                            and attempts > 1:
                        # written only after an overflow retry (ROADMAP
                        # D7): a hint for a statement whose estimates
                        # sufficed would re-size the program just cached
                        # and make the next run compile again (minutes
                        # for a join on the TPU)
                        self.programs.record_hints(
                            st.cache_key,
                            {nid: self._peak(metrics[m])
                             for nid, m in at.comp.flag_caps.values()
                             if m in metrics}, self._feedback())
                    if st.deferred:
                        # parallel retrieve cursor: the program already ran
                        # and every segment's shard is on the host —
                        # finalization happens per-endpoint at RETRIEVE time
                        return EndpointBatch(at.comp, flat, st.snapshot,
                                             st.raw, self.nseg)
                    return self._finish(st, at, flat, metrics)
                tier = self._grow(st, at.comp, overflow, metrics, tier)
                last_err = f"capacity overflow in {overflow} at tier {tier}"
            raise QueryError(f"query exceeded capacity tiers: {last_err}")
        finally:
            TRACKER.release()

    def _program(self, st: _Statement, cache_key, tier: int):
        """Step one for the classic loop -> find_or_compile's triple; a
        shape that cannot be signed compiles uncached."""
        try:
            return self.programs.find_or_compile(
                cache_key, st.plan, st.consts, st.snapshot, tier,
                st.cap_overrides, no_direct=st.spill_pass, uncached=dict(
                    instrument=st.instrument,
                    scan_cap_override=st.scan_cap_override,
                    aux_tables=st.aux_tables,
                    pack_disabled=st.pack_disabled))
        except Unsignable:
            return self._program(st, None, tier)

    def _peak(self, v) -> int:
        """A capacity metric: the per-segment max (multi-host: already
        device-reduced + replicated, so every process records and sizes
        identically and stays in lockstep)."""
        return int(v.flat[0]) if self.multihost else int(np.max(v))

    def _metric(self, name: str, v) -> int:
        """A program metric as one number: row counts (SUMMED_METRICS) sum
        over the segments, capacity metrics report the fullest one's."""
        if self.multihost or not name.startswith(SUMMED_METRICS):
            return self._peak(v)
        return int(np.sum(v))

    def _grow(self, st: _Statement, comp, overflow, metrics, tier) -> int:
        """Size the retry from the exact cardinalities the device reported
        -> the tier to run next."""
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
            st.pack_disabled.add(comp.flag_packs[fname])
        st.expand_retries += any(
            f.startswith("join_expand_overflow") for f in capacity_over)
        for fname in capacity_over:
            hint = comp.flag_caps.get(fname)
            if hint is not None:
                plan_id, metric = hint
                need = self._peak(metrics[metric])
                st.cap_overrides[plan_id] = need + max(need // 16, 64)
        # a compaction overflow (before the Gather, or of a build side
        # or a sort-aggregate's input) carries its exact live count in
        # the cap override — re-run the SAME tier with just that slice
        # widened; bumping the tier would needlessly 4x every other
        # node and disable tier-0 direct joins (advisor r3)
        if [f for f in capacity_over
                if not f.startswith(("gather_compact_overflow",
                                     "compact_overflow"))]:
            tier += 1
        return tier

    def _finish(self, st: _Statement, at: _Attempt, flat, metrics) -> Result:
        """The successful attempt's Result, its statistics and the
        per-phase histograms."""
        comp, staged = at.comp, at.staged
        with _trace.span("finalize", cat="host") as _sp_fin:
            res = self.finalize(comp, flat, st.snapshot, raw=st.raw)
        res.wall_ms = (time.monotonic() - st.t0) * 1e3
        finalize_ms = _span_ms(_sp_fin)
        compile_ms = at.compile_ms
        if not at.was_cached:
            # the first dispatch of a fresh program carries the
            # XLA compile; fold it into the statement's compile
            # cost (EXPLAIN ANALYZE "Plan cache" line, bench)
            compile_ms += at.compute_ms
            counters.inc("compile_ms", int(compile_ms))
        for _mid, (_cap, _slots) in comp.agg_caps.items():
            # how full the sort-based aggregates' group tables ran, and
            # how many slots each sorted to find its groups
            counters.inc("agg_sort_groups", int(np.max(metrics[_mid])))
            counters.inc("agg_sort_capacity", int(_cap))
            counters.inc("agg_sort_input_slots", int(_slots))
            if _mid in comp.agg_direct:
                counters.inc("agg_sort_capacity_direct", int(_cap))
        if comp.join_gather_slots:
            # the slots the inner and left joins gathered build columns into
            counters.inc("join_gather_slots", sum(comp.join_gather_slots))
        if comp.expand_caps:
            # attempts run again because a pair expansion overflowed
            counters.inc("join_expand_retries", st.expand_retries)
        for _mid, (_cap, _mid_null) in comp.expand_caps.items():
            # how full the multi joins' pair expansions ran, and the probe
            # rows their LEFT joins null-extended
            counters.inc("join_expand_rows", self._metric(_mid, metrics[_mid]))
            counters.inc("join_expand_capacity", int(_cap))
            if _mid_null is not None:
                counters.inc("join_null_extended_rows",
                             self._metric(_mid_null, metrics[_mid_null]))
        for _mid in comp.metric_names:
            if _mid.startswith(SEMI_COUNTERS):
                # what the semi and anti joins took and kept: metric
                # semi_<what>_<n> adds to counter semi_<what>
                _what = _mid[len("semi_"):].rsplit("_", 1)[0]
                counters.inc(f"semi_{_what}", self._metric(_mid, metrics[_mid]))
        res.stats = {
            "tiers_used": at.tier + 1,
            "compiled": not at.was_cached,
            # 0: admitted whole; the spill paths overwrite it with
            # the passes they ran (_spill_fallback)
            "spill_passes": 0,
            "compile_ms": round(compile_ms, 1),
            # host-data-path breakdown of the SUCCESSFUL attempt
            "stage_ms": round(staged.stage_ms, 2),
            "compute_ms": round(at.compute_ms, 2),
            "fetch_ms": round(at.fetch_ms, 2),
            # where stage_ms went, as sums of the trace's own span
            # durations (a stat and `gg trace` cannot disagree);
            # absent when the statement is not traced
            **staged.split,
            **({} if finalize_ms is None
               else {"finalize_ms": finalize_ms}),
            "scan_io": staged.scan_io,
            # read units, and those that landed in their staging slots
            **staged.units,
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
            "zone_prune": dict(staged.zone_prune),
            # runtime PartitionSelector results: child partitions
            # kept / total after the build-side key-value probe
            "dynamic_prune": dict(staged.dynamic_prune),
            "below_gather_capacity": comp.capacity,
            "rows_out": len(res),
            # per-node row counters SUM across segments; capacity
            # metrics report the per-segment max (multi-host:
            # already device-reduced + replicated)
            "metrics": {k: self._metric(k, v) for k, v in metrics.items()},
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
            "mem": self._mem_stats(comp, at.admit_bytes, at.admit_measured),
        }
        if st.instrument:
            # per-node Memory annotation source (EXPLAIN ANALYZE)
            res.stats["node_est_bytes"] = dict(comp.node_est_bytes)
            # ... and of its measured device time: which plan node each
            # label of the device trace is, and which program ran (the
            # instrumented one is never cached: the caller reads its
            # node map through this reference or not at all)
            res.stats["node_labels"] = dict(comp.node_labels)
            res.stats["program"] = comp
        # latency histograms (the gpperfmon timing surface):
        # per-phase host-data-path distributions, exposed as
        # Prometheus histograms via `gg metrics`
        histograms.observe("stage_ms", staged.stage_ms)
        histograms.observe("dispatch_ms", at.compute_ms)
        histograms.observe("fetch_ms", at.fetch_ms)
        if not at.was_cached:
            # compile_latency_ms, NOT compile_ms: the legacy
            # total-ms counter already owns that name and one
            # exposition name cannot carry two TYPEs
            histograms.observe("compile_latency_ms", compile_ms)
        return res

    def finalize_endpoint(self, batch: "EndpointBatch", seg: int) -> Result:
        """RETRIEVE body: decode ONE segment's compacted shard of a
        deferred run (the retrieve-session path, reference: src/backend/
        cdb/endpoint/cdbendpointretrieve.c — there a direct segment
        connection, here a host-side per-shard decode)."""
        cols, valids = batch.segs[seg]
        # shallow dict copies: _present reassigns dict slots (merge/limit)
        return self._present(batch.comp, dict(cols), dict(valids),
                             batch.snapshot, batch.raw)

    # ---- step two: admission -----------------------------------------
    def _admit(self, st: _Statement, at: _Attempt) -> Result | None:
        """Charge the attempt's program against the memory ceiling:
        admitted (``at.admit_*`` say by what) -> None; over the ceiling it
        spills (-> the spilled Result) or is refused (raises)."""
        comp = at.comp
        limit = effective_limit_bytes(self.settings)
        if self.multihost is None:
            # memory-pressure brownout (runtime/overload.py): scale
            # the admission ceiling down so borderline statements
            # demote to the spill tier instead of racing a pressured
            # allocator. Single-host only — the factor is
            # process-local state and would desync the multihost
            # lockstep spill decision (est_bytes + settings only)
            limit = _overload.CONTROLLER.scaled_vmem(limit)
        # the MEASURED per-segment executable footprint when the
        # executable is warm and the backend reports real temps, else
        # the compile-time estimate (admission_bytes)
        admit_bytes, measured = self.admission_bytes(comp, st.cache_key)
        if limit and admit_bytes > limit and not measured \
                and self._measure_unstaged(comp, st.aux_tables):
            # the ESTIMATE was about to refuse or spill a statement no
            # one has measured: it sums every plan node's batch as if
            # all were alive at once, and XLA knows better. Ask it
            # (one compile, which the dispatch then reuses) and let
            # the measurement decide.
            admit_bytes, measured = self.admission_bytes(comp, st.cache_key)
        if limit and admit_bytes > limit:
            if st.deferred:
                raise QueryError(
                    f"parallel retrieve cursor would hold ~"
                    f"{admit_bytes >> 20} MB per segment, above the "
                    f"{limit >> 20} MB memory ceiling; cursors pin the "
                    "whole result and cannot spill")
            if not st.spill_pass:
                # host-offload spill (exec/spill.py): partition a
                # probe-linear (or inner-join build) table into passes
                # that fit, merge the captured partial states /
                # deduped keys on a final pass. Multihost-safe: the
                # pass decision is deterministic (est_bytes +
                # settings) and every process gathers identical
                # replicated results, so workers take the same
                # branches in lockstep.
                try:
                    return self._spill_fallback(st)
                except spill.NotSpillable:
                    raise QueryError(
                        f"query would allocate ~{admit_bytes >> 20} MB "
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
                int(getattr(self.settings, "vmem_global_limit_mb", 0)) << 20,
                float(getattr(self.settings, "runaway_red_zone", 0.9)),
                measured=measured)
            TRACKER.check()
        at.admit_bytes, at.admit_measured = admit_bytes, measured
        return None

    def admission_bytes(self, comp: CompileResult,
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
                and ma.get("temp_bytes", 0) > 0 \
                and memaccount.device_memory_stats() is not None:
            # memory_analysis describes the per-DEVICE SPMD module (one
            # device's shard of every buffer): scale to per-segment by
            # the segments each device hosts, not by nseg — on a 1-chip
            # backend all nseg segments share the device
            measured = _analysis_total(comp) // self._segments_per_device()
            if measured > 0:
                counters.inc("admission_measured_total")
                return measured, True
        if ma is None and cache_key is not None \
                and self._feedback() is not None \
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

    def _measure_unstaged(self, comp: CompileResult, aux_tables) -> bool:
        """Compile ``comp`` from the shapes of its inputs alone, so that
        admission can read XLA's memory analysis before anything is staged.
        Only where a measurement could govern (``admission_bytes``: one
        host, a backend with a real allocator) and only for a program of
        base-table scans. -> whether ``comp.mem_analysis`` now holds one."""
        if comp.mem_analysis is not None:
            return True
        if self.multihost is not None or comp.batch_width \
                or memaccount.device_memory_stats() is None \
                or any(t in (aux_tables or {}) for t, *_ in comp.input_spec):
            return False
        with _trace.span("compile", cat="plan", unstaged=True):
            self._ensure_mem_analysis(comp, self.stager.shapes(comp))
        return comp.mem_analysis is not None

    def _ensure_mem_analysis(self, comp: CompileResult, inputs) -> None:
        """First dispatch of a program: AOT-compile it (lower().compile())
        and attach XLA's memory_analysis — temp/argument/output/generated-
        code bytes — to the cached CompileResult. Dispatch then goes
        through the AOT executable, so the program still compiles exactly
        once (the AOT call path measures no slower than the jit wrapper),
        and every warm program-cache hit reuses both the executable and
        the analysis: ``mem_analysis_runs`` counts analyses, and tests
        assert a warm hit adds zero."""
        if comp.mem_failed or comp.aot_fn is not None:
            return
        if self.multihost is not None:
            # multihost keeps the plain jit path: an AOT executable pins
            # the compile-time device assignment, and the PR-6 topology
            # re-formation contract depends on pjit re-binding cached
            # executables to the CURRENT mesh at call site; per-process
            # analysis state would also leak into admission and desync
            # the lockstep branch decisions (see admission_bytes)
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
            total = _analysis_total(comp)
            histograms.observe("executable_mem_mb", total / 1e6,
                               buckets=DEFAULT_BUCKETS_MB)
            # estimate-vs-measured calibration gauge: the analysis is
            # per DEVICE (one SPMD module), so compare against the
            # estimate for the segments that device hosts
            est_dev = comp.est_bytes * self._segments_per_device()
            if est_dev > 0:
                counters.set("mem_est_error_pct", int(round(
                    100.0 * (total - est_dev) / est_dev)))

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

    def _spill_fallback(self, st: _Statement):
        """Host-offload spill paths, shared by the admission rejection
        and the OOM demotion: partial-aggregate passes first, then
        window-partition passes over the PARTITION BY hash space, then
        the external-merge sort. Raises spill.NotSpillable through when
        no shape applies."""
        args = (self, st.plan, st.consts, st.out_cols, st.raw)
        try:
            res, npasses = spill.spill_run(*args, instrument=st.instrument)
        except spill.NotSpillable:
            try:
                # window-partition spill (exec/spill.py spill_window_run):
                # whole partitions per hash bucket, exact results
                res, npasses = spill.spill_window_run(
                    *args, instrument=st.instrument)
            except spill.NotSpillable:
                # external-merge sort spill (tuplesort role): ORDER BY
                # results merge on the host from per-pass device-sorted
                # runs
                res, npasses = spill.spill_sort_run(
                    *args, instrument=st.instrument)
        res.stats = dict(res.stats or {})
        res.stats["spill_passes"] = npasses
        return res

    def _handle_oom(self, e, st: _Statement, at: _Attempt):
        """A dispatched program hit RESOURCE_EXHAUSTED: build the typed
        OutOfDeviceMemory (accounting snapshot + the executable's memory
        analysis — the memaccounting.c OOM dump payload), then demote to
        the spill path ONCE when allowed (oom_spill_retry) before
        surfacing. Multihost never demotes: a one-sided runtime OOM is
        not a deterministic input, and a lone process entering the spill
        regime would desync the lockstep collectives."""
        comp = at.comp
        counters.inc("oom_events")
        acct = memaccount.ACCOUNTS.current()
        snap = acct.snapshot() if acct is not None else {}
        snap["device_stats"] = memaccount.device_memory_stats()
        oom = OutOfDeviceMemory(
            f"out of device memory dispatching at tier {at.tier} "
            f"(estimated ~{comp.est_bytes >> 20} MB/segment): {e}",
            snapshot=snap, mem_analysis=comp.mem_analysis,
            est_bytes=comp.est_bytes)
        if not st.spill_pass and not st.deferred and self.multihost is None \
                and bool(getattr(self.settings, "oom_spill_retry", True)):
            try:
                res = self._spill_fallback(st)
            except spill.NotSpillable:
                raise oom from e
            counters.inc("oom_spill_retries")
            res.stats["oom_demoted"] = True
            return res
        raise oom from e

    # ---- step four: dispatch -----------------------------------------
    def dispatch(self, comp: CompileResult, inputs, fb_key, fault=None,
                 **span_args) -> tuple[list, float, float]:
        """Run a program over its staged inputs and fetch every output to
        the host -> (outputs, when the device was done, when the fetch
        was: time.monotonic()). ``fb_key`` is the feedback-store key its
        measured bytes are noted under; ``fault(comp)`` runs inside the
        `dispatch` span, before the program (the callers' injection
        points); ``span_args`` go onto that span, beside ``program``: the
        id under which runtime/devprofile finds this program's node map."""
        # measured memory accounting: AOT-compile once, attach XLA's
        # memory_analysis to the cached executable (warm hits reuse
        # it — zero re-analysis), and record the device owner on the
        # statement's account before the allocator commits to it
        self._ensure_mem_analysis(comp, inputs)
        fb = self._feedback()
        if fb is not None and fb_key is not None and comp.mem_analysis:
            total = _analysis_total(comp)
            # warm-shape calibration gauge: once the feedback store
            # predicts this shape's footprint (second execution on),
            # report the error of the PREDICTION, not of the planner
            # estimate — this is what collapses toward 0 warm
            pred = fb.measured_bytes(fb_key)
            if pred:
                counters.set("mem_est_error_pct", int(round(
                    100.0 * (total - pred) / pred)))
            fb.note_measured(fb_key, total,
                             comp.est_bytes * self._segments_per_device())
        acct = memaccount.ACCOUNTS.current()
        if acct is not None:
            acct.set_device(comp.mem_analysis, comp.est_bytes)
        with _trace.span("dispatch", cat="device", est_bytes=comp.est_bytes,
                         program=comp.program_id, **span_args):
            if fault is not None:
                fault(comp)
            flat = (comp.aot_fn or comp.device_fn)(*inputs)
            # resolve async dispatch here so compute_ms is the device
            # program and a device failure surfaces at the dispatch,
            # not in device_get
            jax.block_until_ready(flat)
        t_fetch = time.monotonic()
        # ONE device->host fetch for every output (small results pay
        # per-transfer latency, not per-byte cost)
        with _trace.span("fetch", cat="device") as _sp_f:
            flat = jax.device_get(list(flat))
        t_end = time.monotonic()
        _trace.annotate(_sp_f, bytes=int(sum(
            getattr(a, "nbytes", 0) for a in flat)))
        return flat, t_fetch, t_end

    # ------------------------------------------------------------------
    def finalize(self, comp: CompileResult, flat, snapshot,
                 raw: bool = False) -> Result:
        # raw is an explicit parameter, never instance state: a lock-free
        # RETRIEVE finalizing concurrently with a DML's raw-mode run must
        # not flip the other call's decode behavior
        ncols = len(comp.out_cols)
        cap = comp.capacity
        sel = flat[2 * ncols].reshape(self.nseg, cap)
        cols_np = {}
        valids_np = {}
        seg_slice = range(self.nseg)
        if comp.gather_child_locus.kind in (LocusKind.SEGMENT_GENERAL,
                                            LocusKind.GENERAL):
            seg_slice = [0]  # replicated: one copy suffices
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


def _param_hosts(comp: CompileResult, pvec) -> list:
    """One (1,)-array a hoisted-literal parameter slot, in slot order."""
    if not comp.param_dtypes:
        return []
    return [np.asarray([v], dtype=dt)
            for v, dt in zip(pvec.values, comp.param_dtypes)]


def _device_oom_fault(comp: CompileResult) -> None:
    if faults.check("device_oom"):
        # faked allocator failure ('skip' type): the OOM
        # classification/demotion path without needing a real 16 GB
        # exhaustion in CI
        raise RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
            f"{comp.est_bytes} bytes (fault injected: device_oom)")


def _analysis_total(comp: CompileResult) -> int:
    ma = comp.mem_analysis
    return (ma["temp_bytes"] + ma.get("argument_bytes", 0)
            + ma.get("output_bytes", 0))


def _flags_and_metrics(comp: CompileResult, flat) -> tuple[list, dict]:
    """A fetched program's (names of the overflow flags set, metrics)."""
    ncols = len(comp.out_cols)
    nflags = len(comp.flag_names)
    flags = dict(zip(comp.flag_names,
                     flat[2 * ncols + 1: 2 * ncols + 1 + nflags]))
    metrics = dict(zip(comp.metric_names, flat[2 * ncols + 1 + nflags:]))
    if any(k.startswith("join_dup") and v.any() for k, v in flags.items()):
        raise QueryError(
            "hash join build side has duplicate keys; only unique-key "
            "(PK-FK) hash joins are supported in this version")
    return [k for k, v in flags.items()
            if not k.startswith("join_dup") and v.any()], metrics



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
