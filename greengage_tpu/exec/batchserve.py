"""Vectorized serving — batch concurrent same-shape statements into one
XLA dispatch behind an async executor pipeline (docs/PERF.md
"Vectorized serving").

The QD/QE split amortizes planning across many executors; on TPU the
analogous lever is amortizing *dispatch* across many concurrent users. A
serving workload is dominated by repeated statement shapes with varying
literals, and PR 5 already reduced those to ONE executable keyed on the
literal-stripped signature with the literals as traced ``(1,)``-scalar
parameters. This module gives that parameter vector a batch axis:

  * **admission window** — the session-side intake collects in-flight
    statements sharing one plan-cache key (statement signature — which
    pins the shape signature at a given manifest version) for up to
    ``batch_window_ms``, or until ``batch_max_width`` members arrive. An
    idle pipeline flushes immediately, so the window costs latency only
    while the device is busy — exactly when the wait is free.
  * **one dispatch** — members' parameter vectors stack along a leading
    member axis and a width-bucketed batched program (compile.py
    ``batch_width``: the member body vmapped over the stacked params)
    runs ONCE over the shared staged inputs. Widths bucket to pow2 and
    the bucket joins the executor's program-cache key, so serving widths
    1..max_width costs log2(max_width) compiles, not max_width.
  * **pipelined stages** — a stager thread and a dispatcher thread
    connected by a queue: batch k+1 stages (host reads, PR-3 staging
    pool) while batch k runs on the device. Neither thread carries a
    statement context, so no member's cancellation can abort the batch.
  * **per-member demux** — each member's result slice finalizes exactly
    like a classic dispatch; a member cancelled mid-flight is masked out
    at demux (its thread raises the typed ``StatementCancelled``) and
    its batch-mates' results are untouched.
  * **observability** — every flush records a standalone trace (a
    ``batch-dispatch`` root with compile/stage/dispatch/fetch children
    plus one ``batch-member`` child per member) retired into the trace
    ring under a negative id AND grafted into every member's statement
    trace, so one flame graph shows the whole batch. Counters:
    ``batch_dispatch_total`` / ``batch_members_total`` /
    ``batch_window_flush_{full,timer}`` / ``batch_fallback_total``, the
    ``batch_width`` histogram, and the ``batch_queue_depth`` gauge.

Any batch that cannot run as one program — admission ceiling, overflow
flags, an unsignable shape — falls back: every member re-runs serially
through the classic executor path, which owns retries and spill. The
fallback is a routing decision, never a client-visible error.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
from collections import OrderedDict, deque

import numpy as np

from greengage_tpu.exec.compile import CompileResult, _pow2
from greengage_tpu.exec.executor import Result, effective_limit_bytes
from greengage_tpu.exec.programs import Unsignable
from greengage_tpu.runtime import trace as _trace
from greengage_tpu.runtime.faultinject import faults
from greengage_tpu.runtime.interrupt import REGISTRY as _INTERRUPTS
from greengage_tpu.runtime.logger import counters, histograms
from greengage_tpu.runtime.trace import TRACES, Trace

# batch widths are small pow2s, not latencies: explicit buckets
WIDTH_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0)

# hard ceiling on a member's wait for its flush — a stalled pipeline must
# degrade to serial execution, never to a hung client connection
_STALL_TIMEOUT_S = 600.0


class BatchFallback(Exception):
    """A batched-serving window cannot run as one program (admission
    ceiling, overflow flags, unsignable shape): every member re-runs
    serially through the classic path, which owns retries and spill.
    Never surfaces to a client — it only routes execution."""


# ---- one batch through the executor's four steps ---------------------
# One XLA dispatch serves a whole admission window of same-shape
# statements: their hoisted parameter vectors stack along a leading
# member axis and the width-bucketed batched program (compile.py
# batch_width) runs once over the shared staged inputs. Split into
# prepare (find or compile, admit, stage) and Executor.dispatch so the
# pipeline can stage batch k+1 while batch k runs on device.

def prepare_batch(ex, plan, consts, cache_key, pvec_rows):
    """-> (comp, inputs, snapshot, compiled: bool) of the width-bucketed
    batched program. Raises BatchFallback when the batch cannot run as
    one program (admission ceiling, unsignable shape)."""
    width = len(pvec_rows)
    bucket = _pow2(max(width, 1))
    snapshot = ex.store.manifest.snapshot()
    try:
        # no persisted hints here, as there never were (ROADMAP D7)
        comp, was_cached, compile_ms = ex.programs.find_or_compile(
            cache_key, plan, consts, snapshot, 0,
            ex.programs.hints(cache_key), batch_width=bucket)
    except Unsignable:
        raise BatchFallback("unsignable statement shape") from None
    if not was_cached:
        counters.inc("compile_ms", int(compile_ms))
    # admission: est_bytes is already width-scaled (compile.py); the
    # measured footprint of a warm bucket takes over once the AOT
    # analysis ran — ground truth bounding the batch width
    limit = effective_limit_bytes(ex.settings)
    if cache_key is not None:
        # width-bucket-qualified feedback key: est/measured bytes are
        # width-scaled, so each bucket calibrates independently
        comp.fb_key = f"{cache_key}@w{bucket}"
    admit_bytes, _measured = ex.admission_bytes(comp, comp.fb_key)
    if limit and admit_bytes > limit:
        raise BatchFallback(
            f"batched program would hold ~{admit_bytes >> 20} MB "
            f"per segment at width {bucket}, above the "
            f"{limit >> 20} MB ceiling")
    # staged like a classic statement except that parameter-valued prune
    # predicates are DROPPED (pvec None): zone-map pruning by one
    # member's values would starve its batch-mates of blocks their rows
    # live in. Value-pinned prune predicates are shared by every member
    # and stay active.
    padded = list(pvec_rows) + [pvec_rows[-1]] * (bucket - width)
    staged = ex.stager.stage(comp, snapshot, None, [
        np.asarray([[pv.values[slot]] for pv in padded], dtype=dt)
        for slot, dt in enumerate(comp.param_dtypes)])
    _trace.annotate(staged.sid, batch_width=width, batch_bucket=bucket)
    return comp, staged.inputs, snapshot, not was_cached


def dispatch_batch(ex, comp: CompileResult, inputs) -> list:
    """Run a prepared batched program and fetch every output to host.
    The serving pipeline's device stage — runs on the dispatcher thread
    with NO statement context, so a member's cancellation can never
    abort its batch-mates (members are masked at demux)."""
    return ex.dispatch(comp, inputs, comp.fb_key,
                       lambda _comp: faults.check("batch_dispatch"),
                       batch_width=comp.batch_width)[0]


def batch_overflowed(comp: CompileResult, flat) -> list[str]:
    """Flag names any member tripped — capacity overflow, packing
    bounds, duplicate join keys. A batched program never retries in
    place (per-member capacity needs differ); any flag sends every
    member down the serial path, whose tier machinery handles it."""
    ncols_part = 2 * len(comp.out_cols) + 1
    return [name for j, name in enumerate(comp.flag_names)
            if np.asarray(flat[ncols_part + j]).any()]


def demux_batch(ex, comp: CompileResult, flat, member: int,
                snapshot) -> Result:
    """One member's Result from a fetched batched output: slice its
    row along the leading member axis and finalize exactly like a
    classic dispatch (merge keys, host LIMIT, TEXT decode)."""
    ncols_part = 2 * len(comp.out_cols) + 1
    member_flat = [np.asarray(flat[i])[member] for i in range(ncols_part)]
    with _trace.span("finalize", cat="host", member=member):
        return ex.finalize(comp, member_flat, snapshot, raw=False)


def run_batch(ex, plan, consts, cache_key, pvec_rows) -> list[Result]:
    """Synchronous prepare+dispatch+demux of one batch (the gang
    worker's and the tests' surface; the serving pipeline calls the
    halves from its own stage/dispatch threads). Raises BatchFallback
    when the batch must be served serially."""
    comp, inputs, snapshot, compiled = prepare_batch(
        ex, plan, consts, cache_key, pvec_rows)
    flat = dispatch_batch(ex, comp, inputs)
    over = batch_overflowed(comp, flat)
    if over:
        raise BatchFallback(f"overflow flags {over} at width "
                            f"{len(pvec_rows)}")
    out = []
    for m in range(len(pvec_rows)):
        res = demux_batch(ex, comp, flat, m, snapshot)
        res.stats = {"batched": True, "batch_width": len(pvec_rows),
                     "batch_bucket": comp.batch_width,
                     "compiled": compiled, "segments": ex.nseg}
        out.append(res)
    return out


class _Member:
    """One waiting statement: its parameter vector, interrupt context,
    statement trace, and the event its connection thread parks on.
    ``sql`` is the member's statement text — only set (and only needed)
    on a multihost coordinator, where the flush broadcasts the window's
    texts so the gang runs the same batched program."""

    __slots__ = ("pvec", "ctx", "trace", "wait_sid", "event", "result",
                 "fallback", "masked", "t0", "sql")

    def __init__(self, pvec, ctx, trace, sql=None):
        self.pvec = pvec
        self.ctx = ctx
        self.trace = trace
        self.sql = sql
        self.wait_sid = None
        self.event = threading.Event()
        self.result = None
        self.fallback = False     # re-run serially on the member's thread
        self.masked = False       # cancelled: raise, never read the slice
        self.t0 = time.monotonic()


class _Batch:
    """One admission window: same plan-cache key, stacked at flush."""

    __slots__ = ("bid", "key", "plan", "consts", "outs", "members",
                 "deadline", "trace", "root_sid", "staged", "stage_error",
                 "plan_hash")

    def __init__(self, bid, key, plan, consts, outs, deadline,
                 plan_hash=None):
        self.bid = bid
        self.key = key
        self.plan = plan
        self.consts = consts
        self.outs = outs
        self.members: list[_Member] = []
        self.deadline = deadline
        self.trace = None
        self.root_sid = None
        self.staged = None
        self.stage_error = None
        self.plan_hash = plan_hash    # gang broadcast verification


class BatchServer:
    """The per-Database serving pipeline. Created lazily by the session
    on the first batch-eligible statement; its two worker threads are
    daemons that carry no statement context."""

    def __init__(self, db):
        self.db = db
        self._mu = threading.Lock()
        self._cv = threading.Condition(self._mu)
        self._open: OrderedDict[str, _Batch] = OrderedDict()
        # windows that FILLED before the stager collected them: moved
        # here by submit() when it opens a successor window for the same
        # key — a full window must never be orphaned by its replacement
        self._full: deque = deque()
        self._dq: queue.Queue = queue.Queue()
        self._bids = itertools.count(1)
        self._members: dict[int, int] = {}   # statement id -> batch id
        self._inflight = 0     # batches popped from the window, not demuxed
        self._started = False
        # Event, not a bare bool: stop() runs on a statement thread
        # while both pipeline threads poll it (gg check races)
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []
        # finished per-flush traces, newest last (tests + introspection;
        # the same traces sit in the TRACES ring under their -bid ids)
        self.recent: deque = deque(maxlen=32)

    # ---- the statement-thread surface --------------------------------
    def submit(self, plan, consts, outs, key: str, pvec, sql=None,
               plan_hash=None):
        """Enroll the calling statement in the admission window for its
        plan-cache key and wait for the flush. Returns the member's
        Result, or None when the batch fell back (the caller re-runs the
        statement through the classic path). Raises StatementCancelled
        for a member cancelled while waiting or masked at demux. On a
        multihost coordinator the caller passes the statement text and
        plan hash so the flush can broadcast the window to the gang."""
        ctx = _INTERRUPTS.current()
        mtr = TRACES.current()
        m = _Member(pvec, ctx, mtr, sql=sql)
        self._ensure_threads()
        window_s = max(float(getattr(self.db.settings,
                                     "batch_window_ms", 2.0)), 0.0) / 1e3
        maxw = max(int(getattr(self.db.settings, "batch_max_width", 16)), 1)
        # the window is keyed by the BOUND PLAN's identity, not just the
        # statement signature: a concurrent DML bumps the manifest
        # version and the session re-binds (pinned string literals lower
        # to dictionary codes, est seeds move), so a member planned
        # after the commit must open its own window rather than execute
        # a batch-mate's stale binding. Plan objects are alive for the
        # window's lifetime (_Batch.plan holds a reference), so id() is
        # unambiguous here.
        wkey = (key, id(plan))
        qcap = int(getattr(self.db.settings, "batch_queue_limit", 0))
        with self._cv:
            if qcap > 0:
                waiting = sum(len(x.members) for x in self._open.values()) \
                    + sum(len(x.members) for x in self._full)
                if waiting >= qcap:
                    # serving-pipeline shed (docs/ROBUSTNESS.md "Overload
                    # protection"): past the member cap this statement
                    # runs on the classic serial path — bounded by the
                    # admission queue — instead of growing the windows
                    # unboundedly while the device is the bottleneck
                    counters.inc("batch_members_shed_total")
                    return None
            b = self._open.get(wkey)
            if b is not None and len(b.members) >= maxw:
                # the window filled before the stager collected it: hand
                # it over explicitly (replacing it in _open would orphan
                # its members) and open a successor for this member
                del self._open[wkey]
                self._full.append(b)
                b = None
            if b is None:
                b = _Batch(next(self._bids), key, plan, consts, outs,
                           time.monotonic() + window_s,
                           plan_hash=plan_hash)
                self._open[wkey] = b
            b.members.append(m)
            if ctx is not None:
                self._members[ctx.statement_id] = b.bid
            depth = sum(len(x.members) for x in self._open.values()) \
                + sum(len(x.members) for x in self._full)
            self._cv.notify_all()
        counters.set("batch_queue_depth", depth)
        if mtr is not None:
            m.wait_sid = mtr.begin("batch-wait", cat="queue", batch=b.bid)
        try:
            # the member's wait is a cancellation point: poll the
            # statement context so `gg cancel` / timeouts / disconnects
            # take a queued member out immediately — its batch-mates are
            # untouched (the dispatcher masks it at demux)
            hard = time.monotonic() + _STALL_TIMEOUT_S
            while not m.event.wait(0.02):
                if ctx is not None:
                    ctx.check()
                if self._stop.is_set():
                    # Database.close(): whatever this member's window
                    # was doing, degrade to the classic path rather
                    # than park the connection thread on a dead pipeline
                    self._abandon(wkey, b, m)
                    return None
                if time.monotonic() > hard:
                    if self._abandon(wkey, b, m):
                        return None   # window never flushed: run classic
                    # flushed but the pipeline is stalled mid-batch —
                    # degrade to serial rather than hang the connection
                    return None
        finally:
            if mtr is not None:
                mtr.end(m.wait_sid)
            if ctx is not None:
                with self._mu:
                    self._members.pop(ctx.statement_id, None)
        if m.masked and ctx is not None:
            ctx.check()   # raises the typed StatementCancelled
        if m.fallback or m.result is None:
            return None
        m.result.wall_ms = (time.monotonic() - m.t0) * 1e3
        return m.result

    def _abandon(self, wkey, b: _Batch, m: _Member) -> bool:
        """Remove a timed-out member from a still-open window (True) or
        report that its batch already flushed (False)."""
        with self._cv:
            if self._open.get(wkey) is b and m in b.members:
                b.members.remove(m)
                if not b.members:
                    del self._open[wkey]
                return True
        return False

    def member_of(self, statement_id: int) -> int | None:
        """Batch id a waiting statement belongs to (`gg ps` column)."""
        with self._mu:
            return self._members.get(statement_id)

    def queue_depths(self) -> dict:
        """Serving-pipeline depths for the status frame / `gg ps`."""
        with self._mu:
            return {
                "batch_admission_depth": sum(
                    len(b.members) for b in self._open.values())
                + sum(len(b.members) for b in self._full),
                "batch_inflight": self._inflight,
            }

    def stop(self) -> None:
        """Stop the pipeline threads and wait for them briefly (a daemon
        thread still inside an XLA dispatch at interpreter shutdown
        aborts the process from the C++ side), then release every member
        still parked in a window or staged batch — each degrades to the
        classic serial path on its own thread instead of waiting out the
        stall timeout against a dead pipeline."""
        self._stop.set()
        with self._cv:
            self._cv.notify_all()
        for t in self._threads:
            if t is not threading.current_thread():
                # Database.close() teardown, not a statement path: the
                # pipeline threads exit on _stop within one poll tick and
                # the join is hard-bounded
                t.join(timeout=3.0)   # gg:ok(interrupts)
        stranded: list[_Member] = []
        with self._cv:
            for b in list(self._open.values()):
                stranded.extend(b.members)
            self._open.clear()
            while self._full:
                stranded.extend(self._full.popleft().members)
        while True:
            try:
                stranded.extend(self._dq.get_nowait().members)
            except queue.Empty:
                break
        for m in stranded:
            m.fallback = True
            m.event.set()

    # ---- pipeline threads --------------------------------------------
    def _ensure_threads(self) -> None:
        if self._started:
            return
        with self._mu:
            if self._started:
                return
            self._threads = [
                threading.Thread(target=self._stage_loop, daemon=True,
                                 name="gg-batch-stage"),
                threading.Thread(target=self._dispatch_loop, daemon=True,
                                 name="gg-batch-dispatch"),
            ]
            for t in self._threads:
                t.start()
            self._started = True

    def _take_window(self) -> _Batch | None:
        """Block until a window is flushable. A window flushes when it is
        FULL (batch_max_width — whatever the pipeline is doing, staging
        it overlaps the in-flight dispatch), or when the pipeline can
        actually accept it (nothing already staged and waiting) and
        either the pipeline is idle (an extra wait would buy no
        batch-mates — flush immediately, so a lone statement pays ~zero
        window latency) or batch_window_ms has elapsed. While a staged
        batch is already queued behind the dispatcher, windows keep
        accumulating members — the wait is free exactly when the device
        is the bottleneck, and width grows to match the device's pace."""
        with self._cv:
            while not self._stop.is_set():
                now = time.monotonic()
                maxw = max(int(getattr(self.db.settings,
                                       "batch_max_width", 16)), 1)
                while self._full:
                    b = self._full.popleft()
                    if not b.members:
                        continue
                    self._inflight += 1
                    counters.inc("batch_window_flush_full")
                    return b
                idle = (self._inflight == 0 and self._dq.empty())
                can_take = self._dq.empty() and self._inflight <= 1
                for key, b in list(self._open.items()):
                    full = len(b.members) >= maxw
                    if full or (can_take and (idle or now >= b.deadline)):
                        del self._open[key]
                        if not b.members:
                            continue   # every member abandoned
                        self._inflight += 1
                        if full:
                            counters.inc("batch_window_flush_full")
                        else:
                            counters.inc("batch_window_flush_timer")
                        return b
                timeout = 0.25
                if self._open and can_take:
                    timeout = min(max(
                        min(x.deadline for x in self._open.values()) - now,
                        0.001), 0.25)
                # pipeline thread: no statement context to poll — members
                # poll their own contexts in submit()
                self._cv.wait(timeout)   # gg:ok(interrupts)
        return None

    def _stage_loop(self) -> None:
        """Admission -> stage: pop flushable windows and stage them (the
        compile-or-reuse + admission + host data path), overlapping the
        dispatcher's device stage — statement k+1 stages while statement
        k runs on device (the PR-3 staging pool extended past a single
        statement)."""
        while not self._stop.is_set():
            try:
                b = self._take_window()
                if b is None:
                    return
                bt = Trace(-b.bid, f"batch {b.key[:300]}")
                b.trace = bt
                b.root_sid = bt.begin("batch-dispatch", cat="batch",
                                      batch=b.bid, width=len(b.members))
                TRACES.adopt(bt)
                try:
                    b.staged = prepare_batch(
                        self.db.executor, b.plan, b.consts, b.key,
                        [m.pvec for m in b.members])
                except BaseException as e:
                    b.staged = None
                    b.stage_error = e
                finally:
                    TRACES.release(bt)
                self._dq.put(b)
                self._refresh_depth()
            except Exception:
                # the pipeline must survive anything — members time out
                # into the serial path rather than hang; no statement
                # runs on this thread, so there is nothing to poll
                time.sleep(0.01)   # gg:ok(interrupts)

    def _dispatch_loop(self) -> None:
        """Dispatch -> fetch -> demux: run staged batches on the device
        one at a time and hand every member its slice."""
        while not self._stop.is_set():
            try:
                # pipeline thread: members poll their own contexts
                b = self._dq.get(timeout=0.25)   # gg:ok(interrupts)
            except queue.Empty:
                continue
            # the staged queue just drained: wake the stager so the next
            # window flushes and stages WHILE this batch is on the device
            with self._cv:
                self._cv.notify_all()
            try:
                self._run_batch(b)
            except Exception:
                for m in b.members:
                    m.fallback = True
                    m.event.set()
                with self._cv:
                    self._inflight -= 1
                    self._cv.notify_all()

    def _run_batch(self, b: _Batch) -> None:
        ex = self.db.executor
        bt = b.trace
        fell_back = False
        TRACES.adopt(bt)
        try:
            if b.staged is None:
                raise BatchFallback(f"stage failed: {b.stage_error!r}")
            comp, inputs, snapshot, compiled = b.staged
            mh_cm = self._mh_exchange(b)
            if mh_cm is not None:
                # multihost gang: two-phase broadcast of the batch window
                # (readiness acks -> 'go' -> concurrent dispatch ->
                # completion acks); any refusal/failure raises
                # BatchFallback so members re-run via the classic
                # per-statement dispatch, which owns failover
                with mh_cm:
                    flat = dispatch_batch(ex, comp, inputs)
            else:
                flat = dispatch_batch(ex, comp, inputs)
            over = batch_overflowed(comp, flat)
            if over:
                # per-member capacity needs differ (value-dependent join
                # expansion / group counts): the serial path's tier
                # machinery owns the retry — never retry the whole batch
                raise BatchFallback(
                    f"overflow flags {over} at width {len(b.members)}")
            width = len(b.members)
            counters.inc("batch_dispatch_total")
            counters.inc("batch_members_total", width)
            histograms.observe("batch_width", float(width),
                               buckets=WIDTH_BUCKETS)
            for i, m in enumerate(b.members):
                cancelled = m.ctx is not None and m.ctx.cancelled
                with bt.span("batch-member", cat="batch", slot=i,
                             statement=(m.ctx.statement_id
                                        if m.ctx is not None else None),
                             cancelled=bool(cancelled)):
                    if cancelled:
                        # masked out at demux: the member's thread raises
                        # the typed cancellation; its batch-mates keep
                        # their results
                        m.masked = True
                        continue
                    try:
                        res = demux_batch(ex, comp, flat, i, snapshot)
                    except Exception:
                        m.fallback = True   # lone demux hiccup: serial
                        continue
                    res.stats = {
                        "batched": True,
                        "batch_id": b.bid,
                        "batch_width": width,
                        "batch_bucket": comp.batch_width,
                        "compiled": bool(compiled),
                        "segments": ex.nseg,
                        "rows_out": len(res),
                    }
                    m.result = res
        except BatchFallback:
            counters.inc("batch_fallback_total")
            fell_back = True
        except BaseException:
            counters.inc("batch_fallback_total")
            fell_back = True
        finally:
            TRACES.release(bt)
            bt.end(b.root_sid)
            TRACES.retire(bt)
            self.recent.append(bt)
            if fell_back:
                for m in b.members:
                    m.fallback = True
            self._graft(b, bt)
            for m in b.members:
                m.event.set()
            with self._cv:
                self._inflight -= 1
                self._cv.notify_all()
            self._refresh_depth()

    def _mh_exchange(self, b: _Batch):
        """Context manager broadcasting this window to the worker gang
        (session._mh_batch_exchange), or None on a single-host Database.
        Raises BatchFallback when a member lacks its statement text —
        the gang cannot replay what it cannot see."""
        db = self.db
        mh = getattr(db, "multihost", None)
        if mh is None or not getattr(mh, "is_coordinator", False):
            return None
        sqls = [m.sql for m in b.members]
        if not all(sqls):
            raise BatchFallback(
                "batched member lacks statement text for the gang "
                "broadcast")
        return db._mh_batch_exchange(sqls, b.plan_hash)

    # ---- bookkeeping --------------------------------------------------
    def _graft(self, b: _Batch, bt: Trace) -> None:
        """Copy the flush's span tree into every member's statement trace
        under its batch-wait span, re-based onto the member's clock — one
        flame graph shows the whole batch from any member's trace."""
        spans = bt.export()
        for m in b.members:
            if m.trace is None or m.wait_sid is None:
                continue
            try:
                base_ms = (bt.wall0 - m.trace.wall0) * 1e3
                m.trace.graft(spans, m.wait_sid, tid=f"batch-{b.bid}",
                              base_ms=base_ms)
            except Exception:
                pass   # a lost graft must never lose the statement

    def _refresh_depth(self) -> None:
        with self._mu:
            depth = sum(len(x.members) for x in self._open.values()) \
                + sum(len(x.members) for x in self._full)
        counters.set("batch_queue_depth", depth)
