"""Double-buffered bucket schedules — the host half of pipelined motion.

The PR-3/PR-11 work overlapped staging with device dispatch *inside* one
program; bucketed schedules (spill dedupe buckets, window spill buckets,
tiered-workfile promotion) still ran stage -> compute as strictly serial
phases, so the device idled during every bucket's host preparation and
the host idled during every bucket's device program. This module supplies
the missing overlap: ``run_pipeline(items, stage, compute)`` runs the
``stage`` callable for bucket k+1 on a background thread while the
calling thread runs ``compute`` for bucket k — double-buffered (the
stager keeps at most one bucket ahead), so host memory holds at most two
staged buckets and the schedule's wall time tends to
max(sum(stage), sum(compute)) instead of their sum.

Determinism note (multihost lockstep): ``compute`` always runs on the
CALLING thread in bucket order — only the side-effect-free ``stage``
work moves off-thread — so collective programs and spill schedules stay
bit-identical to the serial loop. The ``motion_pipeline`` GUC (or a
single-bucket schedule) falls back to the serial loop with the same
span structure, which is the microbench baseline.

Spans: every bucket records ``motion-stage`` / ``motion-compute``
(cat="motion") with index/total; the realized stage(k+1) x compute(k)
overlap accumulates into the ``motion_overlap_ms`` counter and is what
the trace-timestamp overlap test asserts on.
"""

from __future__ import annotations

import threading
import time

from greengage_tpu.runtime import interrupt
from greengage_tpu.runtime.faultinject import faults
from greengage_tpu.runtime import trace as _trace
from greengage_tpu.runtime.logger import counters


class _Slot:
    __slots__ = ("value", "err", "t0", "t1")


class BucketPipeline:
    """One schedule's staging thread + slot exchange. Shared between the
    statement thread (take/close) and its stager; all slot state moves
    under the one condition lock."""

    def __init__(self, items, stage, trace, label: str):
        self.items = items
        self.stage = stage
        self.trace = trace
        self.label = label
        self._mu = threading.Condition(threading.Lock())
        self._slots: dict[int, _Slot] = {}
        self._consumed = -1          # highest index take() handed out
        self._stop = False
        # the spawning statement's interrupt context: the stager polls it
        # between buckets so a cancelled statement's pipeline dies at the
        # next bucket boundary (close() below never outwaits it)
        self._ctx = interrupt.REGISTRY.current()
        self._thread = threading.Thread(target=self._stage_loop,
                                        daemon=True, name="gg-motion-stage")

    def start(self) -> None:
        self._thread.start()

    def _stage_loop(self) -> None:
        tr = self.trace
        if tr is not None:
            _trace.TRACES.adopt(tr)   # spans land in the statement trace
        try:
            n = len(self.items)
            for i, it in enumerate(self.items):
                with self._mu:
                    # double buffer: at most ONE bucket staged ahead of
                    # the one the consumer is computing
                    while not self._stop and i - self._consumed > 1:
                        self._mu.wait(0.1)   # gg:ok(interrupts) — bounded
                        # wait on the pipeline's own condition; the
                        # statement thread owns cancellation and take()
                        # polls it
                    if self._stop:
                        return
                if self._ctx is not None and self._ctx.cancelled:
                    return
                slot = _Slot()
                slot.t0 = time.monotonic()
                try:
                    with _trace.span("motion-stage", cat="motion", index=i,
                                     total=n, label=self.label):
                        # fault point INSIDE the stage span: a 'sleep'
                        # injection widens stage(k+1) so the overlap test
                        # pins it across compute(k) deterministically
                        faults.check("motion_bucket")
                        slot.value, slot.err = self.stage(it, i), None
                except BaseException as e:   # re-raised at take(i)
                    slot.value, slot.err = None, e
                slot.t1 = time.monotonic()
                with self._mu:
                    self._slots[i] = slot
                    self._mu.notify_all()
                if slot.err is not None:
                    return
        finally:
            if tr is not None:
                _trace.TRACES.release(tr)

    def take(self, i: int) -> _Slot:
        """Block until bucket i is staged; marks it consumed (which frees
        the stager to run bucket i+1 while the caller computes i)."""
        with self._mu:
            self._consumed = max(self._consumed, i)
            self._mu.notify_all()
            while i not in self._slots:
                interrupt.check_interrupts()
                self._mu.wait(0.1)
            slot = self._slots.pop(i)
        if slot.err is not None:
            raise slot.err
        return slot

    def close(self) -> None:
        """Stop + join the stager, bounded; polls the statement's
        cancellation like PassPrefetcher.close so a dying statement never
        sits out a hung stage callable."""
        with self._mu:
            self._stop = True
            self._mu.notify_all()
        t = self._thread
        if not t.is_alive():
            return
        deadline = time.monotonic() + 60.0
        while t.is_alive() and time.monotonic() < deadline:
            if self._ctx is not None and self._ctx.cancelled:
                t.join(timeout=5.0)
                break
            t.join(timeout=0.25)


def run_pipeline(items, stage, compute, settings=None, label: str = "spill"):
    """Run every item through stage -> compute in item order, overlapping
    stage(k+1) with compute(k) on a background thread. ``stage(item, i)``
    must be side-effect-free host work (reads, decodes, mask builds);
    ``compute(staged, item, i)`` runs on the calling thread. Returns the
    list of compute results. Serial (same spans, no thread) when the
    motion_pipeline GUC is off or the schedule has a single bucket."""
    n = len(items)
    enabled = n > 1 and (settings is None
                         or bool(getattr(settings, "motion_pipeline", True)))
    out = []
    if not enabled:
        for i, it in enumerate(items):
            interrupt.check_interrupts()
            with _trace.span("motion-stage", cat="motion", index=i,
                             total=n, label=label):
                faults.check("motion_bucket")
                staged = stage(it, i)
            with _trace.span("motion-compute", cat="motion", index=i,
                             total=n, label=label):
                out.append(compute(staged, it, i))
        return out
    pipe = BucketPipeline(items, stage, _trace.TRACES.current(), label)
    pipe.start()
    overlap_s = 0.0
    try:
        prev = None                    # compute window of bucket i-1
        for i, it in enumerate(items):
            interrupt.check_interrupts()
            slot = pipe.take(i)
            c0 = time.monotonic()
            with _trace.span("motion-compute", cat="motion", index=i,
                             total=n, label=label):
                out.append(compute(slot.value, it, i))
            c1 = time.monotonic()
            if prev is not None:       # stage(i) overlapped compute(i-1)?
                overlap_s += max(0.0, min(slot.t1, prev[1])
                                 - max(slot.t0, prev[0]))
            prev = (c0, c1)
    finally:
        pipe.close()
        if overlap_s > 0.0:
            counters.inc("motion_overlap_ms", max(int(overlap_s * 1e3), 1))
    return out
