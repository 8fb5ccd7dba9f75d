"""Measured memory accounting — the vmem_tracker.c + memaccounting.c
analog for the XLA execution model.

The reference's L0 is a *measured* substrate: ``vmem_tracker.c``
interposes on every palloc and ``memaccounting.c`` keeps a per-statement
owner tree that is dumped on OOM. Everything above it (red zone, runaway
cleaner, workfile spilling) keys off those measured numbers. Our engine's
vmem machinery ran for four PRs on planner *estimates* (node capacity x
dtype width) and never looked at what XLA actually allocated or what the
device actually holds. This module supplies the measured layer:

  * ``MemoryAccount`` — one per-statement owner tree (thread-keyed in
    ``ACCOUNTS``, exactly like the interrupt and trace registries; the
    account id IS the statement id). Owners are the fixed taxonomy in
    ``OWNERS``: host ``staging`` buffers, this statement's ``blockcache``
    inserts, ``spill`` run captures, and the executable's ``device``
    footprint (args/temps/output, measured by XLA when available).
    Charges from staging-pool threads ride an explicit ``bind()`` — the
    same discipline the interrupt context uses for pool reads.
  * ``jax`` executable measurement — the executor attaches
    ``compiled.memory_analysis()`` (temp/argument/output/generated-code
    bytes) to every cached executable at first dispatch and REUSES it on
    warm hits (``mem_analysis_runs`` counts the analyses, so tests can
    assert a warm hit re-analyzes nothing). The estimate-vs-measured
    error lands in the ``mem_est_error_pct`` gauge — the first ground
    truth four PRs of capacity bucketing ever had.
  * live HBM watermarks — ``sample_watermark()`` reads
    ``device.memory_stats()`` (``bytes_in_use`` / ``peak_bytes_in_use``;
    gracefully None on CPU backends, after which sampling self-disables)
    and is installed as the trace substrate's span sampler, so `gg trace`
    shows the device-memory delta of every span.
  * OOM forensics — ``is_oom_error()`` classifies XLA RESOURCE_EXHAUSTED;
    the executor raises a typed ``OutOfDeviceMemory`` carrying the
    accounting snapshot + the offending executable's memory analysis, and
    the session dumps ``mem-<id>.json`` beside the slow-log traces.

Process-wide surfaces: per-owner live-byte gauges
(``mem_owner_bytes_<owner>``), device gauges (``device_bytes_in_use`` /
``device_peak_bytes_in_use``), host process gauges (RSS, open fds,
staging-pool queue depth) — all exported by `gg metrics`; `gg mem` /
the server ``{"op": "mem"}`` frame serve the full ``report()``.
"""

from __future__ import annotations

import os
import resource
import sys
import threading
from collections import OrderedDict
from contextlib import contextmanager

from greengage_tpu.runtime import trace as _trace
from greengage_tpu.runtime.logger import counters

# fixed owner taxonomy (docs/OBSERVABILITY.md "Memory accounting"): the
# per-owner gauges are declared per name in runtime/logger.py, so charges
# outside this set would be invisible to the exposition — charge()
# rejects them rather than losing bytes silently
OWNERS = ("staging", "blockcache", "spill", "device")

# keep the per-owner item detail bounded: a statement scanning thousands
# of partition children must degrade to a truncated item map, never to
# unbounded account growth
MAX_ITEMS_PER_OWNER = 64


class MemoryAccount:
    """One statement's per-owner memory tree. Thread-safe: the statement
    thread charges staging/spill/device, pool threads (via ``bind``)
    charge block-cache inserts concurrently."""

    def __init__(self, statement_id: int, sql: str = ""):
        self.statement_id = statement_id
        self.sql = (sql or "").strip()[:200]
        self.depth = 1            # nested sql() calls share it
        self._lock = threading.Lock()
        # set (under _lock) when the registry retires the account: a
        # straggler pool thread finishing a read unit after a cancelled
        # stage must not charge live bytes the exit already subtracted —
        # the gauge would drift upward for the life of the process
        self._closed = False
        # owner -> [current bytes, peak bytes, {item: bytes}]
        self._owners: dict[str, list] = {}

    def charge(self, owner: str, nbytes: int, item: str | None = None) -> None:
        if owner not in OWNERS:
            raise ValueError(f"unknown memory owner {owner!r} "
                             f"(taxonomy: {OWNERS})")
        nbytes = int(nbytes)
        # the live-total update happens under the SAME lock as the closed
        # check (lock order: account lock -> _owner_mu, nothing reverse),
        # so close() + subtraction can never interleave with a late add
        with self._lock:
            if self._closed:
                return
            ent = self._owners.get(owner)
            if ent is None:
                ent = self._owners[owner] = [0, 0, {}]
            ent[0] += nbytes
            ent[1] = max(ent[1], ent[0])
            if item is not None:
                items = ent[2]
                if item in items or len(items) < MAX_ITEMS_PER_OWNER:
                    items[item] = items.get(item, 0) + nbytes
                else:
                    items["<other>"] = items.get("<other>", 0) + nbytes
            _owner_live_add(owner, nbytes)

    def set_device(self, analysis: dict | None, est_bytes: int = 0) -> None:
        """Record the executable's device footprint: the measured
        memory_analysis when XLA reports one, the compiled estimate
        otherwise (items mark which)."""
        with self._lock:
            if self._closed:
                return
            ent = self._owners.get("device")
            if ent is None:
                ent = self._owners["device"] = [0, 0, {}]
            old = ent[0]
            if analysis:
                total = (analysis.get("argument_bytes", 0)
                         + analysis.get("temp_bytes", 0)
                         + analysis.get("output_bytes", 0))
                ent[2] = {"args": analysis.get("argument_bytes", 0),
                          "temp": analysis.get("temp_bytes", 0),
                          "output": analysis.get("output_bytes", 0),
                          "code": analysis.get("generated_code_bytes", 0)}
            else:
                total = int(est_bytes)
                ent[2] = {"estimate": total}
            ent[0] = total
            ent[1] = max(ent[1], total)
            _owner_live_add("device", ent[0] - old)

    def close(self) -> None:
        """Retire the account: refuse further charges and release its
        live bytes from the process-wide owner totals, atomically with
        respect to concurrent charges."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            for owner, ent in self._owners.items():
                _owner_live_add(owner, -ent[0])

    def owner_totals(self) -> dict[str, int]:
        with self._lock:
            return {o: ent[0] for o, ent in self._owners.items()}

    def total_bytes(self) -> int:
        with self._lock:
            return sum(ent[0] for ent in self._owners.values())

    def snapshot(self) -> dict:
        """The full per-owner accounting tree — what an OOM dump and the
        `gg mem` report carry (MemoryAccounting_SaveToLog analog)."""
        with self._lock:
            owners = {o: {"bytes": ent[0], "peak_bytes": ent[1],
                          "items": dict(ent[2])}
                      for o, ent in self._owners.items()}
        return {"statement_id": self.statement_id, "sql": self.sql,
                "owners": owners,
                "total_bytes": sum(o["bytes"] for o in owners.values())}


class AccountRegistry:
    """Process-wide registry: in-flight accounts keyed by thread (one
    statement per connection thread, like the interrupt and trace
    registries) plus a small completed ring for `gg mem`."""

    RING = 16

    def __init__(self):
        self._lock = threading.Lock()
        self._by_thread: dict[int, MemoryAccount] = {}
        self._ring: OrderedDict[int, dict] = OrderedDict()

    def enter(self, statement_id: int,
              sql: str = "") -> tuple[MemoryAccount, bool]:
        """Open (or re-enter) the calling thread's account; nested sql()
        calls share the outermost one. -> (account, is_outermost)."""
        tid = threading.get_ident()
        with self._lock:
            cur = self._by_thread.get(tid)
            if cur is not None:
                cur.depth += 1
                return cur, False
            acct = MemoryAccount(statement_id, sql)
            self._by_thread[tid] = acct
            return acct, True

    def exit(self, acct: MemoryAccount | None) -> None:
        if acct is None:
            return
        tid = threading.get_ident()
        with self._lock:
            cur = self._by_thread.get(tid)
            if cur is None:
                return
            cur.depth -= 1
            if cur.depth > 0:
                return
            del self._by_thread[tid]
            self._ring[cur.statement_id] = cur.snapshot()
            while len(self._ring) > self.RING:
                self._ring.popitem(last=False)
        # retire: live bytes leave the process-wide owner gauges and any
        # straggler pool thread's late charge becomes a no-op
        cur.close()

    def current(self) -> MemoryAccount | None:
        return self._by_thread.get(threading.get_ident())

    @contextmanager
    def bind(self, acct: MemoryAccount | None):
        """Register a POOL thread against a statement's account for the
        duration of one read unit (the interrupt ctx handoff discipline):
        block-cache inserts inside the unit then attribute correctly."""
        if acct is None:
            yield
            return
        tid = threading.get_ident()
        with self._lock:
            prev = self._by_thread.get(tid)
            self._by_thread[tid] = acct
        try:
            yield
        finally:
            with self._lock:
                if prev is None:
                    self._by_thread.pop(tid, None)
                else:
                    self._by_thread[tid] = prev

    def snapshot(self) -> list[dict]:
        with self._lock:
            # dedup by account identity: during a cold stage every bound
            # pool thread maps to the statement's ONE account, and
            # `gg mem` must not print that statement scan_threads+1 times
            accts = list({id(a): a for a in self._by_thread.values()}
                         .values())
        return [a.snapshot() for a in accts]

    def ring(self) -> list[dict]:
        with self._lock:
            return list(self._ring.values())


ACCOUNTS = AccountRegistry()   # process-wide (shmem MemoryAccounting role)


def charge(owner: str, nbytes: int, item: str | None = None) -> None:
    """Charge the calling thread's current account; a cheap no-op when
    accounting is off or the thread runs no statement."""
    acct = ACCOUNTS.current()
    if acct is not None:
        acct.charge(owner, nbytes, item)


# ---- process-wide per-owner live totals (the gauge source) -------------
_owner_mu = threading.Lock()
_OWNER_LIVE: dict[str, int] = {}


def _owner_live_add(owner: str, nbytes: int) -> None:
    with _owner_mu:
        _OWNER_LIVE[owner] = _OWNER_LIVE.get(owner, 0) + int(nbytes)


def owner_live_bytes() -> dict[str, int]:
    with _owner_mu:
        return {o: max(n, 0) for o, n in _OWNER_LIVE.items()}


# ---- device watermarks -------------------------------------------------
# memory_stats() returns None on backends without an HBM allocator (CPU);
# a clean None probe self-disables sampling so the per-span hook costs
# one flag read. Probe EXCEPTIONS are treated as transient (a TPU
# runtime hiccup must not permanently kill watermarks + measured
# admission) — only a streak of them latches the disable.
_dev_mu = threading.Lock()
_DEV_UNSUPPORTED = False
_DEV_FAILS = 0
_DEV_FAIL_LIMIT = 3
_dev_handle = None   # cached jax device: the sampler runs twice per span
# on allocator-bearing backends, so it must not pay a backend resolution
# (jax.local_devices()) per sample — one memory_stats() C call only


def device_memory_stats() -> dict | None:
    """First local device's allocator stats (bytes_in_use,
    peak_bytes_in_use, ...); None when the backend has none (CPU).
    All probe state (_dev_handle/_DEV_FAILS/_DEV_UNSUPPORTED) moves
    under _dev_mu; only the memory_stats() C call itself runs outside
    it, so concurrent samplers never see a half-updated handle
    (gg check races)."""
    global _DEV_UNSUPPORTED, _DEV_FAILS, _dev_handle
    with _dev_mu:
        if _DEV_UNSUPPORTED:
            return None
        d = _dev_handle
    try:
        if d is None:
            import jax

            devs = jax.local_devices()
            if not devs:
                with _dev_mu:
                    _DEV_UNSUPPORTED = True
                return None
            d = devs[0]
            with _dev_mu:
                _dev_handle = d
        stats = d.memory_stats()
    except Exception:
        with _dev_mu:
            _dev_handle = None   # re-resolve next probe (backend restart)
            _DEV_FAILS += 1
            if _DEV_FAILS >= _DEV_FAIL_LIMIT:
                _DEV_UNSUPPORTED = True
        return None
    if not stats:
        # a SUCCESSFUL probe reporting no allocator is the genuine
        # unsupported-backend answer: latch immediately
        with _dev_mu:
            _DEV_UNSUPPORTED = True
        return None
    with _dev_mu:
        _DEV_FAILS = 0
    return dict(stats)


def sample_watermark() -> int | None:
    """One live HBM sample -> bytes_in_use (None on CPU backends).
    Updates the device gauges as a side effect; installed as the trace
    substrate's span sampler so `gg trace` shows per-span deltas."""
    stats = device_memory_stats()
    if stats is None:
        return None
    used = int(stats.get("bytes_in_use", 0))
    counters.set("device_bytes_in_use", used)
    peak = stats.get("peak_bytes_in_use")
    if peak is not None:
        counters.set("device_peak_bytes_in_use", int(peak))
    return used


_trace.set_mem_sampler(sample_watermark)


# ---- OOM classification ------------------------------------------------
# NO bare "oom" marker: it substring-matches "bloom" (as in bloom-filter
# error text) and would misclassify unrelated failures
_OOM_MARKERS = ("resource_exhausted", "resource exhausted", "out of memory",
                "out_of_memory", "allocation failure")


def is_oom_error(e: BaseException) -> bool:
    """Does this exception look like a device allocation failure? XLA
    surfaces them as XlaRuntimeError with a RESOURCE_EXHAUSTED status
    (BFC allocator: 'Out of memory while trying to allocate N bytes')."""
    s = f"{type(e).__name__}: {e}".lower()
    return any(m in s for m in _OOM_MARKERS)


# ---- host process gauges (`gg metrics` satellite) ----------------------
def _current_rss_bytes() -> int:
    """Current resident set: /proc/self/statm (field 2, pages) where it
    exists; elsewhere fall back to getrusage's ru_maxrss — the lifetime
    PEAK, in KB on Linux but bytes on Darwin."""
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return pages * (os.sysconf("SC_PAGE_SIZE") or 4096)
    except (OSError, ValueError, IndexError):
        ru = resource.getrusage(resource.RUSAGE_SELF)
        scale = 1 if sys.platform == "darwin" else 1024
        return int(ru.ru_maxrss) * scale


def update_process_gauges() -> dict:
    """Refresh the host-side gauges right before an exposition: process
    RSS (live from /proc, getrusage peak as the fallback), open fd
    count, staging-pool queue depth, and the per-owner live totals."""
    out: dict = {}
    try:
        out["host_rss_bytes"] = _current_rss_bytes()
        counters.set("host_rss_bytes", out["host_rss_bytes"])
    except Exception:
        pass
    try:
        nfds = len(os.listdir("/proc/self/fd"))
        out["host_open_fds"] = nfds
        counters.set("host_open_fds", nfds)
    except OSError:
        pass
    from greengage_tpu.exec import staging as _staging

    depth = _staging.pool_queue_depth()
    out["staging_pool_queue_depth"] = depth
    counters.set("staging_pool_queue_depth", depth)
    for owner, n in owner_live_bytes().items():
        counters.set(f"mem_owner_bytes_{owner}", n)
        out[f"mem_owner_bytes_{owner}"] = n
    return out


# ---- the `gg mem` / {"op": "mem"} report -------------------------------
def report(db=None) -> dict:
    """Everything the operator needs in one frame: live device stats,
    in-flight + recent per-statement accounting trees, the runaway
    tracker's ledger, block-cache budget state, and host gauges."""
    from greengage_tpu.runtime.runaway import TRACKER

    out = {
        "device": device_memory_stats(),
        "process": update_process_gauges(),
        "in_flight": ACCOUNTS.snapshot(),
        "recent": ACCOUNTS.ring(),
        "vmem_tracker": TRACKER.snapshot(),
    }
    if db is not None:
        try:
            out["block_cache"] = db.store.blockcache.stats()
        except Exception:
            pass
        try:
            out["executables"] = executable_mem_summary(db.executor)
        except Exception:
            pass
    return out


def executable_mem_summary(executor) -> list[dict]:
    """Per cached executable: the statement key, compile-time estimate,
    and measured memory analysis (None until its first dispatch)."""
    out = []
    for key, comp in executor.programs.items():
        out.append({
            "statement": str(key[0])[:120],
            "est_bytes": int(getattr(comp, "est_bytes", 0)),
            "measured": getattr(comp, "mem_analysis", None),
        })
    return out
