"""Structured cluster logging — the elog/syslogger analog.

Reference parity: the CSV server log emitted by the syslogger
(src/backend/postmaster/syslogger.c, write_csvlog in elog.c): one file
per day under ``<cluster>/log/``, one CSV record per event. Field
layout (a condensed version of the reference's 23-column csvlog):

    timestamp, severity, pid, thread, kind, duration_ms, rows, message

Statements, errors, lifecycle events (startup/shutdown/recovery), and
management actions all land here; ``gg logfilter`` (mgmt/cli.py) is the
gplogfilter analog that mines them. Appends are line-atomic under a
process-wide lock; multiple threads (server connections) share one
logger. The logger never raises into the caller — a full disk must not
take the query path down with it.
"""

from __future__ import annotations

import bisect
import csv
import datetime
import io
import os
import re
import threading

SEVERITIES = ("DEBUG", "INFO", "WARNING", "ERROR", "FATAL", "PANIC")

# names that are levels, not monotone counts, regardless of how they were
# first written — the Prometheus exposition must emit `# TYPE ... gauge`
# for them even in a process that has only inc()'d so far
GAUGE_NAMES = (
    "mh_topology_version",
    # measured memory accounting (runtime/memaccount.py): live device
    # allocator watermarks (absent on CPU backends — no writer runs),
    # the signed estimate-vs-measured executable error, per-owner live
    # host bytes, and the host process gauges `gg metrics` refreshes
    "device_bytes_in_use", "device_peak_bytes_in_use", "mem_est_error_pct",
    "mem_owner_bytes_staging", "mem_owner_bytes_blockcache",
    "mem_owner_bytes_spill", "mem_owner_bytes_device",
    "host_rss_bytes", "host_open_fds", "staging_pool_queue_depth",
    # vectorized serving (exec/batchserve.py): members waiting in open
    # admission windows right now
    "batch_queue_depth",
    # overload armor (runtime/server.py, runtime/overload.py): live
    # client connections on the serving front end, and whether the
    # memory-pressure brownout is engaged (1) or clear (0)
    "server_active_connections", "brownout",
    # streaming ingest plane (runtime/ingest.py): live stream sessions
    # and rows currently buffered host-side across them
    "ingest_active_streams", "ingest_buffered_rows",
    # tiered spill workfile (exec/workfile.py): bytes currently retained
    # in each tier across all spilling statements — host-RAM captured
    # passes vs compressed disk segments awaiting promotion
    "spill_tier_ram_bytes", "spill_tier_disk_bytes",
    # coordinator failover (runtime/standby.py): committed versions on
    # the primary not yet shipped to the registered standby — 0 while
    # the tail sync keeps up, grows while shipping fails
    "standby_lag_commits",
    # self-tuning loop (planner/feedback.py): generation of the applied
    # calibration — joins the bound-plan cache key, so a bump means every
    # affected shape re-plans; workers track the coordinator's via the
    # dispatch-frame payload
    "calibration_version",
)

# Declared metric catalog — the source of truth `gg check`
# (analysis/lint_registry.py) cross-checks against the package source:
# every counters.inc() site must name a declared counter (f-string
# families match by their literal prefix), every counters.set() site a
# declared gauge, every histograms.observe() site a declared histogram —
# and every declared name must have a writer. Undeclared names are a
# merge-time lint failure, so the docs/OBSERVABILITY.md metric catalog
# and the exposition can't silently drift from the code.
COUNTER_NAMES = (
    # plan / executable cache (exec/session.py, exec/executor.py)
    "plan_cache_hit", "plan_cache_miss", "plan_cache_fallback",
    "program_cache_hit", "program_cache_miss", "program_cache_unsignable",
    "params_hoisted", "compile_ms",
    # statement lifecycle (exec/session.py, runtime/resqueue.py)
    "statements_cancelled_user", "statements_cancelled_timeout",
    "statements_cancelled_runaway", "statements_cancelled_client_gone",
    "statements_cancelled_shutdown", "statements_retried",
    "queue_cancelled_total", "slow_statements",
    # host data path (storage/blockcache.py, exec/executor.py)
    "scan_files_read", "scan_bytes_decoded",
    "scan_cache_hit", "scan_cache_miss", "scan_cache_evict",
    # the in-place protocol (exec/staging.py): read units staged, and
    # those whose every column landed in its staging slot on the thread
    # that ran the unit — in_slot / units is how often it engages
    "stage_units", "stage_units_in_slot",
    # the read path after a write (exec/staging.py): read units that were
    # offered their slots and left the in-place path because a column is
    # several data files or the table has a deletion bitmap (a unit under
    # both counts as delmask), read tables whose pushed zone-map
    # predicates a bitmap switched off, and staged device inputs a
    # manifest bump made unreachable
    "stage_units_copy_files", "stage_units_copy_delmask",
    "zone_prune_skipped_delmask", "stage_cache_dropped",
    # the write path (storage/table_store.py, storage/blockfile.py,
    # exec/session.py): rows appended, rows a DML statement deleted,
    # bytes of block files written (data, validity, bitmaps), and commit
    # lines appended to the manifest's log (delta and intent alike)
    "rows_inserted", "rows_deleted", "write_bytes", "manifest_commits",
    # storage self-heal (storage/table_store.py, storage/scrub.py)
    "storage_repair", "storage_standby_repair", "storage_quarantine",
    "storage_scrub_runs", "storage_scrub_files",
    # manifest commit path + topology (storage/manifest.py, exec/session.py)
    "manifest_delta_commits", "manifest_cas_retry_total",
    "manifest_cas_conflict_total", "manifest_folds", "mh_reform_total",
    # measured memory accounting (exec/executor.py): executable analyses
    # performed (a warm program-cache hit must add ZERO), classified
    # device OOMs, and OOMs absorbed by the one-shot spill demotion
    "mem_analysis_runs", "oom_events", "oom_spill_retries",
    # vectorized serving (exec/batchserve.py): device dispatches vs
    # statements they served (members/dispatch = the amortization
    # factor), why windows flushed, and batches routed back to the
    # serial path (admission ceiling / overflow flags / stage failure)
    "batch_dispatch_total", "batch_members_total",
    "batch_window_flush_full", "batch_window_flush_timer",
    "batch_fallback_total",
    # window engine (planner/planner.py, exec/spill.py): plans kept
    # gather-free (global collective / packed-rank / range-repartition
    # modes) vs plans that still took the one-chip SingleQE funnel, and
    # window-partition spill activity (runs + capture/bucket passes)
    "window_gather_free_total", "window_funnel_total",
    "window_spill_runs", "window_spill_passes",
    # scalar data-path fusion (sql/binder.py, ops/scalar.py): scalar
    # function sites lowered INTO the fused device programs (Func /
    # dictionary LUT / raw byte-window op) vs sites that fell back to the
    # per-row host chain (@hp chain predicates, finalize-decode
    # projections) — the fused-coverage ratio docs/PERF.md tracks
    "scalar_device_total", "scalar_host_fallback_total",
    # sort-based aggregates (exec/compile.py _c_aggregate): groups found,
    # and the out_cap their group tables were compiled with, a statement —
    # groups / capacity is how full the tables ran; the capacity of those
    # whose group starts one pass over the rows found, not a search a group
    # (ops/agg.group_starts); the slots they sorted (each one's input
    # capacity, after any compaction)
    "agg_sort_groups", "agg_sort_capacity", "agg_sort_capacity_direct",
    "agg_sort_input_slots",
    # inner and left joins (exec/compile.py _c_join, _c_join_multi): the
    # slots their build columns were gathered into, a statement (a join
    # that compacts its matches first gathers into 1/32 of its probe slots)
    "join_gather_slots",
    # duplicate-key (multi) joins (exec/compile.py _c_join_multi): the pairs
    # each expansion held in its fullest segment and the out_cap it ran with,
    # a statement — rows / capacity is how full the expansions ran; attempts
    # run again because an expansion overflowed its out_cap; probe rows a
    # LEFT join let through with no surviving pair (null-extended)
    "join_expand_rows", "join_expand_capacity", "join_expand_retries",
    "join_null_extended_rows",
    # semi and anti joins (exec/compile.py SEMI_COUNTERS), summed over the
    # segments, a statement: live build rows the table took, those whose
    # key an earlier build row holds, live probe rows, probe rows kept
    "semi_build_rows", "semi_build_dup_rows", "semi_probe_rows",
    "semi_kept_rows",
    # overload armor (docs/ROBUSTNESS.md "Overload protection"):
    # connections accepted vs shed at the bounded front end
    # (runtime/server.py), oversized request frames rejected, statements
    # shed at the admission queues (runtime/resqueue.py shed_check),
    # serving-pipeline members shed to the serial path
    # (exec/batchserve.py), and brownout state transitions
    # (runtime/overload.py)
    "server_connections_total", "connections_shed_total",
    "frames_rejected_total", "admission_shed_total",
    "batch_members_shed_total",
    "brownout_entered_total", "brownout_exited_total",
    # hot-table write scale (storage/manifest.py, runtime/ingest.py):
    # write-intent merges resolved into the commit log, state-replacing
    # commits fenced off by a landed merge (clean conflicts), in-doubt /
    # leftover intent markers swept by recovery and grace-GC, and the
    # streaming ingest plane's committed micro-batches, rows, typed
    # sheds, and replayed batches deduplicated on resume
    "manifest_intent_commits", "manifest_intent_conflict_total",
    "manifest_intent_swept_total",
    "ingest_batches_total", "ingest_rows_total", "ingest_shed_total",
    "ingest_resume_dedup_total",
    # data-movement pipeline (exec/motionpipe.py, exec/workfile.py):
    # realized stage(k+1) x compute(k) overlap milliseconds across
    # bucketed schedules, tiered-workfile passes demoted to / promoted
    # from the disk tier, and dead-process spill segments swept at
    # recovery
    "motion_overlap_ms", "spill_demote_total", "spill_promote_total",
    "spill_orphan_sweep_total",
    # coordinator failover (runtime/standby.py, parallel/multihost.py):
    # standby tail-sync ship failures (files the post-commit/watcher sync
    # could NOT ship — the formerly-silent OSError swallow), standby
    # promotions (watcher-automatic or `gg standby --promote`), and
    # workers re-homed to a non-launch coordinator address after
    # CoordinatorLost (the redial walked mh_coordinator_addrs and landed
    # on the promoted standby)
    "standby_sync_fail_total", "standby_promote_total", "mh_rehome_total",
    # self-tuning loop (planner/feedback.py, exec/executor.py):
    # calibration corrections promoted into applied scales, and how each
    # admission verdict was priced — measured footprint (live AOT
    # analysis OR the feedback store's persisted measurement; the
    # _feedback variant counts the persisted subset) vs planner estimate
    "feedback_applied_total",
    "admission_measured_total", "admission_measured_feedback_total",
    "admission_estimated_total",
)

HISTOGRAM_NAMES = (
    "statement_ms", "queue_wait_ms", "compile_latency_ms",
    "stage_ms", "dispatch_ms", "fetch_ms",
    # measured executable footprint (args+temps+output, MB buckets —
    # observed with DEFAULT_BUCKETS_MB, not the ms defaults)
    "executable_mem_mb",
    # vectorized serving: members per flushed batch (pow2-width buckets,
    # exec/batchserve.WIDTH_BUCKETS — not the ms defaults)
    "batch_width",
)


class Counters:
    """Process-wide monotonic event counters (the pg_stat counter surface):
    storage repair/quarantine/scrub events land here so tests and `gg
    scrub`/`gg state` can assert on behavior without parsing log text.
    Names written through set() are tagged as GAUGES (levels, e.g.
    mh_topology_version) so the Prometheus exposition types them right."""

    def __init__(self):
        from greengage_tpu.runtime import lockdebug

        self._lock = lockdebug.named(threading.Lock(),
                                     "logger.counters._lock")
        # access-witnessed under GGTPU_RACE_DEBUG: every touch must hold
        # the counters lock (docs/ANALYSIS.md "Race analysis")
        self._c: dict[str, int] = lockdebug.shared({}, "logger.counters._c")
        self._gauges: set[str] = set(GAUGE_NAMES)

    def inc(self, name: str, n: int = 1) -> int:
        with self._lock:
            self._c[name] = self._c.get(name, 0) + n
            return self._c[name]

    def set(self, name: str, value: int) -> int:
        """Gauge-style assignment (e.g. mh_topology_version): the counter
        surface also carries a few level values tests assert on."""
        with self._lock:
            self._c[name] = int(value)
            self._gauges.add(name)
            return self._c[name]

    def gauges(self) -> set[str]:
        """Names holding gauge (level) semantics; everything else in
        snapshot() is a monotone counter."""
        with self._lock:
            return set(self._gauges)

    def kind(self, name: str) -> str:
        with self._lock:
            return "gauge" if name in self._gauges else "counter"

    def get(self, name: str) -> int:
        with self._lock:
            return self._c.get(name, 0)

    def snapshot(self) -> dict[str, int]:
        with self._lock:
            # items() not dict(): one access-witness record per snapshot
            # instead of one per key (GGTPU_RACE_DEBUG)
            return dict(self._c.items())

    def since(self, base: dict[str, int],
              prefix: str | None = None) -> dict[str, int]:
        """Delta vs an earlier snapshot() — the per-statement accounting
        the scan I/O counters (scan_files_read / scan_bytes_decoded /
        scan_cache_*) are read through; deterministic, so tests assert on
        it instead of wall clocks."""
        with self._lock:
            return {k: v - base.get(k, 0) for k, v in self._c.items()
                    if (prefix is None or k.startswith(prefix))
                    and v != base.get(k, 0)}

    def reset(self) -> None:
        with self._lock:
            self._c.clear()


counters = Counters()   # shared registry (shmem stats analog)


# fixed latency buckets (ms): wide enough for a cold XLA compile, fine
# enough for a warm cached statement — fixed so two processes' expositions
# aggregate bucket-by-bucket in Prometheus
DEFAULT_BUCKETS_MS = (1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
                      500.0, 1000.0, 2500.0, 5000.0, 10000.0, 30000.0)

# byte-sized histograms (executable memory footprints) bucket in MB:
# fine enough for point-query programs, wide enough for a v5e's 16 GB
DEFAULT_BUCKETS_MB = (1.0, 4.0, 16.0, 64.0, 256.0, 1024.0, 4096.0,
                      16384.0)


class Histograms:
    """Fixed-bucket latency histograms (the pg_stat_statements timing
    role, shaped for Prometheus exposition): statement latency, host
    data-path phases, queue waits. observe() is O(log buckets) under one
    lock — safe for every statement."""

    def __init__(self):
        self._lock = threading.Lock()
        # name -> [buckets tuple, per-bucket counts, overflow, sum, count]
        self._h: dict[str, list] = {}

    def observe(self, name: str, value_ms: float,
                buckets: tuple = DEFAULT_BUCKETS_MS) -> None:
        v = float(value_ms)
        with self._lock:
            h = self._h.get(name)
            if h is None:
                h = self._h[name] = [tuple(buckets),
                                     [0] * len(buckets), 0, 0.0, 0]
            bks, counts, _over, _s, _n = h
            i = bisect.bisect_left(bks, v)
            if i < len(bks):
                counts[i] += 1
            else:
                h[2] += 1
            h[3] += v
            h[4] += 1

    def snapshot(self) -> dict:
        """name -> {"buckets": [...], "counts": [...per bucket...],
        "sum": total_ms, "count": n}; counts are per-bucket (NOT
        cumulative) — the exposition cumulates."""
        with self._lock:
            return {name: {"buckets": list(h[0]), "counts": list(h[1]),
                           "sum": h[3], "count": h[4]}
                    for name, h in self._h.items()}

    def reset(self) -> None:
        with self._lock:
            self._h.clear()


histograms = Histograms()   # shared registry, same lifetime as `counters`


_METRIC_NAME_RE = re.compile(r"[^a-zA-Z0-9_:]")


def _metric_name(name: str, prefix: str) -> str:
    s = _METRIC_NAME_RE.sub("_", name)
    if s and s[0].isdigit():
        s = "_" + s
    return prefix + s


def _fmt_float(v: float) -> str:
    return repr(round(float(v), 6))


def prometheus_text(prefix: str = "ggtpu_") -> str:
    """Prometheus text exposition (format 0.0.4) over the process-wide
    counters, gauges, and histograms — the `gg metrics` / server
    {"op":"metrics"} payload. Counter vs gauge typing comes from the
    Counters gauge tags (set() marks a name as a gauge)."""
    lines: list[str] = []
    snap = counters.snapshot()
    gauges = counters.gauges()
    for name in sorted(snap):
        mn = _metric_name(name, prefix)
        lines.append(f"# TYPE {mn} {'gauge' if name in gauges else 'counter'}")
        lines.append(f"{mn} {snap[name]}")
    hsnap = histograms.snapshot()
    counter_names = {_metric_name(n, prefix) for n in snap}
    for name in sorted(hsnap):
        h = hsnap[name]
        mn = _metric_name(name, prefix)
        if mn in counter_names:
            # one exposition name cannot carry two TYPEs: a histogram
            # colliding with a counter/gauge family exports suffixed
            mn += "_hist"
        lines.append(f"# TYPE {mn} histogram")
        cum = 0
        for b, c in zip(h["buckets"], h["counts"]):
            cum += c
            lines.append(f'{mn}_bucket{{le="{b:g}"}} {cum}')
        lines.append(f'{mn}_bucket{{le="+Inf"}} {h["count"]}')
        lines.append(f"{mn}_sum {_fmt_float(h['sum'])}")
        lines.append(f"{mn}_count {h['count']}")
    return "\n".join(lines) + "\n"


class ClusterLog:
    def __init__(self, root: str, enabled: bool = True):
        self.dir = os.path.join(root, "log")
        self.enabled = enabled
        self._lock = threading.Lock()
        self._fh = None            # open append handle for _fh_day
        self._fh_day: datetime.date | None = None

    def _path(self, day: datetime.date | None = None) -> str:
        day = day or datetime.datetime.now(datetime.timezone.utc).date()
        return os.path.join(self.dir, f"ggtpu-{day.isoformat()}.csv")

    def _handle(self):
        """Open (or roll to today's) append handle; called under _lock."""
        day = datetime.datetime.now(datetime.timezone.utc).date()
        if self._fh is None or self._fh_day != day:
            if self._fh is not None:
                self._fh.close()
            os.makedirs(self.dir, exist_ok=True)
            self._fh = open(self._path(day), "a")
            self._fh_day = day
        return self._fh

    def log(self, severity: str, kind: str, message: str,
            duration_ms: float | None = None, rows: int | None = None) -> None:
        if not self.enabled:
            return
        # UTC to match the archive index / recovery_target_time: logfilter
        # timestamps are the natural way to pick a PITR target
        ts = datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="milliseconds").replace("+00:00", "Z")
        buf = io.StringIO()
        csv.writer(buf).writerow([
            ts, severity, os.getpid(), threading.current_thread().name,
            kind, "" if duration_ms is None else f"{duration_ms:.2f}",
            "" if rows is None else rows,
            message.replace("\n", " ")[:500],
        ])
        try:
            with self._lock:
                fh = self._handle()
                fh.write(buf.getvalue())
                fh.flush()   # line-durable for logfilter/crash forensics
        except OSError:
            pass   # logging must never fail the statement

    # convenience levels -------------------------------------------------
    def info(self, kind: str, message: str, **kw) -> None:
        self.log("INFO", kind, message, **kw)

    def error(self, kind: str, message: str, **kw) -> None:
        self.log("ERROR", kind, message, **kw)

    # ---- mining (the gplogfilter core) --------------------------------
    def files(self) -> list[str]:
        if not os.path.isdir(self.dir):
            return []
        return sorted(os.path.join(self.dir, f)
                      for f in os.listdir(self.dir)
                      if f.startswith("ggtpu-") and f.endswith(".csv"))


FIELDS = ("ts", "severity", "pid", "thread", "kind",
          "duration_ms", "rows", "message")


def read_entries(root: str) -> list[dict]:
    """Parse every log file under <root>/log into dicts (FIELDS keys)."""
    out = []
    log = ClusterLog(root)
    for path in log.files():
        with open(path, newline="") as f:
            for rec in csv.reader(f):
                if len(rec) != len(FIELDS):
                    continue   # torn line (crash mid-append)
                out.append(dict(zip(FIELDS, rec)))
    return out


def filter_entries(entries: list[dict], trouble: bool = False,
                   match: str | None = None, begin: str | None = None,
                   end: str | None = None,
                   min_duration_ms: float | None = None) -> list[dict]:
    """gplogfilter semantics: severity gate (-t), regex (-m), time window
    (-b/-e), slow-statement floor."""
    rx = re.compile(match, re.I) if match else None
    out = []
    for e in entries:
        if trouble and e["severity"] not in ("ERROR", "FATAL", "PANIC"):
            continue
        if rx is not None and not rx.search(e["message"]) \
                and not rx.search(e["kind"]):
            continue
        if begin and e["ts"] < begin:
            continue
        if end and e["ts"] > end:
            continue
        if min_duration_ms is not None:
            try:
                if float(e["duration_ms"] or 0) < min_duration_ms:
                    continue
            except ValueError:
                continue
        out.append(e)
    return out
