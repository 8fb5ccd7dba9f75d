"""Database session facade — the tcop/postgres.c + psql surface.

One object owns the catalog, storage, mesh, settings, and executor; .sql()
is exec_simple_query (reference: src/backend/tcop/postgres.c:1622): parse ->
bind -> parallelize -> compile -> dispatch -> gather. DDL/DML/utility
statements route to their handlers, mirroring ProcessUtility.
"""

from __future__ import annotations

import copy as _copy
import csv as _csv
import dataclasses as _dc
import glob as _glob
import hashlib
import io
import json as _json
import os
import shutil
import subprocess
import sys as _sys
import tempfile
import threading
import time
import uuid as _uuid
import warnings
from collections import OrderedDict as _OD
from contextlib import ExitStack, contextmanager as _contextmanager
from types import SimpleNamespace

import numpy as np

from greengage_tpu import expr as E
from greengage_tpu import types as T
from greengage_tpu.analysis.plancheck import validate_plan
from greengage_tpu.catalog import (Catalog, Column, DistPolicy, Partition,
                                   PolicyKind, TableSchema)
from greengage_tpu.config import Settings
from greengage_tpu.exec import staging as _staging
from greengage_tpu.exec.executor import (Executor, OutOfDeviceMemory,
                                         QueryError, Result)
from greengage_tpu.parallel import make_mesh
from greengage_tpu.planner import plan_query
from greengage_tpu.planner.logical import describe
from greengage_tpu.runtime import devprofile as _devprofile
from greengage_tpu.runtime import memaccount as _memaccount
from greengage_tpu.runtime import overload as _overload
from greengage_tpu.runtime import trace as _trace
from greengage_tpu.runtime.interrupt import (REGISTRY as _INTERRUPTS,
                                             StatementCancelled,
                                             check_interrupts)
from greengage_tpu.runtime.logger import counters as _counters
from greengage_tpu.runtime.logger import histograms as _histograms
from greengage_tpu.runtime.trace import TRACES as _TRACES
from greengage_tpu.sql import ast as A
from greengage_tpu.sql.binder import (Binder, _contains_agg,
                                       type_from_name)
from greengage_tpu.sql.parser import SqlError, parse
from greengage_tpu.storage import TableStore


class Database:
    def __init__(self, path: str | None = None, numsegments: int | None = None,
                 devices=None, mirrors: bool = False, multihost=None):
        import jax

        self.multihost = multihost   # parallel.multihost.MultihostRuntime
        devs = list(devices) if devices is not None else jax.devices()
        self._devices = devs
        if path is not None and os.path.exists(os.path.join(path, "catalog.json")):
            self.catalog = Catalog.load(path)
            if numsegments is None:
                numsegments = self.catalog.segments.numsegments
            elif self.catalog.segments.numsegments != numsegments:
                raise ValueError(
                    f"cluster width mismatch: on-disk {self.catalog.segments.numsegments}, "
                    f"requested {numsegments} (run gpexpand-style redistribution)")
        else:
            if numsegments is None:
                numsegments = len(devs)
            self.catalog = Catalog(numsegments, path=path, mirrors=mirrors)
        self.numsegments = numsegments
        if path is None:
            path = tempfile.mkdtemp(prefix="ggtpu_")
            self.catalog.path = path
        self.path = path
        is_worker = multihost is not None and not multihost.is_coordinator
        if not is_worker:
            self.catalog._save()   # persist width even before the first table
        self.store = TableStore(path, self.catalog)
        if not is_worker:
            # workers never write: recovery/reconciliation would race the
            # coordinator's in-flight transactions
            self.store.manifest.recover()   # in-doubt resolution on startup
            self.store.reconcile_widths()   # expansion crash recovery
        self.settings = Settings()
        # persisted cluster GUCs (the gpconfig role): settings.json holds
        # operator-set values every process (coordinator AND workers)
        # adopts at connect — the per-segment-config-file parity without
        # per-segment files, since settings steer lockstep mesh decisions
        # and must be identical everywhere anyway
        sp = os.path.join(path, "settings.json")
        # adoption failures are COLLECTED, never swallowed (guc.c rejects
        # bad values at SET; the deferred analog is a visible warning):
        # `gg state` prints these, so an operator typo in `gg config`
        # can't become silent divergence between set and running values
        self.settings_warnings: list[str] = []
        if os.path.exists(sp):
            try:
                with open(sp) as f:
                    for k, v in _json.load(f).items():
                        try:
                            self.settings.set(k, v)
                        except ValueError as e:
                            self.settings_warnings.append(
                                f"persisted setting {k!r}={v!r} not adopted: {e}")
            except (OSError, ValueError) as e:
                self.settings_warnings.append(f"settings.json unreadable: {e}")
        self._mh_degraded: str | None = None
        # measured cost-model primitives, if `gg checkperf --device
        # --apply` ran against this cluster (planner/cost.set_calibration;
        # workers load the same file, keeping plan choices in lockstep)
        cal_path = os.path.join(path, "calibration.json")
        from greengage_tpu.planner import cost as _cost

        cal = None
        if os.path.exists(cal_path):
            try:
                with open(cal_path) as f:
                    cal = _json.load(f)
            except (OSError, ValueError):
                cal = None
        # always (re)install — an uncalibrated cluster opened after a
        # calibrated one in the same process must get the defaults back
        _cost.set_calibration(cal)
        # feedback-driven cost calibration (planner/feedback.py): the
        # per-plan-digest store of observed actuals vs estimates,
        # persisted beside the catalog (and shipped by the standby meta
        # sync). Workers read the shared file but never write it — only
        # the coordinator persists, and workers adopt the coordinator's
        # applied scales from each statement broadcast instead of
        # reconciling locally (lockstep planning, parallel/multihost.py)
        from greengage_tpu.planner.feedback import FeedbackStore

        self.feedback = FeedbackStore(os.path.join(path, "feedback.json"),
                                      persist=not is_worker,
                                      settings=self.settings)
        # planner overlap credit for pipelined motion (same process-global
        # pattern; recomputed on SET motion_pipeline*)
        _cost.set_motion_overlap(self._motion_overlap_factor())
        # the store's read-path self-heal honors storage_autorepair live,
        # and the block-cache registry reads scan_cache_limit_mb live
        self.store.settings = self.settings
        self.store.blockcache.settings = self.settings
        # bound-plan LRU (plancache.c analog): (statement signature,
        # manifest version) -> (planned, consts, outs, exec_key, param
        # types). Literal-parameterized keys via sql/paramize.py; bounded
        # by the plan_cache_size GUC (_cached_plan)

        self._select_cache: dict = _OD()
        # per-thread (threaded SQL server): see the _plan_cache_info property
        self._pc_info_local = threading.local()
        # the DML statement a thread is running (_dml): its _DmlRun
        self._dml_local = threading.local()
        # statement signatures the binder proved unparameterizable: later
        # literal variants of the shape skip the doomed normalized bind
        # and go straight to the value-pinned plan (bounded backstop)
        self._paramize_fallback: set = set()
        self.mesh = make_mesh(numsegments, devs)
        self.executor = Executor(self.catalog, self.store, self.mesh,
                                 numsegments, self.settings,
                                 multihost=multihost)
        # measured admission: the executor prefers the store's measured
        # per-shape footprint and persisted capacity hints once a shape
        # is warm (exec/executor.py _admission_bytes / run)
        self.executor.feedback = self.feedback
        if not is_worker:
            # spill segments whose owning process died mid-pass (tiered
            # workfile; live paths clean up in their own finally)
            from greengage_tpu.exec import workfile as _workfile
            _workfile.sweep_orphans(
                _workfile.spill_dir_of(self.settings, self.store))
        # vectorized serving pipeline (exec/batchserve.py): created
        # lazily on the first batch-eligible statement so the two
        # pipeline threads only exist when batch_serving_enabled is on
        self._batch_server = None
        self._batch_server_mu = threading.Lock()
        # last brownout state this Database observed (runtime/overload.py
        # is process-wide; the edge effects — prompt cache eviction, the
        # log line — are per-Database and applied by _overload_tick)
        self._overload_seen = False
        from greengage_tpu.runtime.dtm import DtmSession
        from greengage_tpu.runtime.fts import FtsProber
        from greengage_tpu.runtime.replication import Replicator

        from greengage_tpu.runtime.resqueue import ResourceQueue

        # transaction state is PER THREAD: the SQL server runs one thread
        # per connection, so each wire connection (and each direct-API
        # thread) gets its own transaction, like one backend per libpq
        # connection (reference: src/backend/cdb/cdbtm.c MyTmGxact being
        # per-backend state)
        self._DtmSession = DtmSession
        self._dtm_local = None   # created below once threading is imported
        self.resqueue = ResourceQueue(self.settings)
        from greengage_tpu.runtime.resgroup import (ResourceGroup,
                                                    ResourceGroupManager)

        self.resgroups = ResourceGroupManager(
            self.settings,
            {d["name"]: ResourceGroup.from_dict(d)
             for d in self.catalog.resource_groups})
        self.replicator = (Replicator(self.store, self.catalog.segments)
                           if self.catalog.segments.has_mirrors() else None)
        self.fts = FtsProber(self.catalog.segments, self.mesh, store=self.store,
                             on_change=self.catalog._save)
        if not is_worker:
            # topology gauge (asserted by the reform tests; `gg ps` shows it)
            _counters.set("mh_topology_version", self.catalog.segments.version)
            # coordinator liveness beat (runtime/standby.py): stamp at
            # init so a registered standby's watcher sees this primary
            # alive before its first commit; the post-commit hook and the
            # FTS prober cadence keep it fresh thereafter
            from greengage_tpu.runtime import standby as _standby

            if _standby.registered_standby(self.path) is not None:
                _standby.primary_beat(self.path,
                                      self.catalog.segments.version)
                # the probe cadence re-stamps the beat while idle, so an
                # idle-but-alive primary never looks dead to the watcher
                self.fts.start()
        from greengage_tpu.runtime.logger import ClusterLog

        # elog/syslogger analog: CSV logs under <cluster>/log (mined by
        # `gg logfilter`); workers stay quiet (the coordinator logs)
        self.log = ClusterLog(self.path, enabled=not is_worker)
        self.store.log = self.log   # repair/quarantine events land in the log
        self.log.info("lifecycle", f"database ready: {numsegments} segments, "
                      f"{len(devs)} devices")
        for w in self.settings_warnings:
            self.log.log("WARNING", "settings", w)
        self.stat_activity: list[dict] = []   # recent-query ring (gpperfmon analog)
        self._cursors: dict[str, object] = {}  # parallel retrieve cursors
        self._cursor_owner: dict[str, int] = {}  # cursor -> thread ident
        # monotonic DROP TABLE log: an in-flight (unlocked) DECLARE
        # compares its pre-run mark against this at registration to catch
        # a table dropped out from under it mid-run. _drop_base counts
        # pruned entries (the log is cleared whenever no DECLARE is in
        # flight, so it cannot grow with long-lived drop-heavy sessions)
        self._drop_log: list[str] = []
        self._drop_base = 0
        self._inflight_declares = 0
        self._load_extensions()
        # serializes write/DDL statements across threads sharing this
        # Database (server connections); readers stay lock-free on
        # manifest snapshots. Autocommit single-table appends take the
        # SHARED mode plus a per-table lock, so appenders to different
        # tables run concurrently end-to-end (per-table delta manifests
        # make their commits contention-free too — docs/ROBUSTNESS.md)

        self._write_lock = _RWLock()
        self._table_locks: dict[str, threading.RLock] = {}
        self._table_locks_mu = threading.Lock()
        # post-commit replication/archive is not reentrancy-safe for
        # concurrent shared appenders: serialize it separately
        self._pc_lock = threading.Lock()
        self._dtm_local = threading.local()
        # streaming ingest plane (runtime/ingest.py): long-lived COPY
        # streams committing micro-batches through the write-intent path
        from greengage_tpu.runtime.ingest import StreamIngestor

        self.ingest = StreamIngestor(self)
        # control-channel liveness: the channel reads its deadlines live
        # from THIS session's settings (SET mh_* applies immediately), and
        # the coordinator heartbeats workers between statements so an
        # idle-time partition is caught before the next dispatch
        if multihost is not None and multihost.channel is not None:
            multihost.channel.settings = self.settings
            if multihost.is_coordinator:
                try:
                    multihost.channel.start_heartbeat()
                except Exception as e:
                    self.log.error("multihost", f"heartbeat start failed: {e}")

    def _motion_overlap_factor(self) -> float:
        """Redistribute overlap credit from the motion_pipeline* GUCs
        (planner/cost.set_motion_overlap). The host bucket pipeline alone
        hides a modest slice of each exchange behind neighboring compute;
        sub-exchange splitting deepens the device-timeline overlap — up to
        half the transfer hidden at the deepest split. Deliberately
        conservative: the credit shapes plan choice between motion
        strategies, it does not promise free transfers."""
        if not bool(getattr(self.settings, "motion_pipeline", True)):
            return 1.0
        nb = max(int(getattr(self.settings, "motion_pipeline_buckets", 1)), 1)
        if nb <= 1:
            return 0.9
        # 2 buckets -> 0.75, 4 -> 0.625, >=8 -> floors at 0.5625
        return max(0.5, 0.5 + 0.5 / min(nb, 8))

    @property
    def dtm(self):
        """The calling thread's transaction session (lazily created)."""
        d = getattr(self._dtm_local, "dtm", None)
        if d is None:
            d = self._DtmSession(self.store)
            self._dtm_local.dtm = d
        return d

    def abort_if_active(self) -> None:
        """Roll back the calling thread's open transaction, if any — the
        server calls this when a connection drops mid-transaction."""
        cur = self.dtm.current
        if cur is not None and cur.state == "active":
            with self._write_lock:
                self.dtm.abort()

    def _load_extensions(self) -> None:
        """Best-effort: a recorded extension whose module is gone must not
        brick the cluster (PG opens the database and errors at use); its
        functions simply stay unknown."""

        from greengage_tpu import extensions as X

        for name in self.catalog.extensions:
            try:
                X.load(name, cluster_path=self.path)
            except ValueError as e:
                warnings.warn(f"extension {name!r} failed to load: {e}")

    # ------------------------------------------------------------------
    def sql(self, text: str):
        """Execute one or more statements; returns the last statement's
        Result (or a status string for DDL/DML).

        Every call registers a StatementContext in the process-wide
        interrupt registry (runtime/interrupt.py) — the backend-entry
        CHECK_FOR_INTERRUPTS arming: `gg cancel`, statement_timeout_s,
        the runaway cleaner, and client disconnects all set its flag, and
        the statement dies at its next cancellation point with a typed
        cause. Nested calls (recursive-CTE fixpoints, retry redispatch)
        share the outermost statement's context."""
        ctx, _outer = _INTERRUPTS.enter(
            text, timeout_s=float(self.settings.statement_timeout_s))
        # statement trace (runtime/trace.py, the gpperfmon query-detail
        # role): trace id == statement id, so `gg ps` ids address `gg
        # trace` directly; nested calls share the outermost trace
        tr, t_outer = _TRACES.enter(
            ctx.statement_id, text,
            enabled=bool(getattr(self.settings, "trace_enabled", True)),
            ring_size=int(getattr(self.settings, "trace_ring_size", 64)))
        # per-statement memory account (runtime/memaccount.py, the
        # memaccounting.c owner tree): staging/block-cache/spill/device
        # charges land here; dumped on OOM, served by `gg mem`
        acct, a_outer = _memaccount.ACCOUNTS.enter(ctx.statement_id, text)
        t0 = time.monotonic()
        root = (tr.begin("statement", cat="statement")
                if tr is not None and t_outer else None)
        if t_outer:
            # slow-log digest source: _cached_plan stashes the bound plan
            # here; cleared per statement so a slow DML can't pick up the
            # previous SELECT's digest
            self._pc_info_local.planned = None
            # memory-pressure brownout (runtime/overload.py): evaluate
            # the process-wide controller once per outermost statement
            # (rate-limited inside) and apply edge effects
            self._overload_tick()
        try:
            return self._sql_inner(text)
        except StatementCancelled as e:
            # one count (and one log line) per cancelled statement,
            # whichever cancellation point raised — the
            # statements_cancelled_<cause> family; _sql_inner's generic
            # error logging skips cancellations so this is the only row
            if not ctx.counted:
                ctx.counted = True
                _counters.inc(f"statements_cancelled_{e.cause}")
                if self.settings.log_statement:
                    self.log.error("statement",
                                   f"{e} [cause={e.cause}] -- in: "
                                   f"{text.strip()[:200]}")
            raise
        except OutOfDeviceMemory as e:
            # OOM forensics (memaccounting.c's OOM owner-tree dump):
            # mem-<id>.json beside the slow-log traces, carrying the full
            # per-owner accounting snapshot + the offending executable's
            # memory analysis
            if a_outer:
                self._dump_mem_forensics(e, ctx.statement_id, text)
            raise
        finally:
            if root is not None:
                tr.end(root)
            if t_outer:
                dur_ms = (time.monotonic() - t0) * 1e3
                _histograms.observe("statement_ms", dur_ms)
                self._maybe_log_slow(text, dur_ms, ctx.statement_id)
            _memaccount.ACCOUNTS.exit(acct)
            _TRACES.exit(tr)
            _INTERRUPTS.exit(ctx)

    def _overload_tick(self) -> None:
        """Brownout edge application (docs/ROBUSTNESS.md "Overload
        protection"): evaluate the process-wide controller and, on a
        transition this Database has not yet seen, apply the per-database
        effects — prompt block-cache eviction to the shrunken budget on
        enter (limit_bytes already reads the brownout factor; eviction
        would otherwise wait for the next insert) — and log the edge.
        Never raises: overload protection must not fail the statement it
        is protecting."""
        try:
            state = _overload.CONTROLLER.evaluate(self.settings)
            if state == self._overload_seen:
                return
            self._overload_seen = state
            snap = _overload.CONTROLLER.snapshot()
            with _trace.span("brownout-transition", cat="overload",
                             entered=state):
                self.store.blockcache.evict_to_fit()
            if state:
                self.log.log(
                    "WARNING", "overload",
                    f"brownout entered: {snap.get('reason')} — "
                    f"block-cache budget x{snap.get('cache_factor')}, "
                    "batch serving disabled, admissions prefer the "
                    "spill tier")
            else:
                self.log.info(
                    "overload",
                    "brownout cleared: pressure below the exit "
                    "threshold for brownout_exit_s")
        except Exception:
            pass

    def _maybe_log_slow(self, text: str, dur_ms: float,
                        statement_id: int) -> None:
        """Slow-statement log (log_min_duration_statement analog): any
        statement at/above log_min_duration_ms writes one slow_statement
        row carrying the plan digest and trace id, and exports the trace
        JSON beside the CSV logs for post-mortems (`gg trace` serves the
        same ring entry while the process lives). Never raises — logging
        must not take the query path down."""
        try:
            lm = float(getattr(self.settings, "log_min_duration_ms", -1.0))
            if lm < 0 or dur_ms < lm:
                return
            # digest from the plan this statement ACTUALLY bound (stashed
            # by _cached_plan) — never re-enter the plan cache here: a
            # plan_hash() call would double-count plan_cache_hit/miss,
            # record spurious spans, and on an evicted entry re-plan
            # (scalar subqueries included) on the query path
            digest = None
            planned = getattr(self._pc_info_local, "planned", None)
            if planned is not None:
                from greengage_tpu.planner.logical import describe as _desc

                digest = hashlib.sha1(
                    _desc(planned).encode()).hexdigest()[:16]
            _counters.inc("slow_statements")
            self.log.log(
                "WARNING", "slow_statement",
                f"duration {dur_ms:.1f} ms >= log_min_duration_ms={lm:g} "
                f"[trace={statement_id} plan={digest or '-'}]: "
                f"{text.strip()[:200]}",
                duration_ms=dur_ms)
            tr = _TRACES.current()
            if tr is not None and self.log.enabled:
                # the registry sets dur_ms at exit (after this dump):
                # record the measured duration now so the exported JSON
                # carries it instead of null
                tr.dur_ms = dur_ms
                os.makedirs(os.path.join(self.path, "log"), exist_ok=True)
                path = os.path.join(self.path, "log",
                                    f"trace-{statement_id}.json")
                with open(path, "w") as f:
                    _json.dump(_trace.to_chrome(tr), f)
        except Exception:
            pass

    def _dump_mem_forensics(self, e: OutOfDeviceMemory,
                            statement_id: int, text: str) -> None:
        """Write ``mem-<statement id>.json`` beside the slow-log traces
        (<cluster>/log): the per-owner accounting tree, the offending
        executable's memory_analysis, the admission estimate, and the
        live device stats at failure. Never raises — forensics must not
        replace the typed error the client is owed."""
        try:
            if not self.log.enabled:
                return
            payload = {
                "statement_id": statement_id,
                "sql": text.strip()[:500],
                "error": str(e),
                "est_bytes": e.est_bytes,
                "memory_analysis": e.mem_analysis,
                "accounting": e.snapshot,
                "ts_unix_s": round(time.time(), 3),
            }
            os.makedirs(os.path.join(self.path, "log"), exist_ok=True)
            path = os.path.join(self.path, "log",
                                f"mem-{statement_id}.json")
            with open(path, "w") as f:
                _json.dump(payload, f, indent=1, default=str)
            self.log.error("out_of_device_memory",
                           f"{e} [mem dump={path}]")
        except Exception:
            pass

    def _sql_inner(self, text: str):
        if self.multihost is not None and self.multihost.is_coordinator:
            return self._coordinator_sql(text)
        out = None
        with _trace.span("parse", cat="sql"):
            stmts = parse(text)
        for i, stmt in enumerate(stmts):
            # per-statement attribution even in a multi-statement batch
            what = text.strip() if len(stmts) == 1 else \
                f"[{i + 1}/{len(stmts)} {type(stmt).__name__}] {text.strip()}"
            t0 = time.monotonic()
            try:
                out = self._execute(stmt)
            except Exception as e:
                # cancellations log once in sql()'s handler, with cause
                if self.settings.log_statement \
                        and not isinstance(e, StatementCancelled):
                    self.log.error("statement", f"{e} -- in: {what}",
                                   duration_ms=(time.monotonic() - t0) * 1e3)
                raise
            if self.settings.log_statement:
                self.log.info(
                    "statement", what,
                    duration_ms=(time.monotonic() - t0) * 1e3,
                    rows=(len(out) if hasattr(out, "columns") else None))
            if self.settings.archive_mode and self.settings.archive_dir \
                    and isinstance(stmt, (
                        A.CreateTableStmt, A.DropTableStmt, A.AlterTableStmt,
                        A.CreateExternalTableStmt, A.CreateExtensionStmt,
                        A.ResourceGroupStmt, A.CreateIndexStmt,
                        A.DropIndexStmt)):
                # DDL moves the catalog without a manifest commit: refresh
                # the archived catalog copy (write paths archive via
                # _post_commit)
                from greengage_tpu.storage.archive import Archive

                try:
                    Archive(self.settings.archive_dir).archive_now(
                        self.path, self.store)
                except Exception as e:
                    self.log.error("archive", f"archiving failed: {e}")
        return out

    # ---- multi-host statement protocol (parallel/multihost.py) ---------
    @staticmethod
    def _needs_mesh(stmt) -> bool:
        if isinstance(stmt, (A.SelectStmt, A.UnionStmt)):
            return True
        if isinstance(stmt, A.ExplainStmt):
            return stmt.analyze
        if isinstance(stmt, A.DeleteStmt):
            return stmt.where is not None
        if isinstance(stmt, A.DeclareCursorStmt):
            return True   # the DECLARE runs the mesh program
        return isinstance(stmt, A.UpdateStmt)

    def plan_hash(self, text_or_stmt) -> str | None:
        """Deterministic digest of the plan a SELECT-shaped statement
        produces here (structure + column ids + loci + row estimates):
        the coordinator attaches it to every mesh broadcast and workers
        verify theirs matches BEFORE entering the collectives — the
        lockstep assertion VERDICT r3 #8 asked for. None when the
        statement has no single pre-plannable query."""

        from greengage_tpu.planner.logical import describe

        stmt = (parse(text_or_stmt)[0] if isinstance(text_or_stmt, str)
                else text_or_stmt)
        if isinstance(stmt, A.DeclareCursorStmt):
            stmt = stmt.query
        if not isinstance(stmt, (A.SelectStmt, A.UnionStmt)):
            return None
        if isinstance(stmt, A.SelectStmt) and not stmt.from_:
            return None
        # planning errors propagate: on the coordinator they fail the
        # statement BEFORE the broadcast; on a worker they fail the
        # readiness ack — swallowing them here would let a worker that
        # cannot re-plan enter (and hang) the collectives
        planned, _, _, _ = self._cached_plan(stmt)
        return hashlib.sha1(describe(planned).encode()).hexdigest()[:16]

    # ---- topology state (degraded <-> N-1 <-> full) --------------------
    def mh_state(self) -> dict:
        """The dispatch topology as `gg ps` / the server status frame show
        it: full (whole gang serving), n-1 (re-formed over survivors),
        degraded (single-process fallback), or local (no multihost)."""
        segs = self.catalog.segments
        if self.multihost is None or self.multihost.channel is None \
                or not self.multihost.is_coordinator:
            out = {"state": "local", "topology_version": segs.version}
            self._mh_state_standby(out)
            return out
        ch = self.multihost.channel
        if getattr(self, "_mh_degraded", None):
            state = "degraded"
        elif hasattr(ch, "is_partial") and ch.is_partial():
            state = "n-1"
        else:
            state = "full"
        out = {"state": state, "topology_version": segs.version,
               "expected_workers": getattr(ch, "expected_workers", None),
               "active_workers": (len(ch.active_ids())
                                  if hasattr(ch, "active_ids") else None)}
        if getattr(self, "_mh_degraded", None):
            out["reason"] = self._mh_degraded
        self._mh_state_standby(out)
        return out

    def _mh_state_standby(self, out: dict) -> None:
        """Attach the registered standby's replication health (path, lag
        in commits, cumulative ship failures) so `gg ps` / the status
        frame surface a silently-failing sync instead of hiding it."""
        from greengage_tpu.runtime import standby as _standby
        from greengage_tpu.runtime.logger import counters as _c

        sb = _standby.registered_standby(self.path)
        if sb is None:
            return
        out["standby"] = {
            "path": sb,
            "lag_commits": _standby.lag(self.path),
            "sync_fail_total": int(_c.snapshot().get(
                "standby_sync_fail_total", 0)),
        }

    def _mh_distributed_active(self) -> bool:
        """True when a jax.distributed data plane is live: its global mesh
        cannot re-form over survivors without a runtime re-init, so worker
        death must take the degraded path there. Control-plane-only gangs
        (each process owns its full local mesh; this environment's mode)
        re-form freely — pjit resolves the mesh at call site, so cached
        executables re-bind without recompiling."""
        try:
            from jax._src import distributed as _dist

            return _dist.global_state.client is not None
        except Exception:
            return False

    def _mh_worker_lost(self, reason: str, dead_pid=None) -> None:
        """Topology failover entry: a worker died/hung. Prefer N-1 mesh
        re-formation over the survivors (the cdbgang shrink + mirror
        promotion the reference performs); fall back to the degraded
        single-process path when re-formation is disabled, impossible
        (live jax.distributed data plane), or fails."""
        if getattr(self, "_mh_degraded", None):
            return
        if self.settings.mh_reform_enabled \
                and not self._mh_distributed_active():
            if self._mh_reform(reason, dead_pid):
                return
        self._mh_degrade(reason)

    def _mh_reform(self, reason: str, dead_pid=None) -> bool:
        """Re-form the gang over the SURVIVORS (N-1): quiesce the channel
        (survivors redial the kept listener within seconds — worker_loop
        treats the teardown as a lost coordinator and reconnects), promote
        cross-host mirror roots for contents whose storage died with the
        worker, bump the topology version, adopt whoever redialed before
        mh_reform_deadline_s, and replay the settings/topology sync. The
        re-formed gang serves every later statement — DML included, since
        manifest commits are coordinator-local — and the kept listener
        plus the rejoin accept loop restore full strength when the lost
        worker returns."""
        from greengage_tpu.parallel.multihost import WorkerDied
        from greengage_tpu.runtime.faultinject import FaultError, faults
        from greengage_tpu.runtime.retry import Deadline

        ch = self.multihost.channel
        if not hasattr(ch, "adopt_pending"):
            return False
        try:
            faults.check("mesh_reform")
        except FaultError as e:
            self.log.error("multihost", f"mesh re-formation failed "
                                        f"(fault injected): {e}")
            return False
        who = f"worker {dead_pid}" if dead_pid is not None else "a worker"
        self.log.error("multihost",
                       f"{who} lost; re-forming the gang over survivors: "
                       f"{reason}")
        survivors_want = max(0, len(ch.active_ids()) - 1)
        try:
            ch.quiesce()
        except Exception as e:
            self.log.error("multihost", f"quiesce failed: {e}")
            return False
        # mirror promotion over surviving storage (ftsprobe.c:968 role):
        # probe every content NOW — one whose primary tree died with the
        # worker's host gets its in-sync cross-host mirror promoted, so
        # the N-1 topology serves every content from a surviving root
        try:
            faults.check("mirror_promote_during_reform")
            if self.catalog.segments.has_mirrors():
                self.fts.probe_once()
        except FaultError as e:
            self.log.error("multihost",
                           f"mirror promotion during re-formation failed "
                           f"(fault injected): {e}")
            return False
        except Exception as e:
            self.log.error("multihost", f"re-formation FTS probe failed: {e}")
        # the FTS-version bump: cached dispatch topology is invalid, and
        # rejoining workers must observe this exact version in the sync
        self.catalog.segments.version += 1
        try:
            self.catalog._save()
        except Exception as e:
            self.log.error("multihost", f"topology save failed: {e}")
        dl = Deadline(float(self.settings.mh_reform_deadline_s))
        while ch.pending_count() < survivors_want and not dl.expired:
            # re-formation must run to completion-or-fallback even when
            # the triggering statement was cancelled: aborting mid-reform
            # leaves a half-promoted topology no later statement can use.
            # Bounded by mh_reform_deadline_s.
            time.sleep(0.02)   # gg:ok(interrupts)
        ch.adopt_pending()
        try:
            self._mh_sync_gang(phase="reform sync")
        except (WorkerDied, RuntimeError, OSError) as e:
            self.log.error("multihost", f"gang re-formation failed: {e}")
            try:
                ch.quiesce()
            except Exception:
                pass
            return False
        self._mh_degraded = None
        _counters.inc("mh_reform_total")
        _counters.set("mh_topology_version", self.catalog.segments.version)
        try:
            ch.start_heartbeat()
        except Exception:
            pass
        st = self.mh_state()
        self.log.info(
            "multihost",
            f"gang re-formed: {st['state']} with "
            f"{st['active_workers']}/{st['expected_workers']} workers "
            f"(topology v{st['topology_version']})")
        return True

    def _mh_sync_gang(self, phase: str = "rejoin sync") -> None:
        """Replay the settings + topology sync against the current gang;
        raises WorkerDied/RuntimeError when any member is gone or reports
        a stale topology version (shared directory out of sync)."""

        from greengage_tpu.parallel.multihost import WorkerDied

        payload = {f.name: getattr(self.settings, f.name)
                   for f in _dc.fields(self.settings)
                   if not f.name.startswith("_")}
        want_v = self.catalog.segments.version
        acks = self.multihost.channel.broadcast(
            {"op": "sync", "settings": payload, "topology_version": want_v},
            deadline="mh_ready_deadline", phase=phase)
        stale = [a for a in acks if a.get("topology_version") != want_v]
        if stale:
            raise WorkerDied(
                f"rejoined worker reports topology version "
                f"{stale[0].get('topology_version')}, coordinator has "
                f"{want_v} — shared directory out of sync")

    def _mh_try_restore_full(self) -> None:
        """While an N-1 gang serves, the lost worker may redial the kept
        listener at any time; adopting it restores the full topology.
        Called at each statement boundary — cheap (one lock + len)."""
        from greengage_tpu.parallel.multihost import WorkerDied

        ch = self.multihost.channel
        if not hasattr(ch, "pending_count") or not hasattr(ch, "is_partial"):
            return
        if not ch.is_partial() or ch.pending_count() == 0:
            return
        ch.adopt_pending()
        self.catalog.segments.version += 1
        try:
            self.catalog._save()
        except Exception:
            pass
        try:
            self._mh_sync_gang(phase="restore sync")
        except (WorkerDied, RuntimeError, OSError) as e:
            # the rejoiner (or a survivor) is unusable: fall back to a
            # fresh re-formation over whoever still answers
            self._mh_worker_lost(f"gang restore failed: {e}")
            return
        _counters.set("mh_topology_version", self.catalog.segments.version)
        st = self.mh_state()
        self.log.info(
            "multihost",
            f"gang restored: {st['state']} with "
            f"{st['active_workers']}/{st['expected_workers']} workers "
            f"(topology v{st['topology_version']})")

    def _mh_degrade(self, reason: str) -> None:
        """A worker died: the global device mesh can no longer rendezvous.
        Mark the cluster degraded — every later mesh statement re-forms as
        a single-process session over the SHARED cluster directory (which
        holds every segment's storage) in a subprocess, the
        mirror-failover analog for a lost compute host."""
        self._mh_degraded = reason
        self.log.error("multihost", f"worker lost; degraded to local: {reason}")
        # re-form the topology over surviving storage (ftsprobe.c:968
        # role): probe every content NOW — a content whose primary tree
        # died with the worker's host gets its in-sync mirror promoted,
        # so the re-formed service answers from the mirror trees (which
        # cross-host placement keeps on surviving roots)
        try:
            if self.catalog.segments.has_mirrors():
                self.fts.probe_once()
                self.catalog._save()
        except Exception as e:
            self.log.error("multihost", f"post-death FTS probe failed: {e}")
        # quiesce, don't close: worker connections tear down but the
        # listener stays open so a restarted/woken worker can rejoin and
        # the gang can re-form (docs/ROBUSTNESS.md, _mh_try_recover)
        try:
            self.multihost.channel.quiesce()
        except Exception:
            pass
        # detach the distributed runtime WITHOUT the shutdown barrier: it
        # can never complete against a dead peer — calling shutdown()
        # blocks for the barrier timeout, and leaving it for atexit turns
        # a served degradation into a crash at interpreter exit. Dropping
        # the handles makes both a no-op; the stashed references keep the
        # C++ objects from running disconnect destructors mid-session.
        try:
            from jax._src import distributed as _dist

            self._mh_detached = (_dist.global_state.client,
                                 _dist.global_state.service)
            _dist.global_state.client = None
            _dist.global_state.service = None
        except Exception:
            pass

    def mh_try_recover(self) -> bool:
        """Gang recovery (the cdbgang re-formation role): while DEGRADED,
        adopt the fully-reconnected gang and leave degraded mode; while an
        N-1 partial gang serves, adopt a rejoined worker back to full
        strength. Safe to call any time; also attempted automatically at
        each statement. True when mesh dispatch is available (full or
        N-1)."""
        if self.multihost is None or not self.multihost.is_coordinator:
            return False
        if not getattr(self, "_mh_degraded", None):
            self._mh_try_restore_full()
            return True
        return self._mh_try_recover()

    def _mh_try_recover(self) -> bool:
        from greengage_tpu.parallel.multihost import WorkerDied

        ch = self.multihost.channel
        if not (hasattr(ch, "rejoin_ready") and ch.rejoin_ready()):
            return False
        # settle the topology BEFORE workers re-plan against it: probe now
        # (promotions during the degraded window persist), then require
        # every rejoined worker to report the same topology version — the
        # FTS-version check the reference dispatcher runs per gang
        if self.catalog.segments.has_mirrors():
            try:
                self.fts.probe_once()
                self.catalog._save()
            except Exception as e:
                self.log.error("multihost", f"pre-rejoin FTS probe failed: {e}")
        try:
            ch.adopt_rejoined()
            self._mh_sync_gang(phase="rejoin sync")
        except (WorkerDied, RuntimeError, OSError) as e:
            self.log.error("multihost", f"gang rejoin failed: {e}")
            try:
                ch.quiesce()   # back to accepting reconnections
            except Exception:
                pass
            return False
        # restore the distributed-runtime handles stashed at degrade (the
        # data plane was never torn down — a hung-then-recovered worker's
        # collectives can rendezvous again)
        if getattr(self, "_mh_detached", None) is not None:
            try:
                from jax._src import distributed as _dist

                (_dist.global_state.client,
                 _dist.global_state.service) = self._mh_detached
            except Exception:
                pass
            self._mh_detached = None
        self._mh_degraded = None
        try:
            ch.start_heartbeat()
        except Exception:
            pass
        _counters.set("mh_topology_version", self.catalog.segments.version)
        self.log.info("multihost",
                      f"gang recovered: mesh dispatch restored "
                      f"(topology v{self.catalog.segments.version})")
        return True

    def cluster_inject_fault(self, name: str, type: str = "error",
                             segment: int | None = None, occurrences: int = 1,
                             sleep_s: float = 0.1, start_after: int = 0,
                             reset: bool = False) -> list[dict]:
        """gp_inject_fault dispatched to segments: arm (or reset) a named
        fault point in every WORKER process over the control channel.
        Coordinator-side points are armed directly via
        runtime.faultinject.faults."""
        if self.multihost is None or not self.multihost.is_coordinator \
                or getattr(self, "_mh_degraded", None):
            raise SqlError("cluster_inject_fault needs a non-degraded "
                           "multihost coordinator")
        return self.multihost.channel.broadcast(
            {"op": "fault", "name": name, "type": type, "segment": segment,
             "occurrences": occurrences, "sleep_s": sleep_s,
             "start_after": start_after, "reset": reset},
            deadline="mh_ready_deadline", phase="fault")

    def _degraded_sql(self, text: str):
        """Serve one statement from a fresh single-process subprocess over
        the shared directory (all segments local). Transactions cannot
        span subprocesses; everything else completes with full results."""

        if self.dtm.current is not None and self.dtm.current.state == "active":
            raise SqlError("cluster is degraded (worker died); transactions "
                           "cannot continue — ROLLBACK and retry")
        if any(isinstance(st, A.DeclareCursorStmt) for st in parse(text)):
            # a cursor declared in the throwaway subprocess would vanish
            # before RETRIEVE: refuse instead of reporting false success
            raise SqlError("parallel retrieve cursors are unavailable while "
                           "the cluster is degraded")
        child = (
            "import os, sys, json\n"
            "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            "flags = [f for f in os.environ.get('XLA_FLAGS', '').split()\n"
            "         if 'host_platform_device_count' not in f]\n"
            "flags.append('--xla_force_host_platform_device_count=%d')\n"
            "os.environ['XLA_FLAGS'] = ' '.join(flags)\n"
            "sys.path.insert(0, %r)\n"
            "import greengage_tpu\n"
            "db = greengage_tpu.connect(%r, numsegments=%d)\n"
            "r = db.sql(sys.stdin.read())\n"
            "def enc(x):\n"
            "    try:\n"
            "        import numpy as np\n"
            "        if isinstance(x, np.generic): x = x.item()\n"
            "    except Exception: pass\n"
            "    return x if isinstance(x, (int, float, str, bool,\n"
            "                               type(None))) else str(x)\n"
            "if isinstance(r, str):\n"
            "    print('DEGRADED:' + json.dumps({'status': r}), flush=True)\n"
            "else:\n"
            "    print('DEGRADED:' + json.dumps(\n"
            "        {'columns': list(r.columns),\n"
            "         'rows': [[enc(x) for x in row] for row in r.rows()]}),\n"
            "        flush=True)\n"
        ) % (self.numsegments,
             os.path.dirname(os.path.dirname(os.path.dirname(
                 os.path.abspath(__file__)))),
             self.path, self.numsegments)
        proc = subprocess.run(
            [_sys.executable, "-c", child], input=text, text=True,
            capture_output=True, timeout=900)
        lines = [ln for ln in proc.stdout.splitlines()
                 if ln.startswith("DEGRADED:")]
        if proc.returncode != 0 or not lines:
            raise QueryError(
                f"degraded execution failed (rc={proc.returncode}): "
                f"{proc.stderr[-800:]}")
        payload = _json.loads(lines[-1][len("DEGRADED:"):])
        if "status" in payload:
            return payload["status"]
        return _DegradedResult(payload["columns"], payload["rows"])

    @staticmethod
    def _is_read_only(stmt) -> bool:
        """The dispatcher's retryable classification: statements that
        never touch the manifest/catalog may be transparently redispatched
        after a dispatch failure; anything else is a write and the DTM's
        exactly-once guarantee decides (= no auto-retry)."""
        return isinstance(stmt, (A.SelectStmt, A.UnionStmt, A.ExplainStmt,
                                 A.DeclareCursorStmt))

    def _dispatch_failover(self, stmt, text: str, err, is_retry: bool):
        """A worker died/hung BEFORE anyone entered a collective, so the
        statement never ran. Read-only statements retry transparently
        ONCE: when the gang already re-formed over survivors (N-1 path),
        redispatch immediately; while DEGRADED, wait up to
        mh_retry_window_s for recovery first and otherwise complete on
        the degraded local path as before. Write statements surface the
        error without re-execution: the commit record was never written,
        so nothing committed, and only an explicit client retry (or a
        LATER statement) may run it — exactly-once is the DTM's to keep,
        never the dispatcher's to gamble."""
        from greengage_tpu.runtime.faultinject import faults
        from greengage_tpu.runtime.retry import Deadline

        if not self._is_read_only(stmt):
            raise QueryError(
                f"worker died mid-dispatch; write statement was NOT "
                f"auto-retried (nothing committed — retry explicitly if "
                f"desired): {err}")
        window = float(self.settings.mh_retry_window_s)

        def redispatch():
            # the window a test can force open/shut: sleep widens
            # the race, error fails the redispatch path itself
            faults.check("retry_redispatch")
            _counters.inc("statements_retried")
            self.log.info(
                "statement",
                f"gang re-formed; redispatching read-only "
                f"statement after dispatch failure: "
                f"{text.strip()[:160]}")
            return self._coordinator_sql(text, _is_retry=True)

        # window 0 disables transparent redispatch ENTIRELY — even when an
        # N-1 re-formation already re-bound the gang (the operator opted
        # out of re-executing reads, not just out of waiting)
        if not is_retry and window > 0 \
                and not getattr(self, "_mh_degraded", None):
            return redispatch()     # N-1 re-formation already re-bound
        if not is_retry and window > 0:
            dl = Deadline(window)
            while True:
                if self.mh_try_recover():
                    return redispatch()
                if dl.expired:
                    break
                # retry-window wait = a cancellation point: a cancelled
                # statement must not sit out the full window first
                check_interrupts()
                time.sleep(0.05)
        return self._degraded_sql(text)

    def _coordinator_sql(self, text: str, _is_retry: bool = False):
        """Host-only statements run locally (workers pick the effects up
        from the shared directory at their next refresh). Mesh statements
        run a TWO-PHASE dispatch: broadcast with the coordinator's plan
        hash, collect readiness acks (workers verified the hash and are
        parked before the collectives), then 'go' and execute here
        CONCURRENTLY with the workers. A dead worker surfaces on the
        channel during the readiness round — BEFORE anyone enters a
        collective that could never rendezvous — and the statement fails
        over by class: read-only statements transparently redispatch once
        after gang re-formation (else complete on the degraded local
        path); writes surface the error (_dispatch_failover)."""
        from greengage_tpu.parallel.multihost import WorkerDied

        ch = self.multihost.channel
        # idle-time liveness: the heartbeat thread marks the channel dead
        # on a missed pong — re-form/degrade HERE, before wasting a
        # broadcast on a partitioned gang (and before _execute could
        # enter a collective)
        if not getattr(self, "_mh_degraded", None) \
                and getattr(ch, "hb_failure", None):
            self._mh_worker_lost(f"heartbeat liveness check failed: "
                                 f"{ch.hb_failure}")
        # gang recovery: once the full gang has reconnected, re-sync and
        # fall through to normal mesh dispatch below
        if getattr(self, "_mh_degraded", None) and not self._mh_try_recover():
            stmts = parse(text)
            if any(self._needs_mesh(st) for st in stmts):
                return self._degraded_sql(text)
            out = None
            for stmt in stmts:
                out = self._execute(stmt)
            return out
        # N-1 partial gang: adopt the lost worker back the moment it has
        # redialed the kept listener (full-strength restoration)
        if not getattr(self, "_mh_degraded", None):
            self._mh_try_restore_full()
        stmts = parse(text)
        if any(getattr(st, "_recursive_ctes", None) for st in stmts):
            raise SqlError(
                "WITH RECURSIVE is not supported in multi-host mode yet "
                "(the fixpoint iteration cannot run under mesh lockstep)")
        mesh_stmts = [st for st in stmts if self._needs_mesh(st)]
        if mesh_stmts and len(stmts) > 1:
            raise SqlError(
                "multi-host mode runs one mesh statement (SELECT/DML) per "
                "sql() call; split the statement batch")
        out = None
        for stmt in stmts:
            if self._needs_mesh(stmt):
                # vectorized serving on the gang: an eligible SELECT
                # enrolls in the batch window BEFORE the per-statement
                # two-phase dispatch — the flush broadcasts the whole
                # window (op sql_batch) instead. None = not eligible or
                # the batch fell back; continue on the classic dispatch.
                if isinstance(stmt, A.SelectStmt):
                    bres = self._mh_batch_try(stmt, text)
                    if bres is not None:
                        out = bres
                        continue
                # coordinator-side validation AND queue admission BEFORE
                # the broadcast: a host-side rejection or queue wait after
                # workers enter the collectives would deadlock the cluster
                if isinstance(stmt, (A.DeleteStmt, A.UpdateStmt)):
                    self._check_no_raw_dml(stmt.table)
                    self._tx_for_dml(stmt.table, type(stmt).__name__[:6].upper())
                if isinstance(stmt, A.DeclareCursorStmt):
                    self._validate_declare(stmt)
                # one exchange()-scoped lock covers the whole two-phase
                # dispatch, so the heartbeat thread can never interleave
                # frames mid-statement; every ack round is deadline-
                # bounded (a hung worker classifies as WorkerDied within
                # mh_ready/ack_deadline, never an unbounded readline).
                # The WorkerDied handler sits OUTSIDE the admission scope
                # so a retry redispatch re-admits on a released slot.
                # The whole exchange is the statement's DISPATCH span:
                # worker-side spans arrive in the completion acks and
                # graft under it, so one trace shows the whole cluster
                _tr = _TRACES.current()
                _disp = (_tr.begin("dispatch", cat="multihost")
                         if _tr is not None else None)
                _comp_acks = None
                try:
                    with self._admission():
                        with ch.exchange():
                            # calibration rides the dispatch frame: the
                            # workers adopt OUR applied scales before
                            # re-planning, so corrected estimates never
                            # break the plan-hash lockstep invariant
                            ch.send({"op": "sql", "sql": text,
                                     "plan_hash": self.plan_hash(stmt),
                                     "fb": self.feedback.wire_payload()})
                            try:
                                ch.collect_acks(deadline="mh_ready_deadline",
                                                phase="readiness")
                            except StatementCancelled:
                                # cancelled while parked on readiness:
                                # nobody entered the mesh — release the
                                # parked workers and surface the typed
                                # cancellation
                                ch.send({"op": "skip"})
                                raise
                            except RuntimeError as e:
                                # a worker REFUSED (plan-hash mismatch or
                                # its planning failed): nobody entered the
                                # mesh — release the parked survivors and
                                # fail cleanly
                                ch.send({"op": "skip"})
                                raise QueryError(str(e))
                            ch.send({"op": "go"})
                            # arm spill-schedule recording: the workers
                            # ship theirs in the completion acks and the
                            # parity check below asserts lockstep
                            self.executor.spill_schedule.begin()
                            _sched = None
                            try:
                                out = self._execute(stmt)
                                _sched = \
                                    self.executor.spill_schedule.collect()
                            finally:
                                try:
                                    _acks = ch.collect_acks(
                                        deadline="mh_ack_deadline",
                                        phase="completion")
                                    _comp_acks = _acks
                                    if _disp is not None:
                                        _trace.graft_acks(_tr, _acks, _disp)
                                    if _sched is not None:
                                        # only when our side succeeded —
                                        # never mask an in-flight error
                                        self._mh_spill_parity(_sched, _acks)
                                except WorkerDied as e:
                                    # our side already finished its mesh
                                    # program: the result stands; later
                                    # statements run on the re-formed N-1
                                    # gang (or the degraded path)
                                    self._mh_worker_lost(
                                        str(e),
                                        getattr(e, "process_id", None))
                                except StatementCancelled:
                                    # a half-collected exchange cannot be
                                    # resumed (workers are still running
                                    # their program and will ack into the
                                    # teardown): quiesce so stale acks
                                    # never leak into the next statement;
                                    # the gang re-forms via rejoin
                                    self._mh_degrade(
                                        "statement cancelled while "
                                        "collecting completion acks")
                                    raise
                except WorkerDied as e:
                    # death/hang BEFORE anyone entered a collective
                    # (readiness or go phase): re-form over the survivors
                    # (or degrade), then fail over by statement class
                    # (reads redispatch, writes surface the error —
                    # exactly-once)
                    self._mh_worker_lost(str(e),
                                         getattr(e, "process_id", None))
                    return self._dispatch_failover(stmt, text, e, _is_retry)
                finally:
                    if _disp is not None:
                        _tr.end(_disp)
                # cluster-wide runaway verdict (the multihost
                # runaway_cleaner, VERDICT missing #7): one decision from
                # the AGGREGATED gang watermarks, enforced at the
                # statement completion boundary — raises RunawayCancelled
                self._mh_runaway_check(_comp_acks)
            else:
                if isinstance(stmt, A.SetStmt):
                    # settings steer MESH decisions (spill passes, retry
                    # tiers, fused kernel): workers must apply the same
                    # values or their lockstep branches desync. ONLY this
                    # statement ships (a batch re-parse on the worker
                    # would apply later statements the coordinator might
                    # never reach)
                    try:
                        with ch.exchange():
                            ch.send({"op": "set", "name": stmt.name,
                                     "value": stmt.value})
                            try:
                                out = self._execute(stmt)
                            finally:
                                ch.collect_acks(deadline="mh_ready_deadline",
                                                phase="set")
                    except WorkerDied as e:
                        # apply the SET locally FIRST, then re-form: the
                        # re-formation (or later rejoin) sync re-ships the
                        # whole settings payload, new value included
                        out = self._execute(stmt)
                        self._mh_worker_lost(str(e),
                                             getattr(e, "process_id", None))
                    continue
                out = self._execute(stmt)
        return out

    def worker_sql(self, text: str):
        """Run the DEVICE side of the coordinator's statement in lockstep
        (exec_mpp_query role): SELECT/EXPLAIN ANALYZE execute fully; write
        statements run only their internal mesh scans (DELETE/UPDATE read
        passes) — publishing is the coordinator's job."""
        for stmt in parse(text):
            if isinstance(stmt, (A.SelectStmt, A.UnionStmt)):
                self._select(stmt)
            elif isinstance(stmt, A.DeclareCursorStmt):
                # RETRIEVE is host-side on the coordinator; the worker only
                # participates in the DECLARE's collectives. deferred=True
                # mirrors the coordinator exactly: same pre-collective
                # memory-ceiling behavior, and no wasted full-result
                # finalize/decode of a shard nobody reads
                planned, consts, outs, ek = self._cached_plan(stmt.query)
                try:
                    self.executor.run(planned, consts, outs, cache_key=ek,
                                      deferred=True)
                except QueryError as e:
                    if "duplicate keys" not in str(e):
                        raise
                    # deterministic lockstep with the coordinator's re-plan:
                    # both sides saw the same dup flag on the same data
                    planned, consts, outs, ek = self._cached_plan(
                        stmt.query, force_multi_join=True)
                    self.executor.run(planned, consts, outs, cache_key=ek,
                                      deferred=True)
            elif isinstance(stmt, A.ExplainStmt) and stmt.analyze:
                self._explain(stmt)
            elif isinstance(stmt, (A.DeleteStmt, A.UpdateStmt)):
                self._worker_dml_scan(stmt)
            # everything else is host-side work owned by the coordinator
            # (SET arrives as its own channel op, never via batch text)

    def _worker_dml_scan(self, stmt):
        """Reproduce the coordinator's internal raw SELECT so its mesh
        program has all participants (the plan is deterministic)."""
        if isinstance(stmt, A.DeleteStmt):
            self._delete(stmt, worker_scan_only=True)
        else:
            self._update(stmt, worker_scan_only=True)

    # ---- multihost serving parity (docs/PERF.md "Data movement") ------
    def _mh_batch_try(self, stmt, text: str):
        """Coordinator half of gang batch serving: enroll an eligible
        parameterized SELECT in the batch window BEFORE any per-statement
        broadcast; the BatchServer's flush broadcasts the whole window
        (op sql_batch) through _mh_batch_exchange so every gang member
        dispatches the same width-bucketed program. Returns the member's
        Result, or None (not eligible / window fell back) — the caller
        proceeds with the classic two-phase dispatch."""
        if not bool(getattr(self.settings, "batch_serving_enabled", False)):
            return None
        if not isinstance(stmt, A.SelectStmt) or not stmt.from_:
            return None
        if _overload.CONTROLLER.brownout_active():
            return None
        cur = self.dtm.current
        if cur is not None and cur.state == "active":
            return None
        try:
            planned, consts, outs, exec_key = self._cached_plan(stmt)
        except Exception:
            return None   # the classic path owns surfacing plan errors
        pc_info = self._plan_cache_info
        if (consts or {}).get("@params@") is None:
            return None
        aux, _dirty = self._load_external_aux(planned)
        if aux:
            return None   # external loads stay serial (per-member state)
        with self._admission():
            res = self._batcher().submit(planned, consts, outs, exec_key,
                                         consts["@params@"], sql=text,
                                         plan_hash=self.plan_hash(stmt))
        if res is not None:
            if isinstance(res.stats, dict):
                res.stats["plan_cache"] = dict(pc_info)
            self._record_stats(res)
        return res

    @_contextmanager
    def _mh_batch_exchange(self, sqls: list, plan_hash):
        """Two-phase broadcast of one batch window, called on the
        BatchServer's dispatcher thread (no statement context): readiness
        acks -> 'go' -> yield for the concurrent local dispatch ->
        completion acks. EVERY failure surfaces as BatchFallback — the
        members re-run through the classic per-statement dispatch, which
        owns retries and failover. Gang degradation is NOT handled here:
        this runs on the dispatcher thread, and _mh_degraded/_mh_detached
        belong to the statement role. A dead peer raises WorkerDied again
        on the first serial re-run's own broadcast, where _coordinator_sql
        re-forms the gang on a statement thread."""
        from greengage_tpu.exec.batchserve import BatchFallback
        from greengage_tpu.parallel.multihost import WorkerDied

        ch = self.multihost.channel
        if getattr(ch, "hb_failure", None):
            raise BatchFallback("gang unavailable for batched dispatch")
        try:
            with ch.exchange():
                ch.send({"op": "sql_batch", "sqls": list(sqls),
                         "plan_hash": plan_hash,
                         "fb": self.feedback.wire_payload()})
                try:
                    ch.collect_acks(deadline="mh_ready_deadline",
                                    phase="readiness")
                except RuntimeError as e:
                    # a worker REFUSED (hash mismatch / planning failed):
                    # nobody entered the mesh — release the parked
                    # survivors and serve the members serially
                    ch.send({"op": "skip"})
                    raise BatchFallback(
                        f"worker refused batch window: {e}")
                ch.send({"op": "go"})
                done = False
                try:
                    yield
                    done = True
                finally:
                    try:
                        ch.collect_acks(deadline="mh_ack_deadline",
                                        phase="completion")
                    except WorkerDied:
                        raise
                    except RuntimeError as e:
                        if done:
                            # a worker's batch failed where ours ran:
                            # fall back — the serial re-runs keep the
                            # gang in lockstep statement by statement
                            raise BatchFallback(
                                f"worker batch execution failed: {e}")
                        # local dispatch already raising: let it surface
        except WorkerDied as e:
            raise BatchFallback(f"worker lost during batched dispatch: {e}")

    def worker_sql_batch(self, sqls: list):
        """Worker half of gang batch serving: plan every member of the
        broadcast window (same plan cache, same literal hoisting), stack
        their parameter vectors, and run the SAME width-bucketed batched
        program the coordinator is dispatching concurrently."""
        from greengage_tpu.exec.batchserve import BatchFallback, run_batch

        planned = consts = outs = ek = None
        pvecs = []
        for i, q in enumerate(sqls):
            stmt = parse(q)[0]
            p, c, o, k = self._cached_plan(stmt)
            pv = (c or {}).get("@params@")
            if pv is None:
                raise BatchFallback(
                    "window member did not parameterize on the worker")
            if i == 0:
                # the window's shared program compiles from the FIRST
                # member's bound plan, mirroring the coordinator's window
                planned, consts, outs, ek = p, c, o, k
            pvecs.append(pv)
        run_batch(self.executor, planned, consts, ek, pvecs)

    def _mh_spill_parity(self, mine: list, acks) -> None:
        """Lockstep assertion for tiered-spill schedules: every worker
        ships the pass/bucket schedule it actually ran in its completion
        ack; divergence from the coordinator's means the gang's programs
        could not have rendezvoused deterministically. Tier placement
        (RAM vs disk) is deliberately absent from the schedule — it is
        host-local and MUST NOT affect parity."""
        for a in acks or []:
            ws = a.get("spill_schedule") if isinstance(a, dict) else None
            if ws is None:
                continue
            if list(ws) != list(mine):
                raise QueryError(
                    "spill-schedule parity violation: coordinator ran "
                    f"{mine} but worker {a.get('process_id')} ran {ws}")

    def _mh_runaway_check(self, acks) -> None:
        """Cluster-wide runaway verdict (the multihost runaway_cleaner):
        workers ship their HBM watermark in every completion ack (riding
        the span-shipping path), the coordinator adds its own device
        peak, and ONE decision covers the gang — when the aggregate
        crosses the red zone of vmem_global_limit_mb, cancellation
        broadcasts through every process's interrupt registry and the
        statement surfaces a typed RunawayCancelled to the client.
        Enforcement lands at the completion boundary: an XLA program
        cannot be preempted mid-flight, so the boundary after the gang's
        acks is the cluster's CHECK_FOR_INTERRUPTS."""
        limit = int(getattr(self.settings, "vmem_global_limit_mb", 0)) << 20
        if not limit or not acks:
            return
        from greengage_tpu.parallel.multihost import _hbm_watermark

        total = _hbm_watermark(self)   # the coordinator's own peak
        for a in acks:
            if isinstance(a, dict):
                total += int(a.get("hbm", 0) or 0)
        red = int(limit * float(getattr(self.settings,
                                        "runaway_red_zone", 0.9)))
        if total <= red:
            return
        reason = (f"cluster HBM watermark {total >> 20} MB above the "
                  f"red zone ({red >> 20} MB of vmem_global_limit_mb="
                  f"{limit >> 20} MB)")
        from greengage_tpu.runtime.faultinject import faults

        # 'skip' on this point suppresses the worker broadcast (verdict
        # still enforced locally) — the gang test's partial-failure probe
        if not faults.check("runaway_broadcast"):
            try:
                self.multihost.channel.broadcast(
                    {"op": "runaway", "reason": reason},
                    deadline="mh_ready_deadline", phase="runaway")
            except Exception:
                # a dead/hung worker must not shield the verdict; the
                # next statement's dispatch handles gang re-formation
                pass
        _counters.inc("statements_cancelled_runaway")
        ctx = _INTERRUPTS.current()
        if ctx is not None:
            ctx.cancel("runaway", reason)
            ctx.check()
        # no statement context (internal caller): raise the typed error
        from greengage_tpu.runtime.runaway import RunawayCancelled

        raise RunawayCancelled(reason)

    def refresh(self) -> None:
        """Adopt the coordinator's committed catalog/manifest state from
        the shared cluster directory (workers call this per statement).

        The bound-plan cache is cleared only when the adopted state
        actually CHANGED (catalog bytes or manifest version): paramized
        generic plans carry the row estimates of the literals they were
        first bound with, so a worker that re-binds every statement
        while the coordinator serves its cache would compute a different
        plan hash for every repeated shape with a new literal — the
        lockstep verifier would reject its own gang. Keeping the cache
        across unchanged refreshes makes both sides bind each shape
        once, in the same broadcast order, with the same literals."""
        self.catalog = Catalog.load(self.path)
        self._load_extensions()
        self.store.catalog = self.catalog
        self.numsegments = self.catalog.segments.numsegments
        self.executor.catalog = self.catalog
        state = (self.store.manifest.snapshot().get("version", 0),
                 self._catalog_fingerprint())
        if state != getattr(self, "_refresh_state", None) or None in state:
            self._select_cache.clear()
            self._refresh_state = state
        self.store._invalidate_dicts_all()

    def _catalog_fingerprint(self) -> str | None:
        """Digest of the on-disk catalog (None when unreadable): ANALYZE
        stats, index DDL, and partition changes all ride catalog.json
        without bumping the manifest version, and each must invalidate
        a worker's bound plans exactly like the coordinator's own clear
        sites do."""
        try:
            with open(os.path.join(self.path, "catalog.json"), "rb") as f:
                return hashlib.sha1(f.read()).hexdigest()
        except OSError:
            return None

    def _execute(self, stmt):
        if isinstance(stmt, (A.SelectStmt, A.UnionStmt)):
            return self._select(stmt)
        if isinstance(stmt, A.ExplainStmt):
            return self._explain(stmt)
        if isinstance(stmt, A.RetrieveStmt):
            # read-only endpoint drain: the whole point is N retrieve
            # sessions draining concurrently — never behind the write lock
            return self._retrieve(stmt)
        if isinstance(stmt, A.DeclareCursorStmt):
            # read-only query; only the cursor-registry insert takes the
            # lock (inside _declare_cursor) — a multi-second DECLARE must
            # not stall every concurrent writer
            return self._declare_cursor(stmt)
        # autocommit single-table appends take the SHARED write mode plus
        # a per-table lock: appenders to DIFFERENT tables stage and commit
        # concurrently (per-table delta manifests make the commit path
        # contention-free across tables), while structural statements
        # below still drain them through the exclusive mode
        if isinstance(stmt, (A.InsertStmt, A.CopyStmt)) \
                and not (self.dtm.current is not None
                         and self.dtm.current.state == "active"):
            with self._write_lock.shared(), \
                    (self._table_lock(stmt.table)
                     if self._append_needs_table_lock(stmt.table)
                     else _NullSlot()):
                if isinstance(stmt, A.InsertStmt):
                    out = self._dml(self._insert, stmt)
                else:
                    out = self._copy(stmt)
                self._post_commit()
                return out
        # every other statement mutates shared state (catalog, manifest,
        # dictionaries, settings, tx) — one writer at a time per process
        with self._write_lock:
            return self._execute_write(stmt)

    def _table_lock(self, table: str):
        """Per-table append serializer (same-table appenders queue; the
        base storage table keys the lock so partition children share their
        parent's)."""

        base = table.split("#", 1)[0]
        with self._table_locks_mu:
            lk = self._table_locks.get(base)
            if lk is None:
                lk = self._table_locks[base] = threading.RLock()
            return lk

    def _append_needs_table_lock(self, table: str) -> bool:
        """Whether same-table appenders must still queue on the per-table
        serializer. With write intents on, N appenders stage disjoint
        segment deltas and resolve at commit with zero claim retries —
        UNLESS the table has a dict-encoded TEXT column: Dictionary.encode
        grows shared code maps, and divergent codes assigned by truly
        concurrent appenders are only reconciled by the legacy CAS path's
        conflict, so those tables keep the serializer."""
        if not getattr(self.settings, "write_intents_enabled", True):
            return True
        try:
            schema = self.catalog.get(table.split("#", 1)[0])
        except Exception:
            return True
        return any(c.type.kind is T.Kind.TEXT and c.encoding != "raw"
                   for c in schema.columns)

    def _execute_write(self, stmt):
        if isinstance(stmt, A.CreateTableStmt):
            return self._create_table(stmt)
        if isinstance(stmt, A.AlterTableStmt):
            return self._alter_table(stmt)
        if isinstance(stmt, A.DropTableStmt):
            existed = stmt.name in self.catalog
            schema0 = self.catalog.get(stmt.name) if existed else None
            self.catalog.drop_table(stmt.name, stmt.if_exists)
            if existed:
                # all storage tables backing this relation (partitions are
                # child storage tables named <parent>#<part>)
                storage = schema0.storage_tables()
                # invalidate open cursors that scanned this table: their
                # deferred shards may still dereference the table's files
                # (raw TEXT blobs, dictionaries) at RETRIEVE time
                for cname, batch in list(self._cursors.items()):
                    spec = getattr(getattr(batch, "comp", None),
                                   "input_spec", ())
                    if any(t == stmt.name or t in storage
                           for t, *_ in spec):
                        self._cursors[cname] = (
                            f'cursor "{cname}" was invalidated by DROP '
                            f'TABLE {stmt.name}')
                self._drop_log.append(stmt.name)
                # drop storage too: manifest commit removes the table's
                # segfiles from visibility; data dir cleanup is best-effort
                tx = self.store.manifest.begin()
                touched = False
                for st in storage:
                    if st in tx["tables"]:
                        del tx["tables"][st]
                        touched = True
                if touched:
                    self.store.manifest.commit_tx(tx)
                    # the dead delta chains go NOW (we hold the exclusive
                    # write mode): a same-named CREATE restarts at seq 1
                    # and must not collide with stale claims
                    for st in storage:
                        self.store.manifest.drop_table_deltas(st)
                self.store._invalidate_dicts(stmt.name)
                # compiled programs scanning this table must not survive a
                # same-named recreate (the shape signature could coincide)
                self.executor.programs.invalidate_table(stmt.name)

                for st in storage:
                    shutil.rmtree(os.path.join(self.path, "data", st),
                                  ignore_errors=True)
            return "DROP TABLE"
        if isinstance(stmt, A.CopyStmt):
            out = self._copy(stmt)
            self._post_commit()
            return out
        if isinstance(stmt, (A.InsertStmt, A.DeleteStmt, A.UpdateStmt)):
            out = self._dml({A.InsertStmt: self._insert,
                             A.DeleteStmt: self._delete,
                             A.UpdateStmt: self._update}[type(stmt)], stmt)
            self._post_commit()
            return out
        if isinstance(stmt, A.CreateExternalTableStmt):
            return self._create_external_table(stmt)
        if isinstance(stmt, A.AnalyzeStmt):
            return self._analyze(stmt.table)
        if isinstance(stmt, A.CreateIndexStmt):
            return self._create_index(stmt)
        if isinstance(stmt, A.DropIndexStmt):
            return self._drop_index(stmt)
        if isinstance(stmt, A.CreateExtensionStmt):
            return self._create_extension(stmt)
        if isinstance(stmt, A.CloseCursorStmt):
            if stmt.cursor not in self._cursors:
                raise ValueError(f'cursor "{stmt.cursor}" does not exist')
            del self._cursors[stmt.cursor]
            self._cursor_owner.pop(stmt.cursor, None)
            return "CLOSE CURSOR"
        if isinstance(stmt, A.ShowStmt):
            if stmt.what == "resource_group":
                return self.resgroups.current_group()
            return str(self.settings.show(stmt.what))
        if isinstance(stmt, A.SetStmt):
            if stmt.name == "resource_group":
                # per-THREAD binding (one server connection = one thread),
                # like SET ROLE picking the backend's resgroup
                self.resgroups.set_group(str(stmt.value))
                return "SET"
            self.settings.set(stmt.name, stmt.value)
            if stmt.name.startswith("resource_"):
                # wake blocked waiters: a lowered/disabled cap must admit
                # them now, not at their timeout
                self.resgroups.kick()
            if stmt.name in ("optimizer", "plan_cache_params",
                             "scalar_device_enabled", "cost_feedback"):
                # planner selection / literal-hoisting / scalar-lowering
                # changed: cached bound plans were produced under the
                # other regime. motion_pipeline_buckets needs no clear:
                # binding never reads it — the executor's program cache
                # keys on codegen_settings_sig and recompiles
                self._select_cache.clear()
            if stmt.name in ("motion_pipeline", "motion_pipeline_buckets"):
                from greengage_tpu.planner import cost as _cost
                _cost.set_motion_overlap(self._motion_overlap_factor())
            return "SET"
        if isinstance(stmt, A.ResourceGroupStmt):
            return self._resource_group(stmt)
        if isinstance(stmt, A.TxStmt):
            if stmt.action == "begin":
                self.dtm.begin()
                return "BEGIN"
            if stmt.action == "commit":
                written = set(getattr(self.dtm.current, "tables_written", ()))
                self.dtm.commit()
                self._post_commit()
                # a committed raw-table republish GC's the old blobs —
                # only NOW do open cursors over those tables go stale
                for t in written:
                    if self.store.has_raw_columns(t):
                        self._tombstone_raw_cursors(t)
                return "COMMIT"
            self.dtm.abort()
            return "ROLLBACK"
        raise SqlError(f"unsupported statement {type(stmt).__name__}")

    # ------------------------------------------------------------------
    def _create_index(self, stmt: A.CreateIndexStmt) -> str:
        """CREATE INDEX (pg_index analog): registers the index and builds
        the per-segfile block-value sidecars eagerly so the first probe
        doesn't pay the build. 'btree' and 'bitmap' both lower to the
        block-value index (see TableStore.block_index)."""
        if stmt.using not in ("btree", "bitmap"):
            raise SqlError(f"unknown index access method {stmt.using!r}")
        for schema in (self.catalog.get(t) for t in self.catalog.tables):
            if stmt.name in schema.indexes:
                if stmt.if_not_exists:
                    return "CREATE INDEX"
                raise SqlError(f'index "{stmt.name}" already exists')
        schema = self.catalog.get(stmt.table)
        if self._external_def(schema) is not None:
            raise SqlError("cannot index an external table")
        col = schema.column(stmt.column)
        if col.type.kind is T.Kind.TEXT and col.encoding == "raw":
            raise SqlError(
                "raw-encoded text cannot be indexed (block indexes probe "
                "storage values; raw storage has no per-row value column)")
        schema.indexes[stmt.name] = {"column": stmt.column,
                                     "using": stmt.using}
        self.catalog._save()
        self._build_index_sidecars(schema)
        self._select_cache.clear()
        # staged-input cache entries predate the index (same manifest
        # version): drop them so the next scan actually prunes
        self.executor.stager.stage_cache.clear()
        return "CREATE INDEX"

    def _build_index_sidecars(self, schema) -> None:
        snap = self.store.manifest.snapshot()
        for storage in schema.storage_tables():
            tmeta = snap["tables"].get(storage)
            if not tmeta:
                continue
            cols = {d["column"] for d in schema.indexes.values()}
            for segkey, files in tmeta["segfiles"].items():
                base = os.path.join(
                    self.store.data_root(int(segkey)), storage)
                for rel in files:
                    fn = os.path.basename(rel)
                    parts = fn.split(".")
                    if len(parts) == 3 and fn.endswith(".ggb") \
                            and parts[0] in cols:
                        self.store.block_index(base, rel, table=storage)

    def _drop_index(self, stmt: A.DropIndexStmt) -> str:
        for schema in (self.catalog.get(t) for t in self.catalog.tables):
            if stmt.name in schema.indexes:
                del schema.indexes[stmt.name]
                self.catalog._save()
                self._select_cache.clear()
                return "DROP INDEX"
        if stmt.if_exists:
            return "DROP INDEX"
        raise SqlError(f'index "{stmt.name}" does not exist')

    def _create_extension(self, stmt) -> str:
        """Import the extension module (registering its UDFs) and record
        it in the catalog so reopened clusters and workers reload it
        (reference: src/backend/commands/extension.c:1546)."""
        from greengage_tpu import extensions as X

        if stmt.name in self.catalog.extensions:
            if stmt.if_not_exists:
                return "CREATE EXTENSION"
            raise ValueError(f'extension "{stmt.name}" already exists')
        X.load(stmt.name, cluster_path=self.path)
        self.catalog.extensions.append(stmt.name)
        self.catalog._save()
        return "CREATE EXTENSION"

    def _analyze(self, table: str | None) -> str:
        """ANALYZE [table]: collect per-column NDV/min-max/null-frac/MCV
        into the catalog (pg_statistic analog; planner/stats.py)."""
        from greengage_tpu.planner.stats import analyze_table

        names = [table] if table else list(self.catalog.tables)
        snap = self.store.manifest.snapshot()
        for n in names:
            schema = self.catalog.get(n)
            if self._external_def(schema) is not None:
                if table:
                    raise SqlError("cannot ANALYZE an external table")
                continue   # database-wide ANALYZE skips externals
            schema.stats = analyze_table(self.store, schema, snap)
        self.catalog._save()
        self._select_cache.clear()   # fresh stats can change plans
        return "ANALYZE"

    # ------------------------------------------------------------------
    def _post_commit(self) -> None:
        """Synchronous mirror replication after a committed write (the
        syncrep gate analog): mirrors are copied up to the new manifest
        version before the statement returns, so FTS can always promote.
        SET mirror_sync = off trades that away; mirrors then go stale and
        refresh_sync_state() blocks their promotion."""
        if self.dtm.current is not None and getattr(self.dtm.current, "state", "") == "active":
            return   # still invisible; replicate/archive at COMMIT
        with self._pc_lock:
            self._post_commit_locked()

    def _post_commit_locked(self) -> None:
        if self.settings.archive_mode and self.settings.archive_dir:
            # continuous archiving: ship the committed version before the
            # statement returns (archive_command semantics); a failing
            # archive logs but never fails the write
            from greengage_tpu.storage.archive import Archive

            try:
                Archive(self.settings.archive_dir).archive_now(
                    self.path, self.store)
            except Exception as e:
                self.log.error("archive", f"archiving failed: {e}")
        # standby master (gpinitstandby): ship the committed tail; a
        # failing sync logs, counts, and widens the lag gauge — but never
        # fails the write (async-standby semantics). The liveness beat is
        # stamped either way so the watcher distinguishes "primary alive
        # but shipping fails" (lag grows, no promotion) from "primary
        # silent" (promotion after standby_promote_deadline_s).
        from greengage_tpu.runtime import standby as _standby

        sb = _standby.registered_standby(self.path)
        if sb is not None:
            try:
                _standby.sync(self.path, sb)
            except Exception as e:
                self.log.error("standby", f"standby sync failed: {e}")
                _standby.note_sync_failure(self.path)
            _standby.primary_beat(self.path,
                                  self.catalog.segments.version)
            self.fts.start()    # idempotent: idle-cadence beat coverage
        if self.replicator is None:
            return
        if self.settings.mirror_sync:
            self.replicator.sync()
        else:
            self.replicator.refresh_sync_state()
        # persist the topology only when sync state / roles actually moved
        # (a full catalog save per INSERT would rewrite every table's stats)
        segs = self.catalog.segments
        sig = (segs.version, tuple(e.mode_synced for e in segs.entries))
        if sig != getattr(self, "_cfg_sig", None):
            self._cfg_sig = sig
            self.catalog._save()

    # ------------------------------------------------------------------
    # ---- WITH RECURSIVE (nodeRecursiveunion.c / WorkTableScan role) ----
    def _select_recursive(self, stmt, rctes: dict) -> Result:
        """Session-level fixpoint iteration: materialize each recursive
        CTE by running the base term, then re-running the recursive term
        against a worktable of the previous iteration's NEW rows until
        none appear. Every term executes as an ordinary distributed
        statement; accumulation tables are real (ephemeral) tables, so
        the final query plans/distributes normally. UNION (not ALL)
        dedupes rows across iterations — which is also the cycle guard."""

        MAX_ITER = 500
        mapping: dict[str, str] = {}
        created: list[str] = []
        # unique scratch names: concurrent statements — including OTHER
        # PROCESSES sharing this cluster directory — must never collide
        uid = f"{os.getpid():x}_{next(_REC_COUNTER)}"
        try:
            for name, rc in rctes.items():
                acc = f"__rec_{uid}_{name}"
                wtbl = f"__recw_{uid}_{name}"
                base = _rename_base_tables(_copy.deepcopy(rc.base), mapping)
                # bind once for exact output types (constant-only base
                # terms skip the binder and infer from the result), then
                # execute
                outs0 = None
                try:
                    _, outs0 = Binder(
                        self.catalog, self.store,
                        subquery_executor=self._scalar_subquery,
                        optimizer=self.settings.optimizer).bind_select(
                            _copy.deepcopy(base))
                except SqlError:
                    pass      # constant-only base: infer from the result
                r = self._execute(base)
                if outs0 is None:
                    outs0 = [_inferred_col(nm, np.asarray(r.cols[cid]))
                             for nm, cid in zip(r.columns, r._order)]
                coldefs = ", ".join(
                    f"{c.name} {_ddl_type(c.type)}" for c in outs0)
                for t in (acc, wtbl):
                    self.sql(f"drop table if exists {t}")
                    self.sql(f"create table {t} ({coldefs}) "
                             "distributed randomly")
                    created.append(t)
                rows = r.rows()
                seen = set(rows) if not rc.union_all else None
                if seen is not None:
                    rows = list(seen)
                self._load_rows(acc, outs0, rows)
                cur = rows
                it = 0
                while cur:
                    it += 1
                    if it > MAX_ITER:
                        raise QueryError(
                            f'recursive CTE "{name}" exceeded {MAX_ITER} '
                            "iterations (cycle? use UNION instead of "
                            "UNION ALL, or add a bound)")
                    self.sql(f"delete from {wtbl}")
                    self._load_rows(wtbl, outs0, cur)
                    rec = _rename_base_tables(
                        _copy.deepcopy(rc.rec), {**mapping, name: wtbl})
                    nr = self._execute(rec).rows()
                    if seen is not None:
                        fresh = []
                        for t in nr:
                            if t not in seen:
                                seen.add(t)
                                fresh.append(t)
                        nr = fresh
                    if nr:
                        self._load_rows(acc, outs0, nr)
                    cur = nr
                mapping[name] = acc
            final = _rename_base_tables(_copy.deepcopy(stmt), mapping)
            if hasattr(final, "_recursive_ctes"):
                del final._recursive_ctes
            return self._execute(final)
        finally:
            for t in created:
                try:
                    self.sql(f"drop table if exists {t}")
                except Exception:
                    pass

    def _load_rows(self, table: str, outs, rows: list) -> None:
        """Host row tuples -> bulk column load matching ``outs`` types
        (DECIMAL results arrive descaled as float64 and reload as double
        precision — see _ddl_type)."""
        cols: dict = {}
        valids: dict = {}
        epoch = np.datetime64("1970-01-01")
        for i, c in enumerate(outs):
            vals = [r[i] for r in rows]
            mask = np.array([v is not None for v in vals], bool)
            kind = c.type.kind
            if kind is T.Kind.TEXT:
                cols[c.name] = ["" if v is None else str(v) for v in vals]
            elif kind in (T.Kind.FLOAT64, T.Kind.DECIMAL):
                cols[c.name] = np.array(
                    [0.0 if v is None else float(v) for v in vals],
                    np.float64)
            elif kind is T.Kind.DATE:
                cols[c.name] = np.array(
                    [0 if v is None else
                     int((np.datetime64(v, "D") - epoch)
                         .astype("timedelta64[D]").astype(np.int64))
                     for v in vals], np.int32)
            else:
                cols[c.name] = np.array(
                    [0 if v is None else int(v) for v in vals],
                    c.type.np_dtype)
            valids[c.name] = None if mask.all() else mask
        if rows:
            self.load_table(table, cols, valids)

    def _plan(self, stmt, force_multi_join: bool = False, info: dict | None = None):
        binder = Binder(self.catalog, self.store,
                        subquery_executor=self._scalar_subquery,
                        optimizer=self.settings.optimizer,
                        scalar_device=self.settings.scalar_device_enabled)
        with _trace.span("bind", cat="plan"):
            logical, outs = binder.bind_select(stmt)
        planned = plan_query(logical, self.catalog, self.store, self.numsegments,
                             force_multi_join=force_multi_join,
                             feedback=(self.feedback if bool(getattr(
                                 self.settings, "cost_feedback", True))
                                 else None))
        if self.settings.plan_validate:
            # checkPlan-before-dispatch (analysis/plancheck.py): a plan
            # violating a Motion/locality/prune invariant dies HERE with a
            # typed node path, never as a wrong answer after dispatch
            validate_plan(planned, self.catalog)
        if info is not None:
            info["memo_used"] = binder.memo_used
        # content digest of the LUT pool, computed once per bind: part of
        # the executor's executable-reuse shape signature (the compiled
        # program bakes these arrays)

        h = hashlib.sha1()
        for k in sorted(binder.consts):
            a = np.asarray(binder.consts[k])
            h.update(k.encode())
            h.update(str(a.dtype).encode())
            h.update(str(a.shape).encode())
            h.update(a.tobytes())
        binder.consts["@consts_digest@"] = h.hexdigest()[:16]
        return planned, binder.consts, outs

    def _const_select(self, stmt: A.SelectStmt) -> Result:
        """FROM-less SELECT: one constant row evaluated on the host (the
        coordinator-only Result node analog — no dispatch, no mesh;
        reference: SELECT without FROM planning to a Result plan in
        src/backend/optimizer/plan/planner.c)."""
        from greengage_tpu.sql.binder import Binder, Scope, _ast_name

        if stmt.group_by or stmt.having or stmt.distinct or stmt.order_by:
            raise SqlError(
                "SELECT without FROM supports only a constant target list")
        import jax.numpy as jnp

        from greengage_tpu.ops.batch import Batch
        from greengage_tpu.ops.expr_eval import Evaluator

        binder = Binder(self.catalog, self.store,
                        subquery_executor=self._scalar_subquery)
        scope = Scope()
        one_row = Batch({"__one__": jnp.zeros((1,), jnp.int32)}, {}, None)
        where_false = False
        if stmt.where is not None:
            pred = binder._predicate(stmt.where, scope)
            keep = Evaluator(one_row, binder.consts).predicate(pred)
            where_false = not bool(np.asarray(keep)[0])
        cols, valids, names, order = {}, {}, [], []
        for i, it in enumerate(stmt.items):
            if isinstance(it.expr, A.Star):
                raise SqlError("SELECT * requires FROM")
            e = binder._expr(it.expr, scope)
            name = it.alias or _ast_name(it.expr)
            cid = f"c#{i}"
            t = e.type
            if isinstance(e, E.Literal) and e.value is None:
                val, valid_np = np.array([None], dtype=object), \
                    np.array([False])
            elif t.kind is T.Kind.TEXT:
                if not isinstance(e, E.Literal):
                    raise SqlError("SELECT without FROM supports only "
                                   "constant text expressions")
                val, valid_np = np.array([e.value], dtype=object), None
            else:
                ev = Evaluator(one_row, binder.consts)
                arr, valid = ev.value(e)
                arr = np.asarray(arr)
                valid_np = (None if valid is None
                            else np.asarray(valid).astype(bool))
                if valid_np is not None and not valid_np[0]:
                    val = np.array([None], dtype=object)
                elif t.kind is T.Kind.DECIMAL:
                    val, valid_np = arr / (10.0 ** t.scale), None
                elif t.kind is T.Kind.DATE:
                    val = (np.datetime64("1970-01-01", "D")
                           + arr.astype("timedelta64[D]"))
                    valid_np = None
                else:
                    val, valid_np = arr, None
            cols[cid] = val
            valids[cid] = valid_np
            names.append(name)
            order.append(cid)
        limit = stmt.limit if stmt.limit is not None else 1
        if limit == 0 or stmt.offset or where_false:
            cols = {k: v[:0] for k, v in cols.items()}
            valids = {k: (None if v is None else v[:0])
                      for k, v in valids.items()}
        return Result(columns=names, cols=cols, valids=valids, _order=order)

    def _scalar_subquery(self, stmt):
        """Run an uncorrelated scalar subquery at bind time (InitPlan
        analog): the value is inlined as a literal into the outer plan,
        or, in a DML statement's inner SELECT, hoisted into its parameter
        vector (sql/paramize.py). Planned and compiled through the plan
        and program caches like any SELECT; the outer statement's
        plan-cache report is put back afterwards."""
        local = self._pc_info_local
        outer = (getattr(local, "info", {}), getattr(local, "planned", None))
        try:
            planned, consts, outs, ek = self._cached_plan(stmt)
            if len(outs) != 1:
                raise SqlError("scalar subquery must return one column")
            aux, dirty = self._load_external_aux(planned)
            if dirty:
                planned, consts, outs, ek = self._cached_plan(stmt)
            res = self.executor.run(planned, consts, outs, cache_key=ek,
                                    aux_tables=aux or None)
            if isinstance(res.stats, dict):
                res.stats["plan_cache"] = dict(self._plan_cache_info)
            self._dml_note(res)
        finally:
            local.info, local.planned = outer
        if len(res) > 1:
            raise SqlError("more than one row returned by a scalar subquery")
        t = outs[0].type
        if len(res) == 0:
            return None, t
        v = res.cols[outs[0].id][0]
        valid = res.valids.get(outs[0].id)
        if valid is not None and not valid[0]:
            return None, t
        # convert the presentation value back to storage representation
        if t.kind is T.Kind.DECIMAL:
            return T.decimal_to_int(float(v), t.scale), t
        if t.kind is T.Kind.DATE:
            return int((np.datetime64(v, "D")
                        - np.datetime64("1970-01-01", "D")).astype(int)), t
        if t.kind is T.Kind.TEXT:
            return str(v), T.TEXT
        if t.kind is T.Kind.FLOAT64:
            return float(v), t
        if t.kind is T.Kind.BOOL:
            return bool(v), t
        return int(v), t

    # ---- parallel retrieve cursors (endpoint/cdbendpoint.c analog) -----
    def _declare_cursor(self, stmt) -> str:
        """DECLARE <c> PARALLEL RETRIEVE CURSOR FOR <select>: run the mesh
        program once, keep every segment's output shard addressable as an
        ENDPOINT; RETRIEVE drains one endpoint without gathering the rest
        (reference: src/backend/cdb/endpoint/cdbendpoint.c — there results
        park on the segments behind direct connections, here as per-shard
        host buffers after the single device fetch)."""

        self._validate_declare(stmt)
        with self._write_lock:
            drop_mark = self._drop_base + len(self._drop_log)
            self._inflight_declares += 1
        try:
            # same plan/program memoization as _select: a drain-then-
            # redeclare workload must not replan + recompile each DECLARE
            planned, consts, outs, exec_key = self._cached_plan(stmt.query)
            aux, dirty = self._load_external_aux(planned)
            if dirty:
                planned, consts, outs, exec_key = self._cached_plan(
                    stmt.query)
            with (self._admission() if self.multihost is None
                  else _NullSlot()):
                try:
                    batch = self.executor.run(planned, consts, outs,
                                              cache_key=exec_key,
                                              deferred=True,
                                              aux_tables=aux or None)
                except QueryError as e:
                    if "duplicate keys" not in str(e):
                        raise
                    # same re-plan fallback as _select: the uniqueness
                    # heuristic was wrong at runtime -> CSR multi-match join
                    planned, consts, outs, exec_key = self._cached_plan(
                        stmt.query, force_multi_join=True)
                    batch = self.executor.run(planned, consts, outs,
                                              cache_key=exec_key,
                                              deferred=True,
                                              aux_tables=aux or None)
            with self._write_lock:
                prev = self._cursors.get(stmt.name)
                if prev is not None and not isinstance(prev, str):
                    # raced with another DECLARE of the same name
                    raise ValueError(f'cursor "{stmt.name}" already exists')
                # a table dropped while the (unlocked) run was in flight:
                # register the tombstone DROP TABLE could not place yet
                dropped = set(self._drop_log[drop_mark - self._drop_base:])
                hit = [t for t, *_ in batch.comp.input_spec if t in dropped]
                if hit:
                    self._cursors[stmt.name] = (
                        f'cursor "{stmt.name}" was invalidated by DROP '
                        f'TABLE {hit[0]}')
                    self._cursor_owner[stmt.name] = threading.get_ident()
                    return "DECLARE CURSOR (invalidated by concurrent DROP)"
                self._cursors[stmt.name] = batch
                # cursors are session-scoped (one server connection = one
                # thread); the server closes a dropped connection's cursors
                self._cursor_owner[stmt.name] = threading.get_ident()
            return f"DECLARE CURSOR ({batch.nendpoints} endpoints)"
        finally:
            with self._write_lock:
                self._inflight_declares -= 1
                if self._inflight_declares == 0 and self._drop_log:
                    # no mark can reference the log anymore: prune it
                    self._drop_base += len(self._drop_log)
                    self._drop_log.clear()

    def close_thread_cursors(self) -> None:
        """Release cursors declared by the calling thread (connection
        teardown; the reference's endpoints die with their session)."""

        me = threading.get_ident()
        with self._write_lock:
            for name in [n for n, t in self._cursor_owner.items() if t == me]:
                self._cursors.pop(name, None)
                self._cursor_owner.pop(name, None)

    def _validate_declare(self, stmt) -> None:
        """Host-side DECLARE checks; in multi-host mode these MUST run on
        the coordinator BEFORE the broadcast (workers enter the query's
        collectives unconditionally)."""
        existing = self._cursors.get(stmt.name)
        if existing is not None and not isinstance(existing, str):
            # (a str is a DROP TABLE tombstone — the name is reusable)
            raise ValueError(f'cursor "{stmt.name}" already exists')
        q = stmt.query
        if getattr(q, "order_by", None) or getattr(q, "limit", None) is not None \
                or getattr(q, "offset", 0):
            raise SqlError(
                "parallel retrieve cursors return per-endpoint streams; "
                "a cross-segment ORDER BY/LIMIT/OFFSET would need the "
                "gather this cursor exists to avoid")

    def _retrieve(self, stmt) -> Result:
        batch = self._cursors.get(stmt.cursor)
        if batch is None:
            raise ValueError(f'cursor "{stmt.cursor}" does not exist')
        if isinstance(batch, str):   # DROP TABLE tombstone
            raise ValueError(batch)
        if not 0 <= stmt.endpoint < batch.nendpoints:
            raise ValueError(
                f"endpoint {stmt.endpoint} out of range "
                f"(cursor has {batch.nendpoints})")
        try:
            return self.executor.finalize_endpoint(batch, stmt.endpoint)
        except (FileNotFoundError, OSError):
            # a DROP TABLE can delete this cursor's backing storage while
            # the (lock-free) decode is in flight; surface the tombstone
            # it planted instead of a raw IO error
            now = self._cursors.get(stmt.cursor)
            if isinstance(now, str):
                raise ValueError(now) from None
            raise

    def endpoints(self, cursor: str) -> list[dict]:
        """gp_endpoints analog: addressable endpoints of an open cursor."""
        batch = self._cursors.get(cursor)
        if batch is None:
            raise ValueError(f'cursor "{cursor}" does not exist')
        if isinstance(batch, str):
            raise ValueError(batch)
        return [{"cursor": cursor, "endpoint": k,
                 "state": "READY"} for k in range(batch.nendpoints)]

    @property
    def _plan_cache_info(self) -> dict:
        """Last _cached_plan outcome, PER THREAD: concurrent statements on
        the threaded SQL server must not clobber each other's plan-cache
        reporting (Result.stats["plan_cache"], EXPLAIN ANALYZE)."""
        return getattr(self._pc_info_local, "info", {})

    @_plan_cache_info.setter
    def _plan_cache_info(self, info: dict) -> None:
        self._pc_info_local.info = info

    @staticmethod
    def _attach_params(consts, pv, ptypes, info):
        """Bind the statement's CURRENT hoisted values into a fresh consts
        dict (the cached plan's pool is shared across statements); shared
        tail of _cached_plan's hit and miss paths."""
        from greengage_tpu.sql.paramize import ParamVector

        consts = dict(consts)
        if ptypes is not None:
            consts["@params@"] = ParamVector(pv.values, ptypes)
            _counters.inc("params_hoisted", len(pv.values))
        else:
            info["params"] = 0
        return consts

    def _cached_plan(self, stmt, force_multi_join: bool = False,
                     hoist_subqueries: bool = False):
        """Memoized planning for SELECT-shaped statements (plain SELECT
        and the DECLARE CURSOR body) — the plancache.c prepared-statement
        role. Plan-safe literals are hoisted into a parameter vector
        (sql/paramize.py) and the cache key becomes the literal-STRIPPED
        statement signature plus the hoisted literals' exact types:
        `WHERE x > 5` and `WHERE x > 6` share one bound plan and, through
        the executor's program cache, one XLA executable. Unsafe literals
        (partition keys, distribution-key equality, strings, LIMIT) stay
        pinned in the key so planning-relevant values never silently
        generalize. The key also carries the manifest version (bound
        plans embed dictionary codes/LUTs that grow with data); the
        executor's SHAPE signature decides executable reuse across
        versions. A force_multi_join re-plan is remembered under the
        PLAIN key so repeats skip the failing unique-join program. Real
        LRU, bounded by the plan_cache_size GUC. ``hoist_subqueries`` (a
        DML statement's inner SELECT) runs the uncorrelated scalar
        subqueries HERE, every time, and hoists their values like
        literals: the statement that follows a write re-reads them and
        still finds its program.
        -> (planned, consts, outs, exec_key)."""
        from greengage_tpu.sql.paramize import ParamVector, paramize

        version = self.store.manifest.snapshot().get("version", 0)
        with _trace.span("paramize", cat="plan"):
            norm, pv, sig = (
                paramize(stmt, self.catalog,
                         self._scalar_subquery if hoist_subqueries else None)
                if self.settings.plan_cache_params else (stmt, None, None))
        if sig is not None and sig in self._paramize_fallback:
            # this shape is known-unparameterizable: plan value-pinned
            # directly instead of re-paying the doomed normalized bind
            # for every new literal combination
            norm, pv, sig = stmt, None, None
        info = {"hit": False, "params": 0, "fallback": False}
        self._plan_cache_info = info
        if pv is not None:
            info["params"] = len(pv.values)
        key_sig = sig if sig is not None else repr(stmt)
        # the calibration version joins the key: a feedback promotion
        # touching this shape's digests bumps it, so a re-calibrated
        # shape re-plans instead of serving the stale bound plan
        fbv = self.feedback.version_for(key_sig)
        key = (key_sig, version, fbv)
        cache = self._select_cache
        if not force_multi_join:
            hit = cache.get(key)
            if hit is None and sig is not None:
                # this shape previously fell back to a value-pinned plan
                # (binder cannot parameterize it): look it up under the
                # full repr so the fallback is paid once, not per call
                fbk = (repr(stmt), version,
                       self.feedback.version_for(repr(stmt)))
                fb = cache.get(fbk)
                if fb is not None and fb[4] is None:
                    key, hit = fbk, fb
            if hit is not None:
                try:
                    cache.move_to_end(key)
                except KeyError:
                    pass   # concurrent statement evicted it; `hit` is ours
                _counters.inc("plan_cache_hit")
                info["hit"] = True
                planned, consts, outs, ek, ptypes = hit
                self._pc_info_local.planned = planned   # slow-log digest
                return planned, self._attach_params(consts, pv, ptypes,
                                                    info), outs, ek
        _counters.inc("plan_cache_miss")
        ptypes = pv.types if (pv is not None and norm is not stmt) else None
        try:
            with _trace.span("plan", cat="plan"):
                planned, consts, outs = self._plan(
                    norm, force_multi_join=force_multi_join)
        except (SqlError, NotImplementedError, TypeError):
            if ptypes is None:
                raise
            # a shape the binder cannot parameterize (raw-text predicates,
            # exotic coercions): pin every value and re-plan classically —
            # a genuine user error surfaces identically from the re-plan.
            # Memoize the signature so later literal variants of the shape
            # skip the doomed normalized bind entirely
            _counters.inc("plan_cache_fallback")
            info.update(fallback=True, params=0)
            if len(self._paramize_fallback) > 1024:
                self._paramize_fallback.clear()
            self._paramize_fallback.add(key_sig)
            ptypes = None
            key_sig = repr(stmt)
            key = (key_sig, version, self.feedback.version_for(key_sig))
            with _trace.span("plan", cat="plan", fallback=True):
                planned, consts, outs = self._plan(
                    stmt, force_multi_join=force_multi_join)
        ek = key_sig + ("#multi" if force_multi_join else "")
        # register the shape -> digest dependency set so a promotion on
        # any digest this plan uses bumps version_for(key_sig)
        self.feedback.note_shape(key_sig, planned)
        cache[key] = (planned, consts, outs, ek, ptypes)
        try:
            cache.move_to_end(key)
        except KeyError:
            pass
        bound = max(int(getattr(self.settings, "plan_cache_size", 256)), 1)
        while len(cache) > bound:
            try:
                cache.popitem(last=False)
            except KeyError:   # concurrent statement emptied it
                break
        self._pc_info_local.planned = planned   # slow-log digest source
        return planned, self._attach_params(consts, pv, ptypes,
                                            info), outs, ek

    # ---- vectorized serving (exec/batchserve.py) ---------------------
    def _batcher(self):
        b = self._batch_server
        if b is None:
            with self._batch_server_mu:
                b = self._batch_server
                if b is None:
                    from greengage_tpu.exec.batchserve import BatchServer

                    b = self._batch_server = BatchServer(self)
        return b

    def _batch_eligible(self, consts, aux) -> bool:
        """May this SELECT ride the batched-serving path from _select?
        Parameterized single-host autocommit reads only: external-table
        loads stay serial, and a statement inside an open transaction
        must see its session's uncommitted state. A multihost
        COORDINATOR batches too, but enrolls in _coordinator_sql BEFORE
        the per-statement broadcast (_mh_batch_try) — by the time
        _select runs there, the statement is already inside a classic
        two-phase exchange the workers are parked in, so this gate stays
        False under multihost."""
        if not bool(getattr(self.settings, "batch_serving_enabled", False)):
            return False
        if _overload.CONTROLLER.brownout_active():
            # brownout: stacked member params multiply device footprints
            # exactly when HBM has no headroom — serve serially until
            # pressure clears (docs/ROBUSTNESS.md "Overload protection")
            return False
        if self.multihost is not None or aux:
            return False
        if (consts or {}).get("@params@") is None:
            return False
        cur = self.dtm.current
        return cur is None or cur.state != "active"

    def _select(self, stmt: A.SelectStmt,
                hoist_subqueries: bool = False) -> Result:
        rctes = getattr(stmt, "_recursive_ctes", None)
        if rctes:
            return self._select_recursive(stmt, rctes)
        if isinstance(stmt, A.SelectStmt) and not stmt.from_:
            # pre-screen BEFORE attempting the host fast path: a bind-time
            # failure after an InitPlan scalar subquery already executed
            # would re-run that subquery on the device-path retry
            fastpath = (not stmt.group_by and not stmt.having
                        and not stmt.distinct and not stmt.order_by
                        and not any(_contains_agg(it.expr)
                                    for it in stmt.items)
                        and not any(isinstance(it.expr, A.Star)
                                    for it in stmt.items))
            if fastpath:
                try:
                    return self._const_select(stmt)
                except SqlError:
                    pass   # residual host-path rejections (non-constant
                    # text exprs, stat aggregates the screen can't see)
                    # fall through to the ConstRel device path; the
                    # screen above keeps InitPlan subqueries from running
                    # twice for the COMMON fallthrough shapes
        planned, consts, outs, exec_key = self._cached_plan(
            stmt, hoist_subqueries=hoist_subqueries)
        pc_info = self._plan_cache_info
        # external tables materialize to host arrays before execution
        # (fileam external_beginscan role); first-seen strings grow the
        # dictionary, so the bound plan refreshes afterwards
        aux, dirty = self._load_external_aux(planned)
        if dirty:
            planned, consts, outs, exec_key = self._cached_plan(
                stmt, hoist_subqueries=hoist_subqueries)
            pc_info = self._plan_cache_info
        # resource-queue admission (ResLockPortal analog): bound concurrent
        # mesh statements; excess statements queue or time out. Multi-host
        # admission happens on the COORDINATOR before the broadcast (a
        # post-broadcast wait here would strand workers in the collectives)
        with (self._admission() if self.multihost is None
              else _NullSlot()):
            if self._batch_eligible(consts, aux):
                # vectorized serving: enroll in the admission window for
                # this statement shape — one XLA dispatch serves every
                # in-flight member. None = the batch fell back (or this
                # member should run alone): continue on the classic path
                res = self._batcher().submit(planned, consts, outs,
                                             exec_key, consts["@params@"])
                if res is not None:
                    if isinstance(res.stats, dict):
                        res.stats["plan_cache"] = dict(pc_info)
                    self._record_stats(res, planned, exec_key)
                    return res
            try:
                # executor adds the manifest version; the bare statement
                # identity lets it evict compiled programs of old versions
                res = self.executor.run(planned, consts, outs,
                                        cache_key=exec_key,
                                        aux_tables=aux or None)
                if isinstance(res.stats, dict):
                    res.stats["plan_cache"] = dict(pc_info)
                self._record_stats(res, planned, exec_key)
                return res
            except QueryError as e:
                if "duplicate keys" not in str(e):
                    raise
                # the uniqueness heuristic was wrong at runtime: re-plan with
                # the CSR multi-match join forced everywhere; cached under
                # the plain key so repeats skip the failing program
                planned, consts, outs, exec_key = self._cached_plan(
                    stmt, force_multi_join=True)
                res = self.executor.run(planned, consts, outs,
                                        cache_key=exec_key,
                                        aux_tables=aux or None)
                if isinstance(res.stats, dict):
                    res.stats["plan_cache"] = dict(self._plan_cache_info)
                self._record_stats(res, planned, exec_key)
                return res

    def _record_stats(self, res, planned=None, exec_key=None) -> None:
        self.stat_activity.append({
            "ts": time.time(),
            "wall_ms": res.wall_ms,
            "rows": len(res),
            **(res.stats or {}),
        })
        if len(self.stat_activity) > 200:
            del self.stat_activity[0]
        if planned is not None and exec_key is not None:
            self._feedback_reconcile(planned, exec_key, res)

    def _feedback_reconcile(self, planned, exec_key: str, res) -> None:
        """Close the measurement loop after one execution: per-node
        actual rows (always-on filter counters + instrumented runs) and
        the exact ``rows_out`` reconcile against the planner's
        ``est_rows`` per structural digest; the AOT-measured executable
        bytes reconcile against ``est_bytes`` per shape. Coordinator /
        single-host only: workers adopt the coordinator's applied
        scales from the statement broadcast instead (identical inputs
        would yield identical updates, but the asymmetric rows_out of a
        gathered result must not desync lockstep planning)."""
        if not bool(getattr(self.settings, "cost_feedback", True)):
            return
        if self.multihost is not None and not self.multihost.is_coordinator:
            return
        stats = res.stats if isinstance(res.stats, dict) else {}
        if stats.get("batched"):
            # batched members share one program; per-member node
            # attribution is masked at demux — skip (the classic runs
            # of the shape feed the loop)
            return
        key_sig = exec_key[:-6] if exec_key.endswith("#multi") else exec_key
        mem = stats.get("mem") or {}
        measured = mem.get("measured") or {}
        measured_total = (measured.get("temp_bytes", 0)
                          + measured.get("argument_bytes", 0)
                          + measured.get("output_bytes", 0)) or None
        self.feedback.reconcile(
            key_sig, planned, len(res), stats.get("node_rows"),
            measured_bytes=measured_total,
            est_bytes=mem.get("est_bytes"))

    def _explain(self, stmt: A.ExplainStmt):
        if not isinstance(stmt.query, (A.SelectStmt, A.UnionStmt)):
            raise SqlError("EXPLAIN supports SELECT only")
        text = ""
        if not stmt.analyze:
            info: dict = {}
            planned, consts, outs = self._plan(stmt.query, info=info)
            # report the planner that actually produced the join order (the
            # memo bails without stats / on >10 rels / explicit JOIN syntax)
            text = ("Optimizer: %s\n" % (
                "memo (Cascades-lite)" if info.get("memo_used")
                else "fallback (left-deep DP/greedy)")) + describe(planned)
        if stmt.analyze:
            # ANALYZE goes through the plan cache (plancache exercise +
            # reporting); the instrumented program itself never enters the
            # executor's program cache, so compile_ms below is a real
            # fresh-compile measurement
            planned, consts, outs, _ek = self._cached_plan(stmt.query)
            pc_info = dict(self._plan_cache_info)
            aux, dirty = self._load_external_aux(planned)
            if dirty:
                planned, consts, outs, _ek = self._cached_plan(stmt.query)
                pc_info = dict(self._plan_cache_info)
            # per-node instrumentation (explain_gp.c's Instrumentation
            # tree analog): every operator reports its actual output rows
            # and Motion nodes the bytes they moved. Device time is
            # MEASURED where the statement can be run under the profiler
            # (one host, a TPU, one dispatch: runtime/devprofile.py reads
            # the device's operations back to the plan nodes that emitted
            # them); elsewhere compute_ms is split per node proportional
            # to its rows (host-attributed)
            res, profile = _devprofile.capture(lambda: self.executor.run(
                planned, consts, outs, instrument=True,
                aux_tables=aux or None))
            # instrumented runs carry actual rows for EVERY operator —
            # the richest feedback the loop gets (joins/aggregates that
            # normal runs only observe at the root)
            self._feedback_reconcile(planned, _ek, res)
            s = res.stats or {}
            measured = self._measured_by_node(profile, s)
            annot = self._analyze_annotations(planned, s, measured)
            text = describe(planned, annot=annot)
            if measured is not None:
                _ms_by_id, nobody_ms, d = measured
                text += (f"\n Device: {d.busy_s * 1e3:,.1f} ms busy of "
                         f"{d.span_s * 1e3:,.1f} ms dispatch (head "
                         f"{d.head_s * 1e3:,.1f}, tail {d.tail_s * 1e3:,.1f}), "
                         f"{nobody_ms:,.1f} ms under no node")
            text += (f"\n Plan cache: {'hit' if pc_info.get('hit') else 'miss'}"
                     f"{' (fallback: unparameterizable shape)' if pc_info.get('fallback') else ''}"
                     f", {pc_info.get('params', 0)} params hoisted, "
                     f"compile {s.get('compile_ms', 0)} ms")
            text += (
                f"\n Execution time: {res.wall_ms:.2f} ms, rows: {len(res)}"
                f"\n Segments: {s.get('segments')}, capacity tiers used: "
                f"{s.get('tiers_used')}, result capacity/segment: "
                f"{s.get('below_gather_capacity')}"
                f"\n Tables scanned: {', '.join(s.get('scan_tables', []))}")
            if s.get("stage_ms") is not None:
                # host-data-path breakdown (docs/PERF.md): where the wall
                # time went — host staging vs device program vs fetch
                text += (f"\n Host data path: staging {s['stage_ms']:.2f} ms"
                         f" ({s.get('stage_read_units', 0)} read units)"
                         f", device compute {s['compute_ms']:.2f} ms, "
                         f"result fetch {s['fetch_ms']:.2f} ms")
            io = s.get("scan_io") or {}
            if io:
                text += (f"\n Scan I/O: {io.get('scan_files_read', 0)} files"
                         f" read, {io.get('scan_bytes_decoded', 0)} bytes "
                         f"decoded, block cache "
                         f"{io.get('scan_cache_hit', 0)} hit / "
                         f"{io.get('scan_cache_miss', 0)} miss / "
                         f"{io.get('scan_cache_evict', 0)} evicted")
            mline = self._memory_line(s.get("mem"))
            if mline:
                text += "\n " + mline
            for t, (kept, total) in (s.get("zone_prune") or {}).items():
                text += f"\n Zone-map prune {t}: {kept}/{total} blocks"
            for t, (kept, total) in (s.get("dynamic_prune") or {}).items():
                text += (f"\n Dynamic partition selector {t}: "
                         f"{kept}/{total} children staged")
            if s.get("spill_passes"):
                text += f"\n Spill passes: {s['spill_passes']}"
            for k, v in (s.get("metrics") or {}).items():
                if not k.startswith("nrows_"):
                    text += f"\n {k}: {v}"
        r = Result(columns=["QUERY PLAN"],
                   cols={"p": np.array(text.split("\n"), dtype=object)},
                   valids={}, _order=["p"])
        r.plan_text = text
        return r

    @staticmethod
    def _memory_line(mem: dict | None) -> str | None:
        """The statement-level EXPLAIN ANALYZE Memory line: the vmem
        admission estimate alongside the MEASURED executable bytes (XLA
        memory_analysis — args/temps/output) and, where the backend
        reports one, the live device peak (docs/OBSERVABILITY.md
        "Memory accounting")."""
        if not mem:
            return None
        line = (f"Memory: vmem estimate "
                f"{mem.get('est_bytes', 0) / 1e6:.1f} MB/segment")
        meas = mem.get("measured")
        if meas:
            total = (meas.get("argument_bytes", 0)
                     + meas.get("temp_bytes", 0)
                     + meas.get("output_bytes", 0))
            line += (f"; executable measured: "
                     f"args {meas.get('argument_bytes', 0) / 1e6:.1f}"
                     f" + temps {meas.get('temp_bytes', 0) / 1e6:.1f}"
                     f" + out {meas.get('output_bytes', 0) / 1e6:.1f}"
                     f" = {total / 1e6:.1f} MB")
        if mem.get("admitted_by") == "measured":
            line += " (admitted by measured bytes)"
        if mem.get("device_peak_bytes_in_use") is not None:
            line += (f"; device peak "
                     f"{mem['device_peak_bytes_in_use'] / 1e6:.1f} MB")
        return line

    @staticmethod
    def _measured_by_node(profile, s: dict):
        """The statement's device time as it was measured -> ({id(plan
        node): {part | None: ms}}, ms under no node, devprofile.Dispatch),
        or None where it was not: no capture (the CPU backend, a profiler
        session of someone else's), more or fewer than one dispatch (a
        capacity retry, a spilling statement, another session's statement
        in the capture), or a program whose executable gives no node map
        (multihost). A label that came back without its `#<n>` (an
        executable found in the compile cache, compiled by an older
        program) is its node's where the plan has one node of that kind;
        else nobody's."""
        program, labels = s.get("program"), s.get("node_labels") or {}
        if profile is None or len(profile.dispatches) != 1 \
                or program is None or s.get("spill_passes"):
            return None
        node_map = program.node_map()
        if not node_map:
            return None
        measured = _devprofile.by_node(
            profile.ops, [(*profile.dispatches[0], node_map)])
        of_kind: dict = {}
        for label in labels:
            of_kind.setdefault(_devprofile.kind_of(label), []).append(label)
        ms_by_id: dict = {}
        nobody_ms = 0.0
        for (label, part), sec in measured.seconds.items():
            if label not in labels and len(of_kind.get(label, ())) == 1:
                label = of_kind[label][0]
            if label in labels:
                parts = ms_by_id.setdefault(labels[label], {})
                parts[part] = parts.get(part, 0.0) + sec * 1e3
            else:
                nobody_ms += sec * 1e3
        return ms_by_id, nobody_ms, measured.dispatches[0]

    @staticmethod
    def _analyze_annotations(planned, s: dict, measured=None) -> dict:
        """Per-plan-node EXPLAIN ANALYZE annotations: actual rows out,
        device ms, and moved bytes for Motion nodes (rows x output row
        width). Device ms is ``measured`` where there is a measurement
        (_measured_by_node): the self time of the device operations whose
        innermost node label is the node's, its parts shown apart. Without
        one it is host-attributed: the whole program is one fused XLA
        dispatch, so compute_ms splits proportional to each node's rows.
        Keys are id(plan-node), matching describe()'s annot contract."""
        from greengage_tpu.planner.logical import Motion as _Motion

        node_rows = s.get("node_rows") or {}
        if not node_rows:
            return {}
        node_mem = s.get("node_est_bytes") or {}
        id2node = {}
        stack = [planned]
        while stack:
            p = stack.pop()
            id2node[id(p)] = p
            stack.extend(p.children)
        total = sum(node_rows.values())
        compute = float(s.get("compute_ms") or 0.0)
        ms_by_id = {} if measured is None else measured[0]
        annot = {}
        # the Gather counts no rows of its own; it has a line where it ran
        # operations (the compaction before it)
        for pid in [*node_rows, *(p for p in ms_by_id if p not in node_rows)]:
            n = node_rows.get(pid)
            parts = [] if n is None else [f"actual rows={n}"]
            if pid in ms_by_id:
                apart = "".join(f"; {part} {ms:,.1f}" for part, ms
                                in ms_by_id[pid].items() if part)
                parts.append(f"device {sum(ms_by_id[pid].values()):,.1f} ms "
                             f"(measured{apart})")
            elif measured is None and n is not None and total > 0 \
                    and compute > 0:
                parts.append(f"device ~{compute * n / total:.2f} ms "
                             f"(host-attributed)")
            node = id2node.get(pid)
            if n is not None and isinstance(node, _Motion):
                try:
                    width = sum(int(c.type.np_dtype.itemsize)
                                for c in node.out_cols())
                except Exception:
                    width = 8
                parts.append(f"motion ~{n * width} B")
            # per-node Memory: this node's slice of the compiled device
            # estimate (capacity x widths; spill merges keep the last
            # merge program's slices — pass clones don't re-map)
            mb = node_mem.get(pid)
            if mb:
                parts.append(f"memory ~{mb >> 10} KB")
            annot[pid] = ", ".join(parts)
        return annot

    # ------------------------------------------------------------------
    def _create_table(self, stmt: A.CreateTableStmt):
        cols = [
            Column(c.name, type_from_name(c.type_name, c.typmod), not c.not_null)
            for c in stmt.columns
        ]
        kind = {"hash": PolicyKind.HASH, "random": PolicyKind.RANDOM,
                "replicated": PolicyKind.REPLICATED}[stmt.dist_kind]
        policy = DistPolicy(kind, tuple(stmt.dist_keys) if kind is PolicyKind.HASH else (),
                            self.numsegments)
        options = dict(stmt.options)
        options.setdefault("compresstype", self.settings.default_compresstype)
        options.setdefault("compresslevel", self.settings.default_compresslevel)
        schema = TableSchema(stmt.name, cols, policy, options)
        if stmt.partition_kind is not None:
            if stmt.partition_col not in [c.name for c in cols]:
                raise SqlError(
                    f"partition column {stmt.partition_col} is not a column")
            pcol = schema.column(stmt.partition_col)
            if pcol.type.kind is T.Kind.TEXT:
                raise SqlError("TEXT partition keys are not supported")
            if policy.kind is PolicyKind.REPLICATED:
                # GP parity: replicated tables cannot be partitioned
                raise SqlError("DISTRIBUTED REPLICATED tables cannot be "
                               "partitioned")
            schema.partition_by = (stmt.partition_kind, stmt.partition_col)
            parts: list[Partition] = []
            for pd in stmt.partition_defs:
                parts.extend(self._build_partitions(pd, pcol,
                                                    stmt.partition_kind))
            self._validate_partitions(parts, stmt.partition_kind, stmt.name)
            schema.partitions = parts
        self.catalog.create_table(schema, stmt.if_not_exists)
        return "CREATE TABLE"

    def _part_literal(self, node, col):
        """Coerce a partition-bound literal into the column's storage
        representation (dates = epoch days, decimals = scaled ints).
        NULL bounds are meaningless (NULL keys route to the DEFAULT
        partition) and rejected."""
        binder = Binder(self.catalog, self.store)
        lit = binder._expr(node, _EmptyScope())
        if not isinstance(lit, E.Literal):
            raise SqlError("partition bounds must be literals")
        lit = binder._coerce_literal(lit, col.type)
        if lit.value is None:
            raise SqlError("partition bounds/values cannot be NULL")
        return lit.value

    def _build_partitions(self, pd, pcol, kind) -> list[Partition]:
        if pd.default:
            return [Partition(pd.name, default=True)]
        if kind == "list":
            if not pd.values:
                raise SqlError(
                    f"partition {pd.name}: LIST partitions need VALUES")
            if pd.lo is not None or pd.hi is not None or pd.every is not None:
                raise SqlError(
                    f"partition {pd.name}: START/END/EVERY are RANGE syntax")
            vals = tuple(self._part_literal(v, pcol) for v in pd.values)
            return [Partition(pd.name, values=vals)]
        if pd.values:
            raise SqlError(
                f"partition {pd.name}: VALUES is LIST syntax; this table "
                "is partitioned BY RANGE")
        lo = self._part_literal(pd.lo, pcol) if pd.lo is not None else None
        hi = self._part_literal(pd.hi, pcol) if pd.hi is not None else None
        if pd.every is None:
            return [Partition(pd.name, lo=lo, hi=hi)]
        if lo is None or hi is None:
            raise SqlError("EVERY requires both START and END")
        # the step is a DELTA in the column's storage units (days for
        # DATE, scaled units for DECIMAL), not a value of the column type
        binder = Binder(self.catalog, self.store)
        step_lit = binder._expr(pd.every, _EmptyScope())
        if not isinstance(step_lit, E.Literal) \
                or not isinstance(step_lit.value, (int, float)):
            raise SqlError("EVERY step must be a numeric literal "
                           "(storage units: days for DATE)")
        step = int(step_lit.value) if isinstance(lo, int) else step_lit.value
        if not step or step <= 0:
            raise SqlError("EVERY step must be positive")
        out, k, cur = [], 1, lo
        while cur < hi:
            nxt = min(cur + step, hi)
            out.append(Partition(f"{pd.name}_{k}", lo=cur, hi=nxt))
            cur, k = nxt, k + 1
        return out

    @staticmethod
    def _validate_partitions(parts, kind, table) -> None:
        names = [p.name for p in parts]
        if len(set(names)) != len(names):
            raise SqlError(f"duplicate partition name in {table}")
        if sum(1 for p in parts if p.default) > 1:
            raise SqlError("multiple DEFAULT partitions")
        real = [p for p in parts if not p.default]
        if kind == "range":
            bounded = sorted(
                (p for p in real),
                key=lambda p: (p.lo is not None,
                               p.lo if p.lo is not None else 0))
            for a, b in zip(bounded, bounded[1:]):
                a_hi = a.hi
                b_lo = b.lo
                if a_hi is None or b_lo is None or b_lo < a_hi:
                    raise SqlError(
                        f"overlapping range partitions {a.name}/{b.name}")
        else:
            seen: set = set()
            for p in real:
                for v in p.values:
                    if v in seen:
                        raise SqlError(
                            f"value {v!r} in multiple list partitions")
                    seen.add(v)
        if not parts:
            raise SqlError("partitioned table needs at least one partition")

    def _admission(self):
        """Statement admission: resource-group slot (weighted backoff when
        the global cap binds) nested inside/with the legacy resource
        queue; either is a no-op when unconfigured. The wait is metered
        into the queue_wait_ms histogram (`gg metrics`) and the
        statement's trace."""
        t0 = time.monotonic()
        st = ExitStack()
        try:
            with _trace.span("admission", cat="queue"):
                st.enter_context(self.resgroups.admit())
                st.enter_context(self.resqueue.admit())
        except BaseException:
            # a queue timeout after the group slot was granted must release
            # the slot (and unpin the thread's group memory ceiling)
            st.close()
            raise
        finally:
            _histograms.observe("queue_wait_ms",
                                (time.monotonic() - t0) * 1e3)
        return st

    def resgroup_status(self) -> list[dict]:
        """gp_toolkit.gp_resgroup_status analog."""
        return self.resgroups.status()

    def _resource_group(self, stmt) -> str:
        allowed = {"concurrency", "memory_limit_mb", "cpu_weight"}
        bad = set(stmt.options) - allowed
        if bad:
            raise SqlError(f"unknown resource group option(s): "
                           f"{', '.join(sorted(bad))}")
        if stmt.action == "create":
            self.resgroups.create(stmt.name, **stmt.options)
            tag = "CREATE RESOURCE GROUP"
        elif stmt.action == "drop":
            self.resgroups.drop(stmt.name)
            tag = "DROP RESOURCE GROUP"
        else:
            self.resgroups.alter(stmt.name, **stmt.options)
            tag = "ALTER RESOURCE GROUP"
        # persist definitions (built-ins included so tuned caps survive)
        self.catalog.resource_groups = [
            g.to_dict() for g in self.resgroups.groups.values()]
        self.catalog._save()
        return tag

    # ---- external tables (fileam.c / CREATE EXTERNAL TABLE role) ------
    def _create_external_table(self, stmt) -> str:
        """An external table is a catalog-only relation whose rows come
        from (or go to) a URL/command at scan/insert time — no manifest
        storage (reference: src/backend/access/external/fileam.c,
        exttablecmds.c). Readable scans re-read the source every query."""
        cols = []
        for c in stmt.columns:
            col = Column(c.name, type_from_name(c.type_name, c.typmod),
                         not c.not_null)
            if col.type.kind is T.Kind.TEXT:
                # external TEXT is dictionary-coded at load (the scan path
                # stages device arrays; raw byte blobs need storage files)
                col = Column(col.name, col.type, col.nullable,
                             encoding="dict")
            cols.append(col)
        if not stmt.urls and stmt.exec_cmd is None:
            raise SqlError("external table needs LOCATION or EXECUTE")
        schema = TableSchema(
            stmt.name, cols,
            DistPolicy(PolicyKind.RANDOM, (), self.numsegments),
            {"external": {
                "writable": stmt.writable,
                "urls": list(stmt.urls),
                "exec_cmd": stmt.exec_cmd,
                "format": dict(stmt.format_opts),
                "reject_limit": stmt.reject_limit,
            }})
        self.catalog.create_table(schema, stmt.if_not_exists)
        return "CREATE EXTERNAL TABLE"

    @staticmethod
    def _external_def(schema) -> dict | None:
        return schema.options.get("external")

    def _external_chunks(self, schema, ext: dict) -> list:
        """Fetch the raw bytes of an external source as
        (blob, starts_new_file) pairs — HEADER must be stripped once per
        FILE, not once per scan (a gpfdist stream is one file split into
        chunks; a glob/EXECUTE yields one file per chunk)."""
        from greengage_tpu.runtime import ingest

        chunks: list = []
        if ext["exec_cmd"] is not None:
            # EXECUTE ON ALL: the command runs once per segment with
            # GP_SEGMENT_ID/GP_SEGMENT_COUNT env (fileam.c EXECUTE popen)

            for seg in range(self.numsegments):
                env = dict(os.environ,
                           GP_SEGMENT_ID=str(seg),
                           GP_SEGMENT_COUNT=str(self.numsegments))
                out = subprocess.run(
                    ext["exec_cmd"], shell=True, env=env,
                    capture_output=True, timeout=120)
                if out.returncode != 0:
                    raise SqlError(
                        f"external EXECUTE failed on segment {seg}: "
                        f"{out.stderr.decode(errors='replace')[:200]}")
                chunks.append((out.stdout, True))
            return chunks

        for url in ext["urls"]:
            if url.startswith("gpfdist://"):
                for ci, blob in enumerate(
                        ingest.fetch_chunks(url, self.numsegments)):
                    chunks.append((blob, ci == 0))
            elif url.startswith("s3://"):
                # object store (gpcloud role): one external file per object
                from greengage_tpu.runtime import s3

                objs = s3.fetch(url)
                if not objs:
                    raise SqlError(f"external location {url!r} matches "
                                   "no objects")
                for _key, blob in objs:
                    chunks.append((blob, True))
            else:
                path = url[len("file://"):] if url.startswith("file://") else url
                matches = sorted(_glob.glob(path))
                if not matches:
                    raise SqlError(f"external location {url!r} matches "
                                   "no files")
                for m in matches:
                    with open(m, "rb") as f:
                        chunks.append((f.read(), True))
        return chunks

    def _load_external_aux(self, planned) -> dict:
        """Materialize every external table scanned by this plan into host
        arrays for aux staging (the external_beginscan role: re-read per
        query, SREH reject limits applied)."""
        from greengage_tpu.planner.logical import Scan
        from greengage_tpu.runtime import ingest

        aux: dict = {}
        any_dirty = False
        stack = [planned]
        while stack:
            node = stack.pop()
            stack.extend(node.children)
            if not isinstance(node, Scan) or node.table in aux:
                continue
            schema = self.catalog.get(node.table) \
                if node.table in self.catalog else None
            ext = self._external_def(schema) if schema is not None else None
            if ext is None:
                continue
            if ext["writable"]:
                raise SqlError(
                    f'"{node.table}" is a WRITABLE external table; it '
                    "cannot be scanned")
            fmt = ext.get("format", {})
            delim = fmt.get("delimiter", ",")
            header = str(fmt.get("header", "false")).lower() in ("true", "1")
            null_s = fmt.get("null", "")
            cols_all = {c.name: [] for c in schema.columns}
            valids_all = {c.name: [] for c in schema.columns}
            rejects: list = []
            line_base = 0
            for blob, file_start in self._external_chunks(schema, ext):
                text = blob.decode("utf-8", errors="replace")
                cols, valids, rej = ingest.parse_csv_rows(
                    text, schema, delim, header and file_start, null_s,
                    line_base=line_base)
                for n in cols_all:
                    cols_all[n].extend(cols[n])
                    valids_all[n].extend(valids[n])
                rejects.extend(rej)
                line_base += blob.count(b"\n")
            limit = ext.get("reject_limit")
            if rejects and limit is None:
                line, _raw, err = rejects[0]
                raise SqlError(f"external table {node.table} line {line}: "
                               f"{err}")
            if limit is not None and len(rejects) > limit:
                raise SqlError(
                    f"external scan aborted: {len(rejects)} rejected rows "
                    f"exceed SEGMENT REJECT LIMIT {limit}")
            if rejects:
                ingest.append_error_log(self.path, node.table, rejects)
            enc_c: dict = {}
            enc_v: dict = {}
            dict_dirty = False
            for c in schema.columns:
                va = np.array(valids_all[c.name], dtype=bool)
                if c.type.kind is T.Kind.TEXT:
                    d = self.store.dictionary(node.table, c.name)
                    strs = ["" if not ok else s for s, ok
                            in zip(cols_all[c.name], va)]
                    before = len(d)
                    enc_c[c.name] = d.encode(strs)
                    dict_dirty = dict_dirty or len(d) != before
                else:
                    enc_c[c.name] = np.array(cols_all[c.name],
                                             dtype=c.type.np_dtype)
                enc_v[c.name] = None if va.all() else va
            if dict_dirty:
                self.store.flush_dicts(node.table)
                # new codes can shift LUT-dependent bound plans: the
                # caller re-binds against the grown dictionary
                self._select_cache.clear()
                any_dirty = True
            aux[node.table] = (enc_c, enc_v)
        return aux, any_dirty

    def _alter_table(self, stmt: A.AlterTableStmt) -> str:
        """ALTER TABLE ... ADD/DROP PARTITION (reference: cdbpartition.c
        partition maintenance). DROP is O(1): unlink the child storage
        table; no other partition moves."""
        schema = self.catalog.get(stmt.table)
        if not schema.is_partitioned:
            raise SqlError(f'table "{stmt.table}" is not partitioned')
        kind, pcol_name = schema.partition_by
        if stmt.action == "add_partition":
            pcol = schema.column(pcol_name)
            new = self._build_partitions(stmt.partition, pcol, kind)
            self._validate_partitions(schema.partitions + new, kind,
                                      stmt.table)
            schema.partitions.extend(new)
            self.catalog._save()
            self._select_cache.clear()
            return "ALTER TABLE"
        # drop_partition
        part = schema.partition(stmt.partition_name)   # KeyError -> msg
        child = part.storage_name(stmt.table)
        if len(schema.partitions) == 1:
            raise SqlError("cannot drop the last partition; DROP TABLE")
        schema.partitions = [p for p in schema.partitions
                             if p.name != part.name]
        self.catalog._save()
        for cname, batch in list(self._cursors.items()):
            spec = getattr(getattr(batch, "comp", None), "input_spec", ())
            if any(t == stmt.table for t, *_ in spec):
                self._cursors[cname] = (
                    f'cursor "{cname}" was invalidated by DROP PARTITION '
                    f'on {stmt.table}')
        # same in-flight-DECLARE race as DROP TABLE: a cursor still being
        # declared over this table must tombstone itself at registration
        self._drop_log.append(stmt.table)
        tx = self.store.manifest.begin()
        if child in tx["tables"]:
            del tx["tables"][child]
            self.store.manifest.commit_tx(tx)
            self.store.manifest.drop_table_deltas(child)

        shutil.rmtree(os.path.join(self.path, "data", child),
                      ignore_errors=True)
        self._select_cache.clear()
        self.executor.programs.invalidate_table(stmt.table)
        self._post_commit()
        return "ALTER TABLE"

    # ---- DML: one answer shape, one account ----------------------------
    def _dml(self, handler, stmt) -> "DmlResult":
        """Run an INSERT / DELETE / UPDATE handler (-> its command tag)
        under a fresh account of its inner statements and its two phases
        -> the tag as a DmlResult."""
        run, prev = _DmlRun(), getattr(self._dml_local, "run", None)
        self._dml_local.run = run
        try:
            tag = handler(stmt)
        finally:
            self._dml_local.run = prev
        out = run.answer(tag)
        # rows_inserted is the store's (every append path passes there);
        # a deletion is a bitmap or a republish, so it is counted here
        _counters.inc("rows_deleted", out.stats["rows_deleted"])
        return out

    def _dml_note(self, res) -> None:
        """An inner statement of the running DML statement (a scalar
        subquery, the inner SELECT, the predicate scan) finished."""
        run = getattr(self._dml_local, "run", None)
        if run is not None and isinstance(getattr(res, "stats", None), dict):
            run.inner.append(res.stats)

    @_contextmanager
    def _dml_phase(self, name: str):
        """`dml_scan` (what the statement reads) or `write` (everything
        after): a span, and the phase's clock in the statement's account."""
        run = getattr(self._dml_local, "run", None)
        t0 = time.monotonic()
        try:
            with _trace.span(name, cat="dml"):
                yield
        finally:
            if run is not None:
                run.ms[name] += (time.monotonic() - t0) * 1e3

    def _insert(self, stmt: A.InsertStmt):
        schema = self.catalog.get(stmt.table)
        ext = self._external_def(schema)
        if stmt.query is not None:
            return self._insert_select(schema, ext, stmt)
        if ext is not None:
            raise SqlError(
                f'"{stmt.table}" is an external table; load it via its '
                "LOCATION source (INSERT ... SELECT writes WRITABLE "
                "external tables)")
        names = stmt.columns or schema.column_names
        if set(names) != set(schema.column_names):
            raise SqlError("INSERT must provide all columns")
        cols: dict[str, list] = {n: [] for n in names}
        valids: dict[str, list] = {n: [] for n in names}
        binder = Binder(self.catalog, self.store)
        scope = _EmptyScope()
        for row in stmt.rows:
            if len(row) != len(names):
                raise SqlError("INSERT row arity mismatch")
            for n, v in zip(names, row):
                col = schema.column(n)
                lit = binder._expr(v, scope)
                if not isinstance(lit, E.Literal):
                    raise SqlError("INSERT values must be literals")
                lit = binder._coerce_literal(lit, col.type)
                if lit.value is None:
                    valids[n].append(False)
                    cols[n].append(_zero_for(col.type))
                else:
                    valids[n].append(True)
                    cols[n].append(lit.value)
        enc_cols = {}
        enc_valids = {}
        for n in names:
            col = schema.column(n)
            if col.type.kind is T.Kind.TEXT:
                enc_cols[n] = cols[n]
            else:
                enc_cols[n] = np.array(cols[n], dtype=col.type.np_dtype)
            va = np.array(valids[n], dtype=bool)
            if not va.all():
                enc_valids[n] = va
        with self._dml_phase("write"):
            n = self._write_rows(stmt.table, enc_cols, enc_valids)
        return f"INSERT 0 {n}"

    def _insert_select(self, schema, ext, stmt) -> str:
        """INSERT INTO t SELECT ...: run the query, convert the presented
        values back to storage representation, and either append to the
        table or — for WRITABLE EXTERNAL tables — emit CSV to the
        location/command (the gpfdist WET/EXECUTE writer role)."""
        with self._dml_phase("dml_scan"):
            res = (self._select(stmt.query, hoist_subqueries=True)
                   if not isinstance(stmt.query, A.UnionStmt)
                   else self._execute(stmt.query))
            self._dml_note(res)
        names = stmt.columns or schema.column_names
        if set(names) != set(schema.column_names):
            raise SqlError("INSERT must provide all columns")
        if len(res.columns) != len(names):
            raise SqlError(
                f"INSERT SELECT arity mismatch: query returns "
                f"{len(res.columns)} columns, target has {len(names)}")
        if ext is not None:
            if not ext["writable"]:
                raise SqlError(
                    f'cannot write to READABLE external table "{schema.name}"')
            return self._write_external(schema, ext, res)
        with self._dml_phase("write"):
            with _trace.span("encode", cat="dml", rows=len(res)):
                cols, valids = self._storage_values(schema, names, res)
            n = self._write_rows(schema.name, cols, valids)
            self._post_commit()
        return f"INSERT 0 {n}"

    @staticmethod
    def _storage_values(schema, names, res) -> tuple[dict, dict]:
        """A Result's presented columns back in storage representation,
        under the target's column names -> (columns, validity masks)."""
        cols: dict = {}
        valids: dict = {}
        for n, oid in zip(names, res._order):
            c = schema.column(n)
            data = res.cols[oid]
            v = res.valids.get(oid)
            if c.type.kind is T.Kind.DECIMAL:
                # presented value is a float; re-scale with round-half-
                # away (the engine's numeric rounding rule)
                f = np.asarray(data, dtype=np.float64) * (10.0 ** c.type.scale)
                data = (np.floor(np.abs(f) + 0.5) * np.sign(f)).astype(np.int64)
            elif c.type.kind is T.Kind.DATE:
                data = (np.asarray(data, dtype="datetime64[D]")
                        - np.datetime64("1970-01-01", "D")).astype(np.int32)
            elif c.type.kind is T.Kind.TEXT:
                data = ["" if s is None else str(s) for s in data]
            else:
                data = np.asarray(data)
                if v is not None:
                    # NULL slots may carry NaN/garbage; zero them so the
                    # dtype cast cannot fail
                    data = np.where(v, data, 0)
                data = data.astype(c.type.np_dtype)
            cols[n] = data
            if v is not None:
                valids[n] = np.asarray(v, dtype=bool)
        return cols, valids

    def _write_external(self, schema, ext, res) -> str:
        buf = io.StringIO()
        fmt = ext.get("format", {})
        w = _csv.writer(buf, delimiter=fmt.get("delimiter", ","))
        null_s = fmt.get("null", "")
        for row in res.rows():
            w.writerow([null_s if v is None else v for v in row])
        payload = buf.getvalue()
        if ext["exec_cmd"] is not None:
            out = subprocess.run(ext["exec_cmd"], shell=True,
                                 input=payload.encode(), timeout=120,
                                 capture_output=True)
            if out.returncode != 0:
                raise SqlError(
                    "external EXECUTE writer failed: "
                    f"{out.stderr.decode(errors='replace')[:200]}")
            return f"INSERT 0 {len(res)}"
        url = ext["urls"][0]
        if url.startswith("gpfdist://"):
            raise SqlError("writing through a gpfdist URL is not supported; "
                           "use file://, s3://, or EXECUTE")
        if url.startswith("s3://"):
            # one object per INSERT batch (the gpcloud writable layout:
            # unique keys so parallel writers never clobber)

            from greengage_tpu.runtime import s3

            key = s3.store(url, f"gg_{_uuid.uuid4().hex[:12]}.csv",
                           payload.encode())
            self.log.info("external", f"wrote s3 object {key}")
            return f"INSERT 0 {len(res)}"
        path = url[len("file://"):] if url.startswith("file://") else url
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "a", encoding="utf-8") as f:
            f.write(payload)
        return f"INSERT 0 {len(res)}"

    def _write_rows(self, table: str, columns, valids) -> int:
        """All write paths (INSERT/COPY/load_table) stage into the open
        transaction if one is active; published at COMMIT. (Reads inside the
        tx still see the committed snapshot only.) Partitioned tables route
        rows to their partitions' child storage tables here."""
        schema = self.catalog.get(table)
        if schema.is_partitioned and "#" not in table:
            return self._write_routed(schema, columns, valids or {})
        tx = self.dtm.current
        if tx is not None and tx.state == "active":
            return tx.insert(table, columns, valids)
        return self.store.insert(table, columns, valids)

    def _write_routed(self, schema, columns, valids) -> int:
        """Split a row batch by partition and write each slice into its
        child storage table (one manifest commit when inside a tx; one per
        child otherwise — each child insert is atomic either way)."""
        # whole-batch validation BEFORE any child stages: a later child's
        # constraint failure must not leave earlier slices in the user's tx
        for c in schema.columns:
            v = valids.get(c.name)
            if not c.nullable and v is not None and not np.all(v):
                raise SqlError(
                    f'null value in column "{c.name}" violates not-null '
                    "constraint")
        kind, pcol = schema.partition_by
        col = schema.column(pcol)
        raw = columns[pcol]
        if col.type.kind is T.Kind.DATE and not isinstance(raw, np.ndarray):
            vals = np.array([T.date_to_days(v) for v in raw], dtype=np.int32)
        elif col.type.kind is T.Kind.DECIMAL and not isinstance(raw, np.ndarray):
            vals = np.array([T.decimal_to_int(v, col.type.scale) for v in raw],
                            dtype=np.int64)
        else:
            vals = np.asarray(raw, dtype=col.type.np_dtype)
        pidx = np.asarray(schema.route_rows(vals, valids.get(pcol)))
        if (pidx < 0).any():
            bad = vals[pidx < 0][0]
            raise SqlError(
                f"no partition of {schema.name} accepts value {bad!r} "
                "(and there is no DEFAULT partition)")

        def _slice(v, m):
            if isinstance(v, T.Coded):
                return T.Coded(v.vocab, v.codes[m])
            if isinstance(v, np.ndarray):
                return v[m]
            return np.asarray(v, dtype=object)[m]

        # all children stage into ONE manifest tx (atomic multi-partition
        # insert), the user's own transaction when one is open
        total = 0
        with self._autocommit_tx() as tx:
            for i, p in enumerate(schema.partitions):
                m = pidx == i
                if not m.any():
                    continue
                sub_c = {k: _slice(v, m) for k, v in columns.items()}
                sub_v = {k: _slice(v, m) for k, v in valids.items()
                         if v is not None}
                total += tx.insert(p.storage_name(schema.name), sub_c, sub_v)
        return total

    @_contextmanager
    def _autocommit_tx(self):
        """Yield the thread's active transaction, or an ephemeral one that
        commits on success / aborts on error — the shared wrapper for
        writes that must land atomically across several storage tables."""
        tx = self.dtm.current
        if tx is not None and tx.state == "active":
            yield tx
            return
        own = self.dtm.begin()
        try:
            yield own
            self.dtm.commit()
        except Exception:
            if self.dtm.current is own:
                self.dtm.abort()
            raise

    def cluster_exec(self, cmd: str, timeout: float = 60.0) -> list[dict]:
        """gpssh analog: run a shell command on every host of the cluster
        — workers over the control channel, the coordinator locally.
        -> [{'host': id, 'ok': bool, 'output': str}]."""

        out = []
        local = subprocess.run(cmd, shell=True, capture_output=True,
                               timeout=timeout)
        out.append({"host": 0, "ok": local.returncode == 0,
                    "output": (local.stdout + local.stderr).decode(
                        errors="replace")[-2000:]})
        if self.multihost is not None and self.multihost.is_coordinator \
                and not getattr(self, "_mh_degraded", None):
            ch = self.multihost.channel
            try:
                with ch.exchange():
                    ch.send({"op": "exec", "cmd": cmd, "timeout": timeout})
                    # the ack deadline must outlive the command's own
                    # timeout, or a slow-but-healthy remote command would
                    # classify the worker as hung
                    acks = ch.collect_raw(deadline=float(timeout) + 30.0,
                                          phase="exec")
                for i, a in enumerate(acks):
                    out.append({"host": i + 1, "ok": bool(a.get("ok")),
                                "output": (a.get("error") or "")[:2000]})
            except Exception as e:
                out.append({"host": "?", "ok": False, "output": str(e)})
        return out

    def vacuum(self, table: str | None = None) -> dict:
        """Compact deletion bitmaps away (the lazy-VACUUM role for the
        visimap analog): every table carrying a bitmap is rewritten
        live-rows-only at its current width, which also restores zone-map
        pruned scans. -> {table: live rows kept}."""
        if self.dtm.current is not None and self.dtm.current.state == "active":
            raise SqlError("VACUUM cannot run inside a transaction")
        with self._write_lock:
            compacted: dict = {}
            snap = self.store.manifest.snapshot()
            for t, tmeta in snap.get("tables", {}).items():
                if table is not None and t != table:
                    continue
                if tmeta.get("delmask"):
                    n = self.store.rewrite_table(
                        t, self.catalog.get(t).policy.numsegments)
                    compacted[t] = n
            self.store.reap_gc()
            self._post_commit()
        return compacted

    def load_table(self, table: str, columns: dict, valids: dict | None = None):
        """Bulk load host arrays (the gpfdist/COPY fast path for benchmarks)."""
        n = self._write_rows(table, columns, valids)
        self._post_commit()
        return n

    def _copy(self, stmt: A.CopyStmt):
        schema = self.catalog.get(stmt.table)
        if self._external_def(schema) is not None:
            raise SqlError("COPY targets heap tables; external tables load "
                           "from their LOCATION at scan time")
        delim = stmt.options.get("delimiter", ",")
        header = str(stmt.options.get("header", "false")).lower() in ("true", "1")
        null_s = stmt.options.get("null", "")
        reject_limit = stmt.options.get("segment_reject_limit")
        reject_limit = int(reject_limit) if reject_limit is not None else None
        is_url = stmt.path.startswith("gpfdist://")

        if not is_url and reject_limit is None:
            # native fast path (fstream parsing analog); quoted files and
            # custom null markers fall back to the Python reader below
            from greengage_tpu.storage.csv_native import (CsvFallback,
                                                          parse_file)

            parsed_native = None
            try:
                parsed_native = parse_file(
                    stmt.path, schema, delim, header, null_s)
            except CsvFallback:
                pass
            except ValueError:
                # bad data: re-parse via the SREH-aware reader so the error
                # names the offending line (the try covers ONLY the parse —
                # a write-path error must not re-ingest the file)
                pass
            if parsed_native is not None:
                cols_n, valids_n = parsed_native
                n = self._write_rows(stmt.table, cols_n, valids_n)
                return f"COPY {n}"

        from greengage_tpu.runtime import ingest

        # chunk sources: gpfdist serves disjoint newline-aligned slices
        # fetched in parallel (the per-segment external scan role); local
        # files load as one chunk
        if is_url:
            nchunks = max(int(stmt.options.get("chunks", self.numsegments)), 1)
            chunks = ingest.fetch_chunks(stmt.path, nchunks)
        else:
            with open(stmt.path, "rb") as f:
                chunks = [f.read()]

        all_cols: dict[str, list] = {c.name: [] for c in schema.columns}
        all_valids: dict[str, list] = {c.name: [] for c in schema.columns}
        rejects: list = []
        line_base = 0
        for ci, blob in enumerate(chunks):
            try:
                text = blob.decode("utf-8")
            except UnicodeDecodeError:
                # invalid bytes: salvage per line; undecodable lines go to
                # the reject path instead of silently corrupting TEXT
                lines = []
                for li, raw in enumerate(blob.split(b"\n")):
                    try:
                        lines.append(raw.decode("utf-8"))
                    except UnicodeDecodeError:
                        rejects.append((line_base + li + 1, repr(raw),
                                        "invalid UTF-8"))
                        lines.append("")   # keep line numbering aligned
                text = "\n".join(lines)
            cols, valids, rej = ingest.parse_csv_rows(
                text, schema, delim, header and ci == 0, null_s,
                line_base=line_base)
            for name in all_cols:
                all_cols[name].extend(cols[name])
                all_valids[name].extend(valids[name])
            rejects.extend(rej)
            line_base += blob.count(b"\n")
        if rejects and reject_limit is None:
            line, raw, err = rejects[0]
            raise SqlError(f"COPY line {line}: {err}")
        if reject_limit is not None and len(rejects) > reject_limit:
            raise SqlError(
                f"COPY aborted: {len(rejects)} rejected rows exceed "
                f"SEGMENT REJECT LIMIT {reject_limit}")
        if rejects:
            ingest.append_error_log(self.path, stmt.table, rejects)

        enc_cols = {}
        enc_valids = {}
        for c in schema.columns:
            va = np.array(all_valids[c.name], dtype=bool)
            if c.type.kind is T.Kind.TEXT:
                enc_cols[c.name] = all_cols[c.name]
            else:
                enc_cols[c.name] = np.array(all_cols[c.name], dtype=c.type.np_dtype)
            if not va.all():
                enc_valids[c.name] = va
        n = self._write_rows(stmt.table, enc_cols, enc_valids)
        tag = f"COPY {n}"
        if rejects:
            tag += f" (rejected {len(rejects)} rows, logged)"
        return tag

    def error_log(self, table: str) -> list[dict]:
        """Rejected-row log for a table (gp_read_error_log analog)."""
        from greengage_tpu.runtime import ingest

        return ingest.read_error_log(self.path, table)

    # ------------------------------------------------------------------
    # DELETE / UPDATE: append-only storage rewrites the surviving rows and
    # republishes in one manifest commit (the visimap/SplitUpdate roles,
    # reference: src/backend/access/appendonly visimap + nodeSplitUpdate.c)
    # ------------------------------------------------------------------
    def _tx_for_dml(self, table: str, what: str):
        """DML inside a transaction stages a replacement built from the
        COMMITTED snapshot (tx reads see committed data only, like every
        read here), so a table already written in this tx cannot also be
        rewritten — the replacement would silently drop the tx's rows."""
        tx = self.dtm.current
        if tx is None or tx.state != "active":
            return None
        # partition children count as the parent (storage names "t#part")
        written = {t.split("#", 1)[0] for t in tx.tables_written}
        if table.split("#", 1)[0] in written:
            raise SqlError(
                f"{what}: table was already modified in this transaction "
                "(DML reads the committed snapshot; interleaved rewrite "
                "would lose the transaction's own writes)")
        return tx

    def _run_raw(self, sel_stmt):
        """A DML statement's own scan, in storage representation: planned
        and compiled through the plan and program caches like a SELECT,
        its scalar subqueries hoisted, so the statement finds its program
        again after the write it follows."""
        planned, consts, outs, ek = self._cached_plan(sel_stmt,
                                                      hoist_subqueries=True)
        res = self.executor.run(planned, consts, outs, cache_key=ek, raw=True)
        if isinstance(res.stats, dict):
            res.stats["plan_cache"] = dict(self._plan_cache_info)
        self._dml_note(res)
        return res, outs

    def _check_dml_target(self, table: str):
        schema = self.catalog.get(table)
        if self._external_def(schema) is not None:
            raise SqlError(
                f'"{table}" is an external table; DML is not supported '
                "(reference: external tables reject UPDATE/DELETE)")

    def _check_no_raw_dml(self, table: str):
        self._check_dml_target(table)
        # raw DML republishes decoded strings (see _decode_raw_out); only
        # the partitioned+raw combination stays out — raw surrogates don't
        # identify the storage child the string lives in
        if self.store.has_raw_columns(table) \
                and self.catalog.get(table).is_partitioned:
            raise SqlError(
                f'table "{table}" is partitioned with raw-encoded TEXT '
                "columns; DELETE/UPDATE are not supported on that "
                "combination")

    def _decode_raw_out(self, table: str, cname: str, data, valid):
        """DML republish: raw-column device surrogates -> host strings."""
        data = np.asarray(data, np.int64)
        strs = np.empty(len(data), dtype=object)
        m = (np.ones(len(data), bool) if valid is None
             else np.asarray(valid, bool))
        strs[m] = self.store.fetch_raw(table, cname, data[m])
        return strs

    def _tombstone_raw_cursors(self, table: str) -> None:
        """A committed raw-table republish GC's the old blobs; any open
        cursor over the table would fetch_raw from deleted files — plant
        the same tombstone DROP TABLE uses."""
        for cname, batch in list(self._cursors.items()):
            spec = getattr(getattr(batch, "comp", None), "input_spec", ())
            if any(t == table for t, *_ in spec):
                self._cursors[cname] = (
                    f'cursor "{cname}" was invalidated by DELETE/UPDATE '
                    f'on raw-text table {table}')

    def _replace_table(self, schema, enc, valids, tx, raw_strs=None) -> None:
        """Republish a table's full contents. Partitioned tables route the
        surviving rows by partition key and replace EVERY child (a child
        that receives no rows becomes empty) — UPDATEs may move rows
        across partitions, unlike the reference's pre-7 restriction."""
        if not schema.is_partitioned:
            if tx is not None:
                # tombstoning waits for COMMIT (rollback keeps old blobs
                # live; see the TxStmt commit handler)
                tx.replace(schema.name, enc, valids, raw_strs)
            else:
                self.store.replace_contents(schema.name, enc, valids,
                                            raw_strs)
                if raw_strs:
                    self._tombstone_raw_cursors(schema.name)
            return
        if raw_strs:
            raise SqlError("partitioned raw-text republish is not supported")
        _kind, pcol = schema.partition_by
        pidx = np.asarray(schema.route_rows(enc[pcol], valids.get(pcol)))
        if (pidx < 0).any():
            bad = enc[pcol][pidx < 0][0]
            raise SqlError(
                f"no partition of {schema.name} accepts value {bad!r} "
                "(and there is no DEFAULT partition)")
        # atomic across children: autocommit wraps the multi-child rewrite
        # in ONE manifest commit — a reader must never see a row twice (or
        # zero times) while an UPDATE moves it between partitions
        if tx is not None:
            for i, p in enumerate(schema.partitions):
                m = pidx == i
                tx.replace(p.storage_name(schema.name),
                           {k: v[m] for k, v in enc.items()},
                           {k: v[m] for k, v in valids.items()})
            return
        with self._autocommit_tx() as atx:
            for i, p in enumerate(schema.partitions):
                m = pidx == i
                atx.replace(p.storage_name(schema.name),
                            {k: v[m] for k, v in enc.items()},
                            {k: v[m] for k, v in valids.items()})

    def _predicate_mask(self, table: str, where) -> np.ndarray:
        """Evaluate a DML predicate over every visible row on the mesh:
        -> bool mask in gather order (segment-major, storage row order —
        the plain projection preserves it; NULL predicate = False)."""
        sel = A.SelectStmt(items=[A.SelectItem(where, alias="__dml_pred")],
                           from_=[A.BaseTable(table)])
        res, outs = self._run_raw(sel)
        o = outs[0]
        val = np.asarray(res.cols[o.id]).astype(bool)
        v = res.valids.get(o.id)
        return val if v is None else (val & np.asarray(v, bool))

    def _visimap_masks(self, table: str, pred_mask: np.ndarray) -> dict:
        """Merge a predicate mask over VISIBLE rows into per-segment
        full-length deletion bitmaps (1 = deleted). Replicated tables
        evaluate one copy and stamp every segment with the same bitmap
        (copies share row order by construction)."""
        schema = self.catalog.get(table)
        snap = self.store.manifest.snapshot()
        replicated = schema.policy.kind is PolicyKind.REPLICATED
        nseg = schema.policy.numsegments
        full = self.store.segment_rowcounts(table, snap)
        masks: dict = {}
        off = 0
        for seg in ([0] if replicated else range(nseg)):
            keep = self.store.delmask_keep(table, seg, snap)
            live = int(keep.sum()) if keep is not None else full[seg]
            m = pred_mask[off: off + live]
            off += live
            if not m.any():
                continue
            if keep is None:
                newdel = m.astype(np.uint8)
            else:
                # the predicate's mask laid over the live rows; no index
                # array of them (8 bytes a row of a 60M-row segment)
                newdel = ~keep
                newdel[keep] = m
                newdel = newdel.view(np.uint8)
            masks[seg] = newdel
        if off != len(pred_mask):
            raise RuntimeError(
                f"DML scan returned {len(pred_mask)} rows but storage "
                f"holds {off} visible rows — concurrent write raced the "
                "statement; retry")
        if replicated and masks:
            masks = {s: masks[0] for s in range(nseg)}
        return masks

    def _delete(self, stmt: A.DeleteStmt, worker_scan_only: bool = False):
        self._check_no_raw_dml(stmt.table)
        tx = self._tx_for_dml(stmt.table, "DELETE")
        _reject_dml_subqueries(stmt.where)
        schema = self.catalog.get(stmt.table)
        if not schema.is_partitioned and stmt.where is not None:
            # visimap path (appendonly_visimap.c analog): publish a
            # deletion bitmap instead of rewriting the table — DELETE
            # stages only the predicate's columns and writes O(bitmap),
            # not O(table)
            with self._dml_phase("dml_scan"):
                mask = self._predicate_mask(stmt.table, stmt.where)
            if worker_scan_only:
                return "DELETE 0"   # lockstep scan only; coordinator publishes
            with self._dml_phase("write"):
                with _trace.span("delmask", cat="dml", phase="merge"):
                    masks = self._visimap_masks(stmt.table, mask)
                if masks:
                    if tx is not None:
                        tx.set_delmask(stmt.table, masks)
                    else:
                        self.store.set_delmask(stmt.table, masks)
            return f"DELETE {int(mask.sum())}"
        # VISIBLE rows (manifest counts minus deletion bitmaps): the
        # reported DELETE count must not re-count already-deleted rows
        total = sum(self.store.live_rowcounts(stmt.table))
        raw_names = self.store.raw_column_names(stmt.table)
        if stmt.where is None:
            if worker_scan_only:
                return "DELETE 0"   # truncate: no mesh scan on either side
            empty = {c.name: np.empty(
                0, dtype=(np.int64 if c.name in raw_names
                          else c.type.np_dtype)) for c in schema.columns}
            raw_strs = {n: np.empty(0, dtype=object) for n in raw_names}
            with self._dml_phase("write"):
                self._replace_table(schema, empty, {}, tx, raw_strs or None)
            return f"DELETE {total}"
        # partitioned fallback: republish survivors (predicate false OR
        # NULL) — per-child bitmaps need per-child row spans, deferred
        survive = A.Bin("or", A.Unary("not", stmt.where), A.IsNullTest(stmt.where, False))
        sel = A.SelectStmt(items=[A.SelectItem(A.Star())],
                           from_=[A.BaseTable(stmt.table)], where=survive)
        with self._dml_phase("dml_scan"):
            res, outs = self._run_raw(sel)
        if worker_scan_only:
            return "DELETE 0"
        enc = {}
        valids = {}
        raw_strs = {}
        for c, o in zip(schema.columns, outs):
            v = res.valids.get(o.id)
            if c.name in raw_names:
                # decode surrogates while the old blobs are still live
                raw_strs[c.name] = self._decode_raw_out(
                    stmt.table, c.name, res.cols[o.id], v)
                enc[c.name] = np.zeros(len(res.cols[o.id]), np.int64)
            else:
                enc[c.name] = np.ascontiguousarray(res.cols[o.id],
                                                   dtype=c.type.np_dtype)
            if v is not None:
                valids[c.name] = v
        with self._dml_phase("write"):
            self._replace_table(schema, enc, valids, tx, raw_strs or None)
        return f"DELETE {total - len(res)}"

    def _update(self, stmt: A.UpdateStmt, worker_scan_only: bool = False):
        self._check_no_raw_dml(stmt.table)
        tx = self._tx_for_dml(stmt.table, "UPDATE")
        _reject_dml_subqueries(stmt.where)
        schema = self.catalog.get(stmt.table)
        seen = set()
        for cname, _ in stmt.sets:
            if cname not in schema.column_names:
                raise SqlError(f'column "{cname}" of relation '
                               f'"{stmt.table}" does not exist')
            if cname in seen:
                raise SqlError(f'multiple assignments to column "{cname}"')
            seen.add(cname)
        # one raw pass: all columns + new-value expressions + update flag.
        # Outputs are tracked POSITIONALLY (star cols, then one slot per
        # device-evaluated SET, then the flag) — user column names can never
        # collide with internals.
        items = [A.SelectItem(A.Star())]
        text_literals = {}
        device_slots: dict[str, int] = {}   # colname -> index into outs
        ncols = len(schema.columns)
        next_slot = ncols
        dict_dirty = False
        for cname, e in stmt.sets:
            col = schema.column(cname)
            if col.type.kind is T.Kind.TEXT and col.encoding == "raw":
                raise SqlError(
                    f'column "{cname}" is raw-encoded text; SET on raw '
                    "columns is not supported (raw columns pass through "
                    "UPDATE unchanged)")
            if col.type.kind is T.Kind.TEXT:
                if isinstance(e, A.Str):
                    code = self.store.dictionary(stmt.table, cname).encode([e.value])[0]
                    dict_dirty = True
                    text_literals[cname] = np.int32(code)
                    continue
                if isinstance(e, A.Null):
                    text_literals[cname] = None
                    continue
                items.append(A.SelectItem(e, alias=f"__new_{cname}"))
            else:
                tname, typmod = _sql_type_name(col.type)
                items.append(A.SelectItem(A.CastExpr(e, tname, typmod),
                                          alias=f"__new_{cname}"))
            device_slots[cname] = next_slot
            next_slot += 1
        if dict_dirty and not worker_scan_only:
            self.store.flush_dicts(stmt.table)
        flag = stmt.where if stmt.where is not None else A.Bool(True)
        items.append(A.SelectItem(flag, alias="__upd"))
        flag_slot = next_slot
        # visimap split (nodeSplitUpdate.c + appendonly_visimap.c): mark
        # the old row versions deleted in the bitmap and APPEND the new
        # versions — the matched-rows scan pushes the WHERE (pruning
        # applies), so an UPDATE touches O(matched + bitmap), not
        # O(table). Partitioned / whole-table UPDATEs keep the republish.
        visimap = not schema.is_partitioned and stmt.where is not None
        pred_mask = None
        sel = A.SelectStmt(items=items, from_=[A.BaseTable(stmt.table)],
                           where=stmt.where if visimap else None)
        with self._dml_phase("dml_scan"):
            if visimap:
                pred_mask = self._predicate_mask(stmt.table, stmt.where)
            res, outs = self._run_raw(sel)
        if worker_scan_only:
            return "UPDATE 0"   # multi-host worker: scan only, no publish
        fo = outs[flag_slot]
        fval = res.cols[fo.id].astype(bool)
        fv = res.valids.get(fo.id)
        mask = fval if fv is None else (fval & fv)   # NULL predicate -> no update
        enc, valids = {}, {}
        raw_strs = {}
        for c, o in zip(schema.columns, outs[:ncols]):
            if c.type.kind is T.Kind.TEXT and c.encoding == "raw":
                # pass-through: decode while old blobs are live, republish
                v = res.valids.get(o.id)
                raw_strs[c.name] = self._decode_raw_out(
                    stmt.table, c.name, res.cols[o.id], v)
                enc[c.name] = np.zeros(len(res.cols[o.id]), np.int64)
                if v is not None:
                    valids[c.name] = np.asarray(v, bool)
                continue
            old = np.ascontiguousarray(res.cols[o.id], dtype=c.type.np_dtype)
            oldv = res.valids.get(o.id)
            oldv = np.ones(len(old), bool) if oldv is None else oldv
            if c.name in text_literals:
                lit = text_literals[c.name]
                if lit is None:
                    new = old
                    newv = np.zeros(len(old), bool)
                else:
                    new = np.full(len(old), lit, dtype=np.int32)
                    newv = np.ones(len(old), bool)
            elif c.name in device_slots:
                no = outs[device_slots[c.name]]
                if (c.type.kind is T.Kind.TEXT and no.dict_ref is not None
                        and no.dict_ref != (stmt.table, c.name)):
                    raise SqlError(
                        "text UPDATE from a different dictionary is not supported")
                new = np.ascontiguousarray(res.cols[no.id], dtype=c.type.np_dtype)
                nv = res.valids.get(no.id)
                newv = np.ones(len(new), bool) if nv is None else nv
            else:
                new, newv = old, oldv
            merged = np.where(mask, new, old)
            mergedv = np.where(mask, newv, oldv)
            enc[c.name] = merged.astype(c.type.np_dtype)
            if not mergedv.all():
                valids[c.name] = mergedv
        if visimap:
            if len(res) != int(pred_mask.sum()):
                raise RuntimeError(
                    f"UPDATE matched-row scan returned {len(res)} rows but "
                    f"the predicate pass marked {int(pred_mask.sum())} — "
                    "concurrent write raced the statement; retry")
            with self._dml_phase("write"):
                with _trace.span("delmask", cat="dml", phase="merge"):
                    masks = self._visimap_masks(stmt.table, pred_mask)
                with self._autocommit_tx() as atx:
                    if masks:
                        atx.set_delmask(stmt.table, masks)
                    if len(res):
                        atx.insert_encoded(stmt.table, enc, valids,
                                           raw_strs or None)
            return f"UPDATE {int(pred_mask.sum())}"
        with self._dml_phase("write"):
            self._replace_table(schema, enc, valids, tx, raw_strs or None)
        return f"UPDATE {int(mask.sum())}"

    # ------------------------------------------------------------------
    def expand(self, new_numsegments: int) -> dict:
        """gpexpand analog: widen the cluster and redistribute every table.

        Phase 1 adds segments to the topology; phase 2 rewrites each table
        at the new width (ALTER TABLE ... EXPAND TABLE). Tables stay
        readable between phases because plans honor per-table numsegments
        (mixed-width, gp_policy.h:35 semantics)."""
        if self.dtm.current is not None and self.dtm.current.state == "active":
            raise SqlError("cannot expand inside a transaction")
        devs = self._devices
        if new_numsegments > len(devs):
            raise ValueError(
                f"cannot expand to {new_numsegments}: only {len(devs)} devices")
        if new_numsegments <= self.numsegments:
            raise ValueError("expansion must increase the segment count")
        # phase 1: new topology (existing entries, incl. FTS state, preserved)
        self.catalog.segments.expand(new_numsegments)
        self.numsegments = new_numsegments
        self.catalog._save()
        self.mesh = make_mesh(new_numsegments, devs)
        self.executor = Executor(self.catalog, self.store, self.mesh,
                                 new_numsegments, self.settings)
        self._select_cache.clear()
        self.fts.config = self.catalog.segments
        self.fts.mesh = self.mesh
        # phase 2: redistribute each table
        moved = {}
        for name in list(self.catalog.tables):
            schema = self.catalog.get(name)
            if schema.is_partitioned:
                # rewrite each child; the shared policy width flips once
                # (all children reference the parent's DistPolicy)
                moved[name] = sum(
                    self.store.rewrite_table(st, new_numsegments)
                    for st in schema.storage_tables())
            else:
                moved[name] = self.store.rewrite_table(name, new_numsegments)
        if self.replicator is not None:
            from greengage_tpu.runtime.replication import Replicator

            self.replicator = Replicator(self.store, self.catalog.segments)
        self._post_commit()
        return moved

    def set(self, name: str, value):
        self.settings.set(name, value)

    def close(self):
        # stop the background probers/heartbeats and send the gang a clean
        # stop frame (workers distinguish this from a coordinator crash)
        try:
            self.ingest.stop()   # drain-or-abort open streams first
        except Exception:
            pass
        try:
            # calibration state survives restart (promotion already kept
            # hot state: reconcile saves on every applied correction)
            self.feedback.save()
        except Exception:
            pass
        try:
            self.fts.stop()
        except Exception:
            pass
        if self._batch_server is not None:
            try:
                self._batch_server.stop()
            except Exception:
                pass
        if self.multihost is not None and self.multihost.is_coordinator \
                and self.multihost.channel is not None:
            try:
                self.multihost.channel.close()
            except Exception:
                pass


class _RWLock:
    """Write-path lock with a SHARED mode for per-table appenders.

    Exclusive = the classic session write lock (DDL, transactions,
    catalog moves, DELETE/UPDATE): one holder, re-entrant per thread.
    Shared = autocommit single-table appends (INSERT/COPY): any number of
    holders, each additionally serialized per TABLE by the session's
    table-lock map — so hot appenders to DIFFERENT tables stage and
    commit concurrently (their manifest commits are per-table delta CAS,
    storage/manifest.py) while anything structural still drains them.
    A waiting exclusive holder gates NEW shared entrants (no writer
    starvation); a thread holding exclusive may take shared (nested
    statement paths)."""

    def __init__(self):
        self._c = threading.Condition()
        self._excl: int | None = None     # owning thread ident
        self._depth = 0
        self._excl_waiting = 0
        self._shared: dict[int, int] = {}  # thread ident -> hold depth

    # exclusive (context manager: `with db._write_lock:`)
    def __enter__(self):
        me = threading.get_ident()
        with self._c:
            self._excl_waiting += 1
            try:
                while not (self._excl in (None, me)
                           and all(t == me for t in self._shared)):
                    # timed slices: a cancelled writer must leave the
                    # wait (statement cancellation point, PR-4 style)
                    self._c.wait(0.25)
                    check_interrupts()
            finally:
                self._excl_waiting -= 1
            self._excl = me
            self._depth += 1
        return self

    def __exit__(self, *a):
        with self._c:
            self._depth -= 1
            if self._depth == 0:
                self._excl = None
            self._c.notify_all()
        return False

    def shared(self):
        @_contextmanager
        def _shared_cm():
            me = threading.get_ident()
            with self._c:
                while (self._excl not in (None, me)
                       or (self._excl_waiting and self._excl is None
                           and me not in self._shared)):
                    # timed slices: cancelled appenders leave the wait
                    self._c.wait(0.25)
                    check_interrupts()
                self._shared[me] = self._shared.get(me, 0) + 1
            try:
                yield self
            finally:
                with self._c:
                    n = self._shared.get(me, 1) - 1
                    if n:
                        self._shared[me] = n
                    else:
                        self._shared.pop(me, None)
                    self._c.notify_all()

        return _shared_cm()


class DmlResult(str):
    """What `Database.sql` answers an INSERT, DELETE or UPDATE with: its
    command tag — it IS that string, so `db.sql("delete ...") == "DELETE
    2"` holds and every caller that prints or forwards a tag still does —
    which also answers like a SELECT's Result: `rows()` is one row (tag,
    rows affected) and `stats` holds a SELECT's keys where they apply,
    summed over the statement's inner statements, plus `dml_scan_ms`,
    `write_ms`, `rows_written` and `rows_deleted`."""

    def __new__(cls, tag: str, stats: dict | None = None):
        self = super().__new__(cls, tag)
        self.stats = stats if stats is not None else {}
        return self

    def __reduce__(self):
        return (DmlResult, (str(self), self.stats))

    @property
    def nrows(self) -> int:
        return int(self.rsplit(" ", 1)[1])

    def rows(self) -> list[tuple]:
        return [(str(self), self.nrows)]


class _DmlRun:
    """One DML statement's account (Database._dml): the statistics of its
    inner statements as they finish and its two phases' clocks."""

    # a SELECT's statistics that add up over a statement's inner
    # statements: the phases of each attempt and the staging counts
    SUMMED = ("compile_ms", "stage_ms", "compute_ms", "fetch_ms",
              *_staging.STAGE_COUNTERS)

    def __init__(self):
        self.inner: list[dict] = []
        self.ms = {"dml_scan": 0.0, "write": 0.0}

    def answer(self, tag: str) -> DmlResult:
        out = DmlResult(tag)
        verb = tag.split(" ", 1)[0]
        caches = [s.get("plan_cache") or {} for s in self.inner]
        out.stats.update(
            {k: round(sum(s.get(k, 0) for s in self.inner), 2)
             for k in self.SUMMED},
            compiled=any(s.get("compiled") for s in self.inner),
            plan_cache={"hit": all(c.get("hit") for c in caches),
                        "params": sum(c.get("params", 0) for c in caches)},
            inner_statements=len(self.inner),
            dml_scan_ms=round(self.ms["dml_scan"], 2),
            write_ms=round(self.ms["write"], 2),
            rows_written=out.nrows if verb in ("INSERT", "UPDATE") else 0,
            rows_deleted=out.nrows if verb in ("DELETE", "UPDATE") else 0)
        return out


class _DegradedResult:
    """Result façade for statements served by the degraded-mode
    subprocess (worker death): rows come back JSON-decoded."""

    def __init__(self, columns, rows):
        self.columns = list(columns)
        self._rows = [tuple(r) for r in rows]
        self.stats = {"degraded": True}

    def rows(self):
        return self._rows

    def __len__(self):
        return len(self._rows)


class _NullSlot:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


class _EmptyScope:
    tables: list = []

    def resolve(self, parts):
        raise SqlError(f'column "{".".join(parts)}" does not exist')


def _zero_for(t: T.SqlType):
    if t.kind is T.Kind.TEXT:
        return ""
    return 0


def _reject_dml_subqueries(where) -> None:
    """IN/EXISTS in DML WHERE need dedicated survivor-semantics handling
    (x IN S being NULL must *survive* a DELETE); until then, fail clearly."""
    if where is None:
        return
    stack = [where]
    while stack:
        n = stack.pop()
        if isinstance(n, (A.InSubquery, A.ExistsExpr)):
            raise SqlError(
                "IN/EXISTS subqueries in DELETE/UPDATE WHERE are not "
                "supported yet")
        for f in ("left", "right", "arg", "lo", "hi", "else_"):
            v = getattr(n, f, None)
            if isinstance(v, A.ANode):
                stack.append(v)
        for v in getattr(n, "args", []) or []:
            stack.append(v)
        for v in getattr(n, "values", []) or []:
            if isinstance(v, A.ANode):
                stack.append(v)
        for cond, val in getattr(n, "whens", []) or []:
            stack.append(cond)
            stack.append(val)


def _sql_type_name(t: T.SqlType) -> tuple[str, tuple[int, ...]]:
    """SqlType -> (type name, typmod) for constructing CAST ASTs."""
    k = t.kind
    if k is T.Kind.DECIMAL:
        return "numeric", (38, t.scale)
    return {
        T.Kind.INT32: ("int", ()),
        T.Kind.INT64: ("bigint", ()),
        T.Kind.FLOAT64: ("double precision", ()),
        T.Kind.DATE: ("date", ()),
        T.Kind.BOOL: ("bool", ()),
        T.Kind.TEXT: ("text", ()),
    }[k]


_REC_COUNTER = __import__("itertools").count()


def _ddl_type(t) -> str:
    """SqlType -> DDL text for recursive-CTE materialization (DECIMAL
    degrades to double precision: host accumulation sees descaled
    floats)."""
    k = t.kind
    if k is T.Kind.INT32:
        return "int"
    if k is T.Kind.INT64:
        return "bigint"
    if k in (T.Kind.FLOAT64, T.Kind.DECIMAL):
        return "double precision"
    if k is T.Kind.BOOL:
        return "bool"
    if k is T.Kind.DATE:
        return "date"
    return "text"


def _rename_base_tables(node, mapping: dict):
    """Rewrite BaseTable references per ``mapping`` everywhere in the AST
    (including subqueries) — the worktable substitution."""

    if isinstance(node, A.BaseTable):
        if node.name in mapping:
            if node.alias is None:
                node.alias = node.name       # keep qualified refs valid
            node.name = mapping[node.name]
        return node
    if isinstance(node, A.ANode):
        for f in _dc.fields(node):
            v = getattr(node, f.name)
            setattr(node, f.name, _rename_base_tables(v, mapping))
        return node
    if isinstance(node, list):
        return [_rename_base_tables(v, mapping) for v in node]
    if isinstance(node, tuple):
        return tuple(_rename_base_tables(v, mapping) for v in node)
    return node


def _inferred_col(name: str, arr):
    """ColInfo-lite (name+type) from a host result array — the typing
    fallback for constant-only recursive base terms."""

    k = arr.dtype.kind
    if k == "M":
        t = T.DATE
    elif k == "b":
        t = T.BOOL
    elif k == "i" and arr.dtype.itemsize <= 4:
        t = T.INT32
    elif k in ("i", "u"):
        t = T.INT64
    elif k == "f":
        t = T.FLOAT64
    else:
        t = T.TEXT
    return SimpleNamespace(name=name, type=t)
