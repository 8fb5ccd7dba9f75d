"""Line-protocol SQL server — the postmaster/libpq listener analog.

A thin concurrent front end over one Database: clients connect to a unix
socket (or TCP port) and exchange newline-delimited JSON:

    -> {"sql": "select ..."}
    <- {"ok": true, "columns": [...], "rows": [[...], ...], "tag": null}
    <- {"ok": false, "error": "..."}

Control frames ride the same protocol (the pg_stat_activity /
pg_cancel_backend surface, served from ANOTHER connection since the
executing one is blocked in its statement):

    -> {"op": "ps"}            <- {"ok": true, "rows": [activity...]}
    -> {"op": "cancel", "id": N}  <- {"ok": true/false}
    -> {"op": "mem"}           <- {"ok": true, "mem": {device/accounts...}}

Reference parity: exec_simple_query serving many clients
(src/backend/tcop/postgres.c:1622). Each connection gets a thread; SELECTs
run lock-free on manifest snapshots, write statements serialize on the
session write lock (one writer gang at a time), so concurrent COPY +
SELECT + UPDATE interleave safely. Transaction state is per connection
(the Database keeps one DtmSession per thread, and each connection is a
thread), so BEGIN/COMMIT/ROLLBACK work over the wire; a connection that
drops mid-transaction is rolled back, like a backend exiting — and a
disconnect observed mid-exchange cancels the connection's in-flight
statement with cause ``client_gone`` instead of letting the broken-pipe
error escape into socketserver. Conflicting commits fail at the manifest
CAS with a serialization error.

Overload armor (docs/ROBUSTNESS.md "Overload protection") — the front
end is BOUNDED in every dimension a hostile or overloaded client could
grow it:

  * ``max_connections`` caps concurrent handler threads; an excess
    connect receives one typed, retryable ``too_many_connections`` frame
    (the SQLSTATE 53300 fast-fail) and the socket closes — never silent
    thread growth. ``connections_shed_total`` counts the sheds and the
    ``server_active_connections`` gauge tracks the live population; the
    ``overload_accept`` fault point forces the shed path in tests.
  * ``client_auth_deadline_s`` bounds the TCP auth handshake and
    ``client_idle_timeout_s`` (optional) bounds idle reads between
    statements, so a stalled peer cannot pin a handler forever.
  * ``max_frame_bytes`` bounds one request frame; an oversized line gets
    a typed ``frame_too_large`` error and the connection closes (the
    stream cannot be resynced past a partially-read line), so a
    multi-GB JSON line cannot OOM the host.
  * load-shed errors from admission (``AdmissionShed``,
    runtime/resqueue.py) map to a typed retryable frame with
    ``"sqlstate": "53300"``.
  * ``stop()`` drains gracefully: stop accepting, flag in-flight
    statements with cause ``shutdown`` via the interrupt registry,
    bounded join (``server_drain_s``), then force-close stragglers.

Disconnect watching is one ``_ConnWatcher`` thread PER CONNECTION (not
per statement): the handler arms it around each db.sql() and it parks
between statements, so a client pipelining 10k statements reuses one
watcher instead of spawning 10k short-lived threads.
"""

from __future__ import annotations

import errno
import json
import os
import socket
import socketserver
import threading
import time
import select

from greengage_tpu.runtime import lockdebug
from greengage_tpu.runtime import overload as _overload
from greengage_tpu.runtime.faultinject import FaultError, faults
from greengage_tpu.runtime.interrupt import REGISTRY, StatementCancelled
from greengage_tpu.runtime.logger import counters
from greengage_tpu.runtime.resqueue import AdmissionShed

# select/recv errnos that genuinely prove the peer (or our own fd) is
# gone; anything else is a TRANSIENT poll hiccup that must NOT cancel a
# live client's statement (the old behavior treated every OSError as an
# EOF and killed healthy statements on a spurious select failure)
_WATCH_FATAL_ERRNOS = frozenset({
    errno.EBADF, errno.ENOTCONN, errno.ECONNRESET, errno.EPIPE,
    errno.ESHUTDOWN, errno.ECONNABORTED,
})

# consecutive transient poll failures before the watcher gives up on the
# CURRENT statement (without cancelling — losing disconnect detection is
# the lesser harm vs cancelling a live client's work)
_WATCH_TRANSIENT_LIMIT = 5


def _is_failover_error(e: BaseException) -> bool:
    """Coordinator-failover causes deserving the typed retryable 57P01
    frame: a stale fenced coordinator's refused commit, or the gang's
    coordinator channel dying out from under a dispatched statement.
    One causal hop is checked too — the session wraps commit errors."""
    from greengage_tpu.parallel.multihost import CoordinatorLost
    from greengage_tpu.storage.manifest import CoordinatorFenced

    kinds = (CoordinatorFenced, CoordinatorLost)
    if isinstance(e, kinds):
        return True
    return isinstance(e.__cause__ or e.__context__, kinds)


def _watch_tick(sock) -> str:
    """One disconnect-watch poll of the client socket. Returns:
    ``eof``   — the peer closed (or our fd is gone): the statement has
                nobody to read it;
    ``data``  — a pipelined request is buffered (client alive; the byte
                is PEEKed, never consumed);
    ``idle``  — nothing readable;
    ``transient`` — the poll itself failed for a reason that does not
                prove the peer is gone (spurious select error)."""
    try:
        r, _, _ = select.select([sock], [], [], 0)
        if not r:
            return "idle"
        if sock.recv(1, socket.MSG_PEEK | socket.MSG_DONTWAIT) == b"":
            return "eof"
        return "data"
    except (BlockingIOError, InterruptedError):
        return "idle"
    except ValueError:
        return "eof"       # fd already closed on our side (drain/teardown)
    except OSError as e:
        if e.errno in _WATCH_FATAL_ERRNOS:
            return "eof"
        return "transient"


class _ConnWatcher:
    """One client-disconnect watcher per CONNECTION: while the handler
    thread is blocked inside db.sql(), only a peeker can observe the
    client's EOF and flag the statement ``client_gone`` so it dies at
    its next cancellation point instead of running to completion for
    nobody. The handler arms the watcher around each statement; between
    statements (and after observing pipelined DATA, which means the
    client is alive) it parks on its condition instead of exiting, so
    one thread serves the whole connection's statement stream."""

    POLL_S = 0.1

    def __init__(self, sock, thread_ident: int):
        self._sock = sock
        self._ident = thread_ident
        self._mu = lockdebug.named(threading.Lock(), "server.watcher._mu")
        self._cv = threading.Condition(self._mu)
        self._armed = False
        self._stopping = False
        # arm/disarm epoch: a self-disarm (pipelined data / transient
        # streak) must not erase an arm() the handler issued for the
        # NEXT statement in the meantime
        self._gen = 0
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="gg-client-watch")
        self._thread.start()

    def arm(self) -> None:
        with self._cv:
            self._gen += 1
            self._armed = True
            self._cv.notify_all()

    def disarm(self) -> None:
        with self._cv:
            self._gen += 1
            self._armed = False

    def shutdown(self) -> None:
        with self._cv:
            self._stopping = True
            self._cv.notify_all()
        self._thread.join(timeout=1.0)

    def _loop(self) -> None:
        transient = 0
        while True:
            with self._cv:
                while not self._armed and not self._stopping:
                    self._cv.wait(0.5)
                if self._stopping:
                    return
                gen = self._gen
            state = _watch_tick(self._sock)
            if state == "eof":
                REGISTRY.cancel_thread(self._ident, "client_gone")
                return
            if state == "data":
                # buffered pipelined request: client alive — stop
                # watching THIS statement (never consume the byte)
                transient = 0
                self._self_disarm(gen)
                continue
            if state == "transient":
                transient += 1
                if transient >= _WATCH_TRANSIENT_LIMIT:
                    # a persistent poll failure proves nothing about the
                    # peer: give up on this statement WITHOUT cancelling
                    transient = 0
                    self._self_disarm(gen)
                    continue
            else:
                transient = 0
            time.sleep(self.POLL_S)

    def _self_disarm(self, gen: int) -> None:
        with self._cv:
            if self._gen == gen:   # handler has not re-armed since
                self._armed = False


def _pipeline_depths(db) -> dict:
    """Serving-pipeline queue depths for the ps/status frames: members
    waiting in batched-serving admission windows, batches staged-but-not-
    demuxed, and the staging pool's read-unit backlog (the PR-10
    staging_pool_queue_depth probe, reused rather than re-measured)."""
    from greengage_tpu.exec import staging

    out = {"staging_pool_queue_depth": staging.pool_queue_depth()}
    bs = getattr(db, "_batch_server", None)
    if bs is not None:
        try:
            out.update(bs.queue_depths())
        except Exception:
            pass
    return out


def _cluster_status(db) -> dict:
    """Topology state for the ps/status control frames; resilient to a
    Database predating mh_state (bare test doubles)."""
    try:
        return db.mh_state()
    except Exception:
        return {"state": "unknown", "topology_version": None}


def _encode_value(v):
    import numpy as np

    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    if isinstance(v, (np.bool_,)):
        return bool(v)
    if isinstance(v, np.integer):
        return int(v)
    if isinstance(v, np.floating):
        return float(v)
    return str(v)


class SqlServer:
    def __init__(self, db, socket_path: str, host: str | None = None,
                 port: int | None = None):
        self.db = db
        self.socket_path = socket_path
        self.host, self.port = host, port
        self._server = None
        self._tcp_server = None
        self._thread = None
        self._tcp_thread = None
        # connection admission/drain state, shared with every handler
        # thread (declared in analysis/threadmodel.py SHARED_CLASSES;
        # all mutation under _conn_mu)
        self._conn_mu = lockdebug.named(threading.Lock(),
                                        "server._conn_mu")
        self._active_conns = 0
        self._served = 0
        self._draining = False
        self._conns: dict = {}      # thread ident -> client socket
        self._handlers: dict = {}   # thread ident -> handler Thread

    @property
    def connections_served(self) -> int:
        with self._conn_mu:
            return self._served

    # ---- bounded front end (admission / drain) -----------------------
    def _admit_connection(self, sock) -> tuple | None:
        """Admit the calling handler thread, or return the typed shed
        ``(code, message)``. The cap check and the bookkeeping are one
        atomic step under _conn_mu — two racing connects cannot both
        claim the last slot (the connections_served data race this
        replaces was exactly that shape)."""
        limit = int(getattr(self.db.settings, "max_connections", 0))
        try:
            forced = faults.check("overload_accept")
        except FaultError:
            forced = True
        me = threading.current_thread()
        with self._conn_mu:
            if self._draining:
                shed = ("shutting_down", "server is shutting down")
            elif forced or (limit > 0 and self._active_conns >= limit):
                shed = ("too_many_connections",
                        f"too many connections (max_connections={limit}, "
                        f"active={self._active_conns})")
            else:
                shed = None
                self._active_conns += 1
                self._served += 1
                self._conns[me.ident] = sock
                self._handlers[me.ident] = me
                # gauge set INSIDE the lock: a set outside with a
                # captured count can land out of order against a racing
                # release and leave the gauge wrong forever
                counters.set("server_active_connections",
                             self._active_conns)
        if shed is not None:
            counters.inc("connections_shed_total")
            self.db.log.log("WARNING", "overload",
                            f"connection shed: {shed[1]}")
            return shed
        counters.inc("server_connections_total")
        return None

    def _release_connection(self) -> None:
        me = threading.get_ident()
        with self._conn_mu:
            if self._conns.pop(me, None) is not None:
                self._active_conns -= 1
            self._handlers.pop(me, None)
            counters.set("server_active_connections",
                         self._active_conns)   # under the lock: ordered

    def _draining_now(self) -> bool:
        with self._conn_mu:
            return self._draining

    # ------------------------------------------------------------------
    def start(self) -> None:
        if os.path.exists(self.socket_path):
            os.remove(self.socket_path)
        outer = self

        class Handler(socketserver.StreamRequestHandler):
            REMOTE = False   # TCP subclass flips this: remote => auth

            def handle(self):
                shed = outer._admit_connection(self.connection)
                if shed is not None:
                    # typed fast-fail (SQLSTATE 53300 analog): one frame,
                    # then the socket closes — the client can back off
                    # and retry instead of hanging on a dead connection
                    self._send({"ok": False, "error": shed[1],
                                "code": shed[0], "sqlstate": "53300",
                                "retryable": True})
                    return
                try:
                    if self.REMOTE and not self._authenticate():
                        return
                    self._serve()
                finally:
                    outer._release_connection()
                    # a connection dropping mid-transaction rolls back, and
                    # its cursors close, like a libpq backend exiting
                    outer.db.abort_if_active()
                    outer.db.close_thread_cursors()

            def _send(self, obj: dict) -> None:
                """Best-effort frame write: a peer that vanished before
                reading its typed error is not an event worth a
                traceback."""
                try:
                    self.wfile.write((json.dumps(obj) + "\n").encode())
                    self.wfile.flush()
                except (OSError, ValueError):
                    pass

            def _authenticate(self) -> bool:
                """Challenge-response over TCP (auth.c role): unix-socket
                peers are trusted, remote peers must prove a gg_hba.json
                password without sending it (runtime/auth.py). The whole
                handshake is bounded by client_auth_deadline_s — a peer
                that connects and goes silent cannot pin this handler."""
                from greengage_tpu.runtime import auth

                deadline = float(getattr(outer.db.settings,
                                         "client_auth_deadline_s", 10.0))
                old_timeout = self.connection.gettimeout()
                if deadline > 0:
                    self.connection.settimeout(deadline)
                users = auth.load_users(outer.db.path)
                ok = False
                timed_out = False
                try:
                    hello = json.loads(self.rfile.readline() or b"{}")
                    user = str(hello.get("user", ""))
                    ch = auth.challenge(users, user, outer.db.path)
                    self.wfile.write((json.dumps(ch) + "\n").encode())
                    self.wfile.flush()
                    resp = json.loads(self.rfile.readline() or b"{}")
                    ok = auth.verify(users, user, ch["nonce"],
                                     str(resp.get("proof", "")))
                    self.wfile.write((json.dumps(
                        {"ok": ok, "error": None if ok
                         else "authentication failed"}) + "\n").encode())
                    self.wfile.flush()
                except (socket.timeout, TimeoutError):
                    # silent peer past the deadline: shed the handler
                    ok = False
                    timed_out = True
                except Exception:
                    # dropped peers and malformed handshakes must not
                    # traceback per port-scan probe
                    ok = False
                finally:
                    try:
                        self.connection.settimeout(old_timeout)
                    except OSError:
                        pass
                if timed_out:
                    counters.inc("connections_shed_total")
                    outer.db.log.log(
                        "WARNING", "overload",
                        f"auth handshake exceeded client_auth_deadline_s"
                        f"={deadline:g}; connection closed")
                elif not ok:
                    outer.db.log.log("WARNING", "auth",
                                     "remote authentication failed")
                return ok

            def _serve(self):
                me = threading.get_ident()
                watcher = None
                settings = outer.db.settings
                idle_s = float(getattr(settings,
                                       "client_idle_timeout_s", 0.0))
                max_frame = int(getattr(settings,
                                        "max_frame_bytes", 64 << 20))
                if idle_s > 0:
                    try:
                        self.connection.settimeout(idle_s)
                    except OSError:
                        return
                try:
                    while True:
                        try:
                            line = self.rfile.readline(max_frame + 1)
                        except (socket.timeout, TimeoutError):
                            # idle past the deadline: typed goodbye
                            self._send({
                                "ok": False, "code": "idle_timeout",
                                "error": "connection idle beyond client_"
                                         f"idle_timeout_s={idle_s:g}; "
                                         "closing"})
                            return
                        if not line:
                            return      # EOF: client closed cleanly
                        if len(line) > max_frame:
                            # the stream cannot be resynced past a
                            # partially-read oversized line: reject AND
                            # close, so a multi-GB frame costs the host
                            # max_frame_bytes, not its full length
                            counters.inc("frames_rejected_total")
                            self._send({
                                "ok": False, "code": "frame_too_large",
                                "error": "request frame exceeds "
                                         f"max_frame_bytes={max_frame}; "
                                         "closing connection"})
                            return
                        line = line.strip()
                        if not line:
                            continue
                        if outer._draining_now():
                            self._send({
                                "ok": False, "code": "shutting_down",
                                "sqlstate": "53300", "retryable": True,
                                "error": "server is shutting down"})
                            return
                        try:
                            req = json.loads(line)
                            if "op" in req and "sql" not in req:
                                resp = self._control(req)
                            else:
                                # watch for a mid-statement disconnect:
                                # this thread is blocked in db.sql(), so
                                # only a peeker can observe the EOF and
                                # flag the statement client_gone. ONE
                                # watcher per connection, armed per
                                # statement (satellite: no thread per
                                # pipelined statement)
                                if watcher is None:
                                    watcher = _ConnWatcher(
                                        self.connection, me)
                                watcher.arm()
                                try:
                                    out = outer.db.sql(req["sql"])
                                finally:
                                    watcher.disarm()
                                if isinstance(out, str) or out is None:
                                    resp = {"ok": True, "columns": None,
                                            "rows": None, "tag": out}
                                else:
                                    resp = {
                                        "ok": True,
                                        "columns": list(out.columns),
                                        "rows": [[_encode_value(v)
                                                  for v in row]
                                                 for row in out.rows()],
                                        "tag": None,
                                    }
                        except StatementCancelled as e:
                            # surface the typed cause to the client (the
                            # '57014 query_canceled' SQLSTATE analog)
                            resp = {"ok": False, "error": f"{e}",
                                    "cancelled": e.cause}
                        except AdmissionShed as e:
                            # load shed (docs/ROBUSTNESS.md "Overload
                            # protection"): typed + retryable, the
                            # SQLSTATE 53300 queue-rejection analog
                            resp = {"ok": False, "error": f"{e}",
                                    "code": "admission_shed",
                                    "sqlstate": "53300",
                                    "retryable": True}
                        except Exception as e:  # per-statement isolation
                            if _is_failover_error(e):
                                # coordinator failover (docs/ROBUSTNESS.md
                                # "Coordinator failover"): the statement
                                # died because this coordinator was fenced
                                # by a promoted standby or lost its gang
                                # mid-failover — typed + retryable, the
                                # SQLSTATE 57P01 admin-shutdown analog;
                                # the client retries against the promoted
                                # coordinator's address
                                resp = {"ok": False, "error": f"{e}",
                                        "code": "coordinator_failover",
                                        "sqlstate": "57P01",
                                        "retryable": True}
                            else:
                                resp = {"ok": False, "error": f"{e}"}
                        try:
                            self.wfile.write(
                                (json.dumps(resp) + "\n").encode())
                            self.wfile.flush()
                        except (socket.timeout, TimeoutError):
                            # client_idle_timeout_s also deadlines WRITES
                            # (settimeout covers both directions): a
                            # reader too slow to drain its result within
                            # the idle budget is the same overload class
                            # as a silent peer — close, never traceback
                            outer.db.log.log(
                                "WARNING", "overload",
                                "response write exceeded client_idle_"
                                "timeout_s; closing connection")
                            return
                except (BrokenPipeError, ConnectionResetError):
                    # the client vanished mid-exchange: flag whatever this
                    # connection still has in flight as client_gone and
                    # end the handler cleanly — a disconnect must never
                    # traceback into socketserver (the statement dies at
                    # its next cancellation point and nobody reads the
                    # error)
                    REGISTRY.cancel_thread(me, "client_gone")
                    outer.db.log.log("WARNING", "connection",
                                     "client disconnected mid-exchange")
                finally:
                    if watcher is not None:
                        watcher.shutdown()

            def _control(self, req: dict) -> dict:
                """Protocol control ops (never parsed as SQL): 'ps' lists
                in-flight statements, 'cancel' flags one by id, 'metrics'
                serves the Prometheus text exposition, 'trace' exports one
                statement's Chrome trace_event JSON from the trace ring."""
                op = req.get("op")
                if op == "ps":
                    from greengage_tpu.runtime.trace import TRACES

                    rows = REGISTRY.snapshot()
                    bs = getattr(outer.db, "_batch_server", None)
                    for r in rows:
                        # current execution phase from the trace registry
                        # (`gg ps` SPAN column): deepest open span + its
                        # elapsed ms, when the statement is traced
                        sp = TRACES.active_span(r["id"])
                        if sp is not None:
                            r["span"], r["span_ms"] = sp[0], round(sp[1], 1)
                        # batched-serving membership (`gg ps` BATCH
                        # column): which flush window this statement is
                        # riding, when it is riding one
                        if bs is not None:
                            bid = bs.member_of(r["id"])
                            if bid is not None:
                                r["batch"] = bid
                    return {"ok": True, "rows": rows,
                            "cluster": _cluster_status(outer.db),
                            "pipeline": _pipeline_depths(outer.db),
                            "overload": _overload.CONTROLLER.snapshot(),
                            "ingest": outer.db.ingest.stream_status()}
                if op == "metrics":
                    # Prometheus text exposition over the process-wide
                    # counters/gauges/histograms (`gg metrics`); host
                    # process gauges (RSS, fds, staging-pool depth,
                    # per-owner live bytes) refresh at scrape time
                    from greengage_tpu.runtime import memaccount
                    from greengage_tpu.runtime.logger import prometheus_text

                    memaccount.update_process_gauges()
                    return {"ok": True, "text": prometheus_text()}
                if op == "mem":
                    # the measured-memory surface (`gg mem`): device
                    # allocator stats, per-statement accounting trees,
                    # the runaway ledger, block-cache budget state, and
                    # per-executable measured footprints
                    from greengage_tpu.runtime import memaccount

                    return {"ok": True, "mem": memaccount.report(outer.db)}
                if op == "checkperf":
                    # the self-tuning surface (`gg checkperf --feedback`
                    # against a live server): per-plan-digest
                    # est-vs-actual error, with apply/reset sub-ops
                    fb = outer.db.feedback
                    if req.get("reset"):
                        fb.reset()
                        return {"ok": True, "reset": True}
                    out = {"ok": True}
                    if req.get("apply"):
                        out["applied"] = fb.apply_pending()
                    out["feedback"] = fb.report()
                    return out
                if op == "trace":
                    from greengage_tpu.runtime.trace import TRACES, to_chrome

                    tid = req.get("id")
                    if tid is None:
                        tr = TRACES.last()
                    else:
                        try:
                            tr = TRACES.get(int(tid))
                        except (TypeError, ValueError):
                            return {"ok": False,
                                    "error": "trace needs a numeric id"}
                    if tr is None:
                        return {"ok": False,
                                "error": f"no trace for statement {tid!r} "
                                         "(evicted from the ring, or "
                                         "tracing is disabled)"}
                    return {"ok": True, "trace": to_chrome(tr)}
                if op == "status":
                    # the server status frame: dispatch topology state
                    # (full / n-1 / degraded), FTS topology version, the
                    # reform/commit-path counter family, and the overload
                    # state (fresh evaluation: operators polling status
                    # must see current pressure, not the rate-limited
                    # statement-path sample)
                    from greengage_tpu.runtime.logger import counters as _c

                    _overload.CONTROLLER.evaluate(outer.db.settings,
                                                  force=True)
                    st = _cluster_status(outer.db)
                    st["counters"] = {
                        k: v for k, v in _c.snapshot().items()
                        if k.startswith(("mh_", "manifest_", "batch_",
                                         "server_", "connections_",
                                         "admission_", "brownout",
                                         "frames_", "standby_"))}
                    st["counters"].update({
                        k: v for k, v in _c.snapshot().items()
                        if k.startswith("ingest_")})
                    return {"ok": True, "cluster": st,
                            "pipeline": _pipeline_depths(outer.db),
                            "overload": _overload.CONTROLLER.snapshot(),
                            "ingest": outer.db.ingest.stream_status()}
                if op == "cancel":
                    try:
                        sid = int(req.get("id"))
                    except (TypeError, ValueError):
                        return {"ok": False,
                                "error": "cancel needs a numeric id"}
                    if REGISTRY.cancel(sid, "user"):
                        outer.db.log.info(
                            "cancel", f"statement {sid} cancelled by "
                            "operator request")
                        return {"ok": True}
                    return {"ok": False,
                            "error": f"no in-flight statement {sid}"}
                # streaming ingest plane (runtime/ingest.py): long-lived
                # micro-batch COPY sessions; AdmissionShed raised here is
                # mapped by _serve into the typed retryable 53300 frame
                if op == "stream_begin":
                    out = outer.db.ingest.stream_begin(
                        req.get("table"), req.get("stream"))
                    return {"ok": True, **out}
                if op == "stream_rows":
                    seq = req.get("seq")
                    # a missing seq must NOT default to 0: feed() treats
                    # seq <= acked_seq as a resume replay and acks it as
                    # a duplicate — silently dropping the frame's rows
                    if isinstance(seq, bool) or not isinstance(seq, int):
                        return {"ok": False,
                                "error": "stream_rows requires an integer"
                                         " 'seq' (batch sequence number)"}
                    out = outer.db.ingest.stream_rows(
                        req.get("stream"), req.get("columns") or {}, seq)
                    return {"ok": True, **out}
                if op == "stream_end":
                    out = outer.db.ingest.stream_end(req.get("stream"))
                    return {"ok": True, **out}
                return {"ok": False, "error": f"unknown op {op!r}"}

        class Server(socketserver.ThreadingUnixStreamServer):
            daemon_threads = True
            allow_reuse_address = True
            # a connect storm must reach the TYPED shed path, not the
            # kernel's tiny default backlog (refused connects can't be
            # told to back off); sheds are one frame + close, so a deep
            # accept queue drains in microseconds
            request_queue_size = 128

        self._server = Server(self.socket_path, Handler)
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="gg-server", daemon=True)
        self._thread.start()

        if self.host is not None and self.port is not None:
            class TcpHandler(Handler):
                REMOTE = True

            class TcpServer(socketserver.ThreadingTCPServer):
                daemon_threads = True
                allow_reuse_address = True
                request_queue_size = 128   # accept-then-shed, as above

            self._tcp_server = TcpServer((self.host, self.port), TcpHandler)
            self.port = self._tcp_server.server_address[1]  # resolve port 0
            self._tcp_thread = threading.Thread(
                target=self._tcp_server.serve_forever, name="gg-server-tcp",
                daemon=True)
            self._tcp_thread.start()

    def stop(self) -> None:
        """Graceful drain (docs/ROBUSTNESS.md "Overload protection"):

        1. flag draining and stop accepting (new connects shed typed);
        2. flag every in-flight statement ``shutdown`` via the interrupt
           registry and SHUT_RD the client sockets — idle readers wake
           with EOF immediately, in-flight statements die at their next
           cancellation point and still flush their typed error (writes
           stay open);
        3. join every handler thread, bounded by ``server_drain_s``;
        4. force-close straggler sockets and join once more — no daemon
           thread is left parked on a socket the process is abandoning
           (a thread still inside an XLA dispatch finishes its program
           and exits at the next cancellation point)."""
        drain_s = max(float(getattr(self.db.settings,
                                    "server_drain_s", 5.0)), 0.0)
        with self._conn_mu:
            self._draining = True
            conns = dict(self._conns)
            handlers = dict(self._handlers)
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None
        if self._tcp_server is not None:
            self._tcp_server.shutdown()
            self._tcp_server.server_close()
            self._tcp_server = None
        for ident, sock in conns.items():
            REGISTRY.cancel_thread(ident, "shutdown")
            try:
                sock.shutdown(socket.SHUT_RD)
            except OSError:
                pass
        deadline = time.monotonic() + drain_s
        for t in handlers.values():
            t.join(timeout=max(deadline - time.monotonic(), 0.0))
        leftover = [t for t in handlers.values() if t.is_alive()]
        if leftover:
            with self._conn_mu:
                socks = [self._conns[t.ident] for t in leftover
                         if t.ident in self._conns]
            for s in socks:
                try:
                    s.close()
                except OSError:
                    pass
            for t in leftover:
                t.join(timeout=1.0)
            still = sum(1 for t in leftover if t.is_alive())
            if still:
                self.db.log.log(
                    "WARNING", "overload",
                    f"drain deadline ({drain_s:g}s) expired with {still} "
                    "connection(s) still closing")
        # open ingest streams flush-or-abort once their handlers are gone:
        # nothing buffered is silently abandoned, and the plane stays up
        # for Database.close() to stop for real
        try:
            self.db.ingest.drain_all()
        except Exception:
            pass
        # _draining stays set: a straggler handler past the deadline must
        # not serve another statement on a server that no longer accepts
        if os.path.exists(self.socket_path):
            os.remove(self.socket_path)


class SqlClient:
    """Tiny client for the line protocol (the psql/libpq stand-in).
    Local: SqlClient(path). Remote: SqlClient(host=..., port=...,
    user=..., password=...) — challenge-response, password never sent."""

    def __init__(self, socket_path: str | None = None, *,
                 host: str | None = None, port: int | None = None,
                 user: str = "", password: str = ""):
        if socket_path is not None:
            self._sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            self._sock.connect(socket_path)
            self._f = self._sock.makefile("rwb")
        else:
            from greengage_tpu.runtime import auth

            self._sock = socket.create_connection((host, port))
            self._f = self._sock.makefile("rwb")
            self._f.write((json.dumps({"user": user}) + "\n").encode())
            self._f.flush()
            ch = json.loads(self._f.readline())
            if not ch.get("ok", True) and ch.get("code"):
                # typed connection shed (too_many_connections /
                # shutting_down) arrived instead of the auth challenge
                self._sock.close()
                raise ConnectionRefusedError(ch.get("error", "shed"))
            proof = auth.prove(ch["salt"], ch["nonce"], password)
            self._f.write((json.dumps({"proof": proof}) + "\n").encode())
            self._f.flush()
            resp = json.loads(self._f.readline())
            if not resp.get("ok"):
                self._sock.close()
                raise PermissionError(resp.get("error", "auth failed"))

    def sql(self, text: str):
        self._f.write((json.dumps({"sql": text}) + "\n").encode())
        self._f.flush()
        resp = json.loads(self._f.readline())
        if not resp.get("ok"):
            raise RuntimeError(resp.get("error", "server error"))
        return resp

    def op(self, payload: dict) -> dict:
        """Send a control frame (ps/cancel) and return the raw response
        (not raising on ok=false — 'no such statement' is an answer)."""
        self._f.write((json.dumps(payload) + "\n").encode())
        self._f.flush()
        return json.loads(self._f.readline())

    def close(self):
        self._f.close()
        self._sock.close()
