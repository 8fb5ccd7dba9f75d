"""Multi-host runtime — the interconnect/dispatch fabric across hosts.

Reference parity: the reference spans hosts with libpq dispatch (control
plane) + UDPIFC/ic-proxy (data plane, src/backend/cdb/motion/ic_udpifc.c,
README.ic-proxy.md). The TPU-native translation:

  data plane   = XLA collectives over the GLOBAL device mesh
                 (jax.distributed: every process contributes its local
                 chips; all_to_all/all_gather ride ICI/DCN)
  control plane = a slim TCP statement channel (the libpq 'M'-message
                 role): the coordinator broadcasts each SQL statement,
                 every process plans/compiles the SAME program from the
                 shared catalog (multi-controller SPMD), workers stage
                 only their LOCAL segments' storage, and the jitted
                 program's collectives synchronize execution.

Lockstep invariants (why this is deterministic):
  * all processes see the same cluster directory (shared/replicated fs);
    workers refresh catalog+manifest before each statement,
  * binder/planner are deterministic, so every process compiles an
    identical HLO and the collectives rendezvous,
  * overflow flags and metrics are device-reduced (pmax/psum over the
    mesh) and replicated, so every process takes the same capacity-retry
    decision without any extra control traffic,
  * only the coordinator performs writes (manifest/catalog/dictionaries);
    workers run the device part of DML's internal scans and skip the
    publish.

Failure model (docs/ROBUSTNESS.md): every control-channel read — the
startup accept, readiness/completion acks, the worker's statement wait —
is bounded by a deadline from config.py (mh_connect_deadline,
mh_ready_deadline, mh_ack_deadline), so silence classifies as WorkerDied
instead of hanging the cluster; idle-time ping/pong heartbeats
(mh_heartbeat_interval) catch partitions between statements; and a
quiesced coordinator keeps its listener open so a recovered worker can
rejoin (hello/sync handshake) and mesh dispatch resumes — the ftsprobe
timeout + cdbgang re-formation roles.
"""

from __future__ import annotations

import json
import socket
import sys
import threading
from contextlib import contextmanager
from dataclasses import dataclass
import subprocess

from greengage_tpu.runtime import interrupt
from greengage_tpu.runtime.faultinject import FaultError, faults
from greengage_tpu.runtime.logger import counters
from greengage_tpu.runtime.retry import (Deadline, RetryPolicy,
                                         TRANSIENT_ERRORS)


@dataclass
class MultihostRuntime:
    process_id: int
    num_processes: int
    channel: object = None            # CoordinatorChannel | WorkerChannel
    local_segments: tuple = ()        # mesh positions of this process's devices

    @property
    def is_coordinator(self) -> bool:
        return self.process_id == 0


def init_multihost(coordinator: str, num_processes: int, process_id: int,
                   control_port: int,
                   connect_deadline: float | None = None,
                   distributed: bool = True) -> MultihostRuntime:
    """Join the distributed JAX runtime and the control channel. Must run
    BEFORE any devices are used.

    distributed=False joins ONLY the control channel (no jax.distributed
    global mesh): every process compiles and executes the lockstep program
    over its own full local mesh. That is the mode for replicated-device
    deployments and for CPU demo clusters — XLA's CPU backend has no
    cross-process collectives, and the coordination service force-kills
    surviving processes when a peer dies, which would defeat the gang
    recovery the control plane provides (docs/ROBUSTNESS.md)."""
    if distributed:
        import jax

        jax.distributed.initialize(coordinator, num_processes=num_processes,
                                   process_id=process_id)
    host = coordinator.rsplit(":", 1)[0]
    if process_id == 0:
        ch = CoordinatorChannel(control_port, num_processes - 1,
                                connect_deadline=connect_deadline)
    else:
        ch = WorkerChannel(host, control_port, process_id=process_id,
                           connect_deadline=connect_deadline)
    return MultihostRuntime(process_id, num_processes, ch)


def local_segment_positions() -> tuple:
    """Mesh positions (= segment ids) of this process's devices, assuming
    the mesh enumerates jax.devices() in order (parallel/mesh.py does)."""
    import jax

    all_devs = {id(d): i for i, d in enumerate(jax.devices())}
    return tuple(sorted(all_devs[id(d)] for d in jax.local_devices()))


# ---------------------------------------------------------------------------
# control channel: line-JSON over TCP
# ---------------------------------------------------------------------------

class WorkerDied(ConnectionError):
    """A worker's control connection is gone OR silent past its deadline
    (process death / network partition / hung process): the statement
    channel cannot reach the full gang. ``process_id`` carries the peer
    the failure was observed on (None when unattributable) so mesh
    re-formation can name the lost worker."""

    def __init__(self, msg: str, process_id: int | None = None):
        super().__init__(msg)
        self.process_id = process_id


class CoordinatorLost(ConnectionError):
    """The worker's control connection to the coordinator dropped WITHOUT
    a clean 'stop' frame — coordinator death or a gang re-formation, never
    a normal shutdown."""


def _limit(settings, name_or_value) -> float:
    """Resolve a deadline: a literal number, or a config.py setting name —
    falling back to the Settings dataclass DEFAULT (its class attribute)
    when no Settings object is attached yet (the channel exists before the
    Database that owns the live values)."""
    if isinstance(name_or_value, (int, float)):
        return float(name_or_value)
    v = getattr(settings, name_or_value, None) if settings is not None else None
    if v is None:
        from greengage_tpu.config import Settings

        v = getattr(Settings, name_or_value)
    return float(v)


class _Peer:
    """One accepted worker connection (socket kept for per-read timeouts)."""

    __slots__ = ("sock", "f", "process_id")

    def __init__(self, sock, f, process_id):
        self.sock = sock
        self.f = f
        self.process_id = process_id

    def close(self):
        for obj in (self.f, self.sock):
            try:
                obj.close()
            except Exception:
                pass


class CoordinatorChannel:
    """Accepts every worker once, then broadcasts statements and collects
    acks (the CdbDispatchCommand/checkDispatchResult roles).

    Locking: one re-entrant lock serializes whole EXCHANGES (send .. acks)
    against the heartbeat thread; hold it via the ``exchange()`` context
    manager. send/collect also take it internally (re-entrant), so a
    failed send can never leave the lock held across methods — close()
    always completes.
    """

    def __init__(self, port: int, expected_workers: int, settings=None,
                 connect_deadline: float | None = None):
        self.settings = settings
        self.hb_failure: str | None = None   # set by the heartbeat thread
        self._lock = threading.RLock()
        self._workers: list[_Peer] = []
        self._pending: dict[int, _Peer] = {}  # rejoin handshakes by process id
        self._expected = expected_workers
        self._quiesced = False
        self._closed = False
        self._hb_thread: threading.Thread | None = None
        self._hb_stop = threading.Event()
        self._rejoin_thread: threading.Thread | None = None
        self._rejoin_stop = threading.Event()
        self._rejoin_ready = threading.Event()
        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind(("0.0.0.0", port))
        self._srv.listen(max(expected_workers, 1))
        # bounded gang assembly (gp_segment_connect_timeout): a worker that
        # never launches must fail startup with a count, not hang accept()
        dl = Deadline(_limit(settings, connect_deadline
                             if connect_deadline is not None
                             else "mh_connect_deadline"))
        try:
            for _ in range(expected_workers):
                try:
                    self._srv.settimeout(dl.remaining(minimum=0.001))
                    # gang assembly at Database init: no statement exists
                    # yet; bounded by mh_connect_deadline
                    conn, _ = self._srv.accept()   # gg:ok(interrupts)
                    peer = self._handshake(conn, dl)
                except (socket.timeout, TimeoutError):
                    raise WorkerDied(
                        f"only {len(self._workers)} of {expected_workers} "
                        f"workers joined within the "
                        f"{dl.seconds:.0f}s mh_connect_deadline")
                self._workers.append(peer)
        except BaseException:
            for p in self._workers:
                p.close()
            self._srv.close()
            raise
        self._srv.settimeout(None)

    def _handshake(self, conn, dl: Deadline) -> _Peer:
        """Read the worker's hello frame (identifies its process id; a
        connection that never says hello counts against the deadline)."""
        conn.settimeout(dl.remaining(minimum=0.001))
        f = conn.makefile("rwb")
        line = f.readline()
        if not line:
            raise WorkerDied("worker connection closed during handshake")
        try:
            msg = json.loads(line)
        except ValueError:
            raise WorkerDied(f"bad hello frame: {line[:80]!r}")
        conn.settimeout(None)
        return _Peer(conn, f, msg.get("process_id"))

    # ---- exchange discipline -------------------------------------------
    @contextmanager
    def exchange(self):
        """Scope one whole protocol exchange (send .. collect) so the
        heartbeat thread can never interleave frames with a statement."""
        with self._lock:
            yield self

    def send(self, msg: dict) -> None:
        with self._lock:
            if self._closed:
                raise WorkerDied("control channel is closed")
            if self.hb_failure:
                # a stale late ack from the failed heartbeat round could
                # otherwise be mis-read as this exchange's ack
                raise WorkerDied(
                    f"control channel marked dead by heartbeat: "
                    f"{self.hb_failure}")
            try:
                if faults.check("dispatch_send"):
                    return     # 'skip' drops the frame (partition analog)
            except FaultError as e:
                raise WorkerDied(str(e))
            line = (json.dumps(msg) + "\n").encode()
            pid = None
            try:
                for p in self._workers:
                    pid = p.process_id
                    p.sock.settimeout(
                        _limit(self.settings, "mh_ready_deadline"))
                    p.f.write(line)
                    p.f.flush()
            except (socket.timeout, TimeoutError) as e:
                raise WorkerDied(f"worker send timed out: {e}",
                                 process_id=pid)
            except OSError as e:
                raise WorkerDied(f"worker connection lost on send: {e}",
                                 process_id=pid)

    def collect_acks(self, deadline="mh_ack_deadline",
                     phase: str = "ack") -> list[dict]:
        acks = self.collect_raw(deadline, phase)
        errs = [a for a in acks if not a.get("ok")]
        if errs:
            raise RuntimeError(f"worker error: {errs[0].get('error')}")
        return acks

    def collect_raw(self, deadline="mh_ack_deadline",
                    phase: str = "ack") -> list[dict]:
        """Collect one ack per worker WITHOUT raising on not-ok — for
        ops whose ack 'error' slot carries payload (exec/gpssh output).
        One deadline bounds the WHOLE round: a silent worker classifies
        as dead, never as an unbounded block."""
        with self._lock:
            limit = _limit(self.settings, deadline)
            dl = Deadline(limit)
            acks = []
            cancelled = None
            for p in self._workers:
                # per-worker read boundary = cancellation point, ONLY for
                # the statement phases whose callers handle the unwind —
                # a raise during set/sync/fault exchanges would strand
                # buffered acks for the next exchange to misread. A no-op
                # for the heartbeat thread (no registered statement).
                # Completion raises EARLY (the wait IS workers running
                # their program; the session degrades the gang, and the
                # quiesce clears any already-buffered acks). Readiness
                # DRAINS the round first — workers ack readiness
                # promptly, so finishing the reads is cheap and leaves
                # the ack stream clean for the session's 'skip' release.
                if phase == "completion":
                    interrupt.check_interrupts()
                elif phase == "readiness" and cancelled is None:
                    try:
                        interrupt.check_interrupts()
                    except Exception as e:
                        cancelled = e
                try:
                    p.sock.settimeout(dl.remaining(minimum=0.001))
                    line = p.f.readline()
                except (socket.timeout, TimeoutError):
                    raise WorkerDied(
                        f"{phase} ack from worker {p.process_id} timed out "
                        f"after {limit:.1f}s — hung or partitioned",
                        process_id=p.process_id)
                except OSError as e:
                    raise WorkerDied(f"worker connection lost: {e}",
                                     process_id=p.process_id)
                if not line:
                    raise WorkerDied(
                        f"worker {p.process_id} connection closed (EOF) — "
                        "the process died mid-statement",
                        process_id=p.process_id)
                try:
                    acks.append(json.loads(line))
                except ValueError as e:
                    raise WorkerDied(f"garbled ack frame: {e}",
                                     process_id=p.process_id)
            if cancelled is not None:
                raise cancelled   # after the drain: no stale acks remain
            return acks

    def broadcast(self, msg: dict, deadline="mh_ack_deadline",
                  phase: str = "ack") -> list[dict]:
        """Send to all workers and wait for every ack, as one exchange."""
        with self.exchange():
            self.send(msg)
            return self.collect_acks(deadline, phase)

    # ---- heartbeats (idle-time liveness, FTS-probe cadence) ------------
    def start_heartbeat(self) -> None:
        """Ping/pong between statements. A beat is skipped while an
        exchange holds the lock (an in-flight statement IS liveness
        traffic). On failure the channel marks itself dead — the next
        statement degrades instead of dispatching into a black hole."""
        if _limit(self.settings, "mh_heartbeat_interval") <= 0:
            return
        if self._hb_thread is not None and self._hb_thread.is_alive():
            return
        self._hb_stop = threading.Event()

        def loop():
            while True:
                interval = _limit(self.settings, "mh_heartbeat_interval")
                if interval <= 0:
                    return     # '0 disables' applies to a LIVE SET too —
                               # wait(0) would turn this into a busy loop
                # heartbeat daemon thread: never a statement thread
                if self._hb_stop.wait(interval):   # gg:ok(interrupts)
                    return
                if self._quiesced or self._closed or self.hb_failure:
                    return
                if not self._lock.acquire(blocking=False):
                    continue       # statement in flight = alive
                try:
                    if self._quiesced or self._closed:
                        return
                    try:
                        self.send({"op": "ping"})
                        self.collect_acks(
                            deadline=max(_limit(self.settings,
                                                "mh_heartbeat_interval"),
                                         1.0),
                            phase="heartbeat")
                    except (WorkerDied, RuntimeError, OSError) as e:
                        if not self._closed:
                            self.hb_failure = str(e)
                        return
                finally:
                    self._lock.release()

        self._hb_thread = threading.Thread(target=loop, name="mh-heartbeat",
                                           daemon=True)
        self._hb_thread.start()

    def _stop_heartbeat(self) -> None:
        self._hb_stop.set()
        t = self._hb_thread
        if t is not None and t.is_alive() and t is not threading.current_thread():
            # control-plane teardown: the loop exits on _hb_stop within
            # one heartbeat tick and the join is hard-bounded
            t.join(timeout=5)   # gg:ok(interrupts)
        self._hb_thread = None

    # ---- quiesce + rejoin (gang re-formation, cdbgang recreation) ------
    def quiesce(self) -> None:
        """Tear down worker connections but KEEP the listener: a worker
        that wakes from a hang (or is restarted) can reconnect, and the
        session can re-form the gang (docs/ROBUSTNESS.md)."""
        if self._quiesced or self._closed:
            return
        self._quiesced = True
        self._stop_heartbeat()
        self._stop_accept_loop()   # a partial gang keeps one running
        with self._lock:
            for p in self._workers:
                p.close()
            self._workers = []
        self._rejoin_stop = threading.Event()
        self._rejoin_ready.clear()

        def accept_loop():
            while not self._rejoin_stop.is_set():
                try:
                    self._srv.settimeout(0.2)
                    # rejoin accept thread (quiesce keeps the listener
                    # open for redialing workers): not a statement thread
                    conn, _ = self._srv.accept()   # gg:ok(interrupts)
                except (socket.timeout, TimeoutError):
                    continue
                except OSError:
                    return           # listener closed: channel shut down
                try:
                    dl = Deadline(5.0)
                    peer = self._handshake(conn, dl)
                except Exception:
                    try:
                        conn.close()
                    except Exception:
                        pass
                    continue
                with self._lock:
                    old = self._pending.pop(peer.process_id, None)
                    if old is not None:
                        old.close()   # a worker re-dialing replaces itself
                    self._pending[peer.process_id] = peer
                    # ready = the missing complement has reconnected (the
                    # whole gang when quiesced; the dead worker when a
                    # partial N-1 gang is serving)
                    if len(self._pending) >= self._expected - len(self._workers):
                        self._rejoin_ready.set()

        self._rejoin_thread = threading.Thread(
            target=accept_loop, name="mh-rejoin-accept", daemon=True)
        self._rejoin_thread.start()

    def rejoin_ready(self) -> bool:
        """True once every MISSING worker has reconnected and said hello
        (the full gang after a quiesce; the dead member while an N-1
        partial gang serves)."""
        return self._rejoin_ready.is_set()

    # ---- partial gangs (N-1 mesh re-formation) -------------------------
    def pending_count(self) -> int:
        with self._lock:
            return len(self._pending)

    def active_ids(self) -> list:
        with self._lock:
            return sorted((p.process_id for p in self._workers),
                          key=lambda x: (x is None, x))

    def is_partial(self) -> bool:
        with self._lock:
            return len(self._workers) < self._expected

    @property
    def expected_workers(self) -> int:
        return self._expected

    def adopt_pending(self) -> int:
        """Fold every reconnected worker into the serving gang — the
        re-bind step of mesh re-formation. Works from quiesced (adopt the
        survivors into an N-1 gang) and from a partial gang (the dead
        member rejoined: restore full strength). The rejoin accept loop
        stays up while the gang is still short so a late rejoiner is never
        locked out; it stops once the gang is whole. Returns the number of
        workers adopted."""
        with self._lock:
            adopted = 0
            for pid in sorted(self._pending, key=lambda x: (x is None, x)):
                peer = self._pending[pid]
                stale = [p for p in self._workers if p.process_id == pid]
                for p in stale:
                    p.close()
                    self._workers.remove(p)
                self._workers.append(peer)
                adopted += 1
            self._pending = {}
            self._workers.sort(key=lambda p: (p.process_id is None,
                                              p.process_id))
            self._quiesced = False
            self.hb_failure = None
            self._rejoin_ready.clear()
            full = len(self._workers) >= self._expected
        if full:
            self._stop_accept_loop()
        return adopted

    def _stop_accept_loop(self) -> None:
        self._rejoin_stop.set()
        t = self._rejoin_thread
        if t is not None and t.is_alive() \
                and t is not threading.current_thread():
            # gang-reformation teardown: the acceptor exits on
            # _rejoin_stop within one accept timeout, join hard-bounded
            t.join(timeout=2)   # gg:ok(interrupts)
        self._rejoin_thread = None

    def adopt_rejoined(self) -> None:
        """Swap the reconnected gang in; the caller then replays the
        sync handshake before clearing degraded mode."""
        self._stop_accept_loop()
        with self._lock:
            for p in self._workers:
                p.close()   # a full swap replaces any partial remnants
            self._workers = [self._pending[k]
                             for k in sorted(self._pending,
                                             key=lambda x: (x is None, x))]
            self._pending = {}
            self._quiesced = False
            self.hb_failure = None
            self._rejoin_ready.clear()

    def close(self):
        if self._closed:
            return
        self._stop_heartbeat()
        self._rejoin_stop.set()
        try:
            # best-effort clean stop so workers exit instead of rejoining
            with self._lock:
                if not self.hb_failure:
                    self.send({"op": "stop"})
        except Exception:
            pass
        self._closed = True
        with self._lock:
            for p in self._workers:
                p.close()
            self._workers = []
            for p in self._pending.values():
                p.close()
            self._pending = {}
        self._srv.close()


class WorkerChannel:
    def __init__(self, host: str, port: int, process_id: int | None = None,
                 settings=None, connect_deadline: float | None = None):
        self.host = host
        self.port = port
        self.process_id = process_id
        self.settings = settings
        self._connect_deadline = connect_deadline
        self._dial(rejoin=False)

    @staticmethod
    def parse_addrs(spec: str) -> list:
        """'host:port,host:port' -> [(host, port)], order preserved;
        malformed entries are dropped (a worker must never crash on a
        broadcast GUC value)."""
        out = []
        for part in (spec or "").split(","):
            part = part.strip()
            if not part:
                continue
            host, _, port_s = part.rpartition(":")
            try:
                hp = (host or "127.0.0.1", int(port_s))
            except ValueError:
                continue
            if hp not in out:
                out.append(hp)
        return out

    def candidate_addrs(self) -> list:
        """Ordered redial candidates: the CURRENT coordinator address
        first (gang re-formation rejoins the same kept listener), then
        every mh_coordinator_addrs entry in its declared order — the
        standby listener(s) a promoted coordinator answers on."""
        cands = [(self.host, self.port)]
        spec = (getattr(self.settings, "mh_coordinator_addrs", "")
                if self.settings is not None else "")
        for hp in self.parse_addrs(spec):
            if hp not in cands:
                cands.append(hp)
        return cands

    def _dial(self, rejoin: bool, host: str | None = None,
              port: int | None = None, limit: float | None = None) -> None:
        host = self.host if host is None else host
        port = self.port if port is None else port
        if limit is None:
            limit = _limit(self.settings,
                           self._connect_deadline
                           if self._connect_deadline is not None
                           else "mh_connect_deadline")
        # at STARTUP a refused connect means the coordinator's listener is
        # not up yet — retry. At REJOIN the listener predates us (quiesce
        # keeps it open), so refused means the coordinator process itself
        # is gone: give up immediately instead of burning the deadline.
        retryable = ((TimeoutError, socket.timeout, InterruptedError,
                      ConnectionResetError, ConnectionAbortedError)
                     if rejoin else TRANSIENT_ERRORS)
        pol = RetryPolicy(deadline_s=limit, base_s=0.1, cap_s=2.0,
                          retryable=retryable)
        try:
            self._sock = pol.call(lambda: socket.create_connection(
                (host, port), timeout=min(10.0, limit)))
        except OSError as e:
            raise ConnectionError(
                f"cannot reach coordinator at {host}:{port} within "
                f"{limit:.0f}s mh_connect_deadline: {e}")
        self._sock.settimeout(None)
        self._f = self._sock.makefile("rwb")
        self._f.write((json.dumps(
            {"op": "hello", "process_id": self.process_id,
             "rejoin": rejoin}) + "\n").encode())
        self._f.flush()

    def recv(self, idle_timeout: float | None = None) -> dict:
        """Next control frame. EOF and silence are NOT a clean stop: they
        raise CoordinatorLost so the worker can log the loss and attempt a
        rejoin, instead of exiting as if shut down."""
        try:
            self._sock.settimeout(idle_timeout)
            line = self._f.readline()
        except (socket.timeout, TimeoutError):
            raise CoordinatorLost(
                f"no control traffic for {idle_timeout:.0f}s "
                "(heartbeats stopped — coordinator hung or partitioned)")
        except OSError as e:
            raise CoordinatorLost(f"control connection error: {e}")
        if not line:
            raise CoordinatorLost(
                "control connection closed without a stop frame — the "
                "coordinator died or re-formed the gang")
        try:
            return json.loads(line)
        except ValueError as e:
            raise CoordinatorLost(f"garbled control frame: {e}")

    def ack(self, ok: bool = True, error: str | None = None, **extra):
        payload = {"ok": ok, "error": error}
        payload.update(extra)
        self._f.write((json.dumps(payload) + "\n").encode())
        self._f.flush()

    def reconnect(self) -> bool:
        """Bounded re-dial + hello after a lost coordinator connection
        (the gang-rejoin dial), walking the ordered candidate list: the
        current address first (gang re-formation), then each
        mh_coordinator_addrs entry — landing on a DIFFERENT address is a
        re-home to a promoted standby (mh_rehome_total). False once
        every candidate has burned its share of mh_connect_deadline:
        all addresses dead."""
        self.close()
        cands = self.candidate_addrs()
        limit = _limit(self.settings,
                       self._connect_deadline
                       if self._connect_deadline is not None
                       else "mh_connect_deadline")
        per = max(0.5, limit / max(1, len(cands)))
        for host, port in cands:
            try:
                self._dial(rejoin=True, host=host, port=port, limit=per)
            except (ConnectionError, OSError):
                continue
            if (host, port) != (self.host, self.port):
                counters.inc("mh_rehome_total")
                print(f"worker {self.process_id}: re-homed to promoted "
                      f"coordinator {host}:{port}",
                      file=sys.stderr, flush=True)
                self.host, self.port = host, port
            return True
        return False

    def close(self):
        for obj in (getattr(self, "_f", None), getattr(self, "_sock", None)):
            try:
                obj.close()
            except Exception:
                pass


# ---------------------------------------------------------------------------
# worker loop
# ---------------------------------------------------------------------------

def worker_loop(db) -> None:
    """Follow the coordinator: execute each statement's DEVICE work in
    lockstep (the exec_mpp_query role, postgres.c:1057). Writes are the
    coordinator's job; the shared-directory refresh picks them up.

    Mesh statements arrive as a TWO-PHASE exchange: the worker first
    refreshes, re-plans, and acks readiness — verifying the coordinator's
    plan hash when one is attached, so a nondeterminism bug fails the
    statement on the channel instead of desyncing the collectives — and
    only enters the mesh program after an explicit 'go'. The readiness
    ack doubles as the liveness probe that keeps a dead worker from
    hanging the coordinator inside a collective.

    A lost coordinator connection (EOF without a stop frame, or silence
    past mh_ack_deadline while heartbeats are on) is LOGGED and answered
    with one bounded reconnect attempt — the worker half of gang
    recovery; only a clean 'stop' frame is a silent exit."""
    ch = db.multihost.channel
    ch.settings = db.settings
    while True:
        try:
            if not _serve_one(db, ch):
                return
        except (CoordinatorLost, OSError) as e:
            # a crashed coordinator must be VISIBLE, not a silent exit
            print(f"worker {db.multihost.process_id}: coordinator "
                  f"connection lost: {e}; attempting rejoin",
                  file=sys.stderr, flush=True)
            if not ch.reconnect():
                addrs = ", ".join(f"{h}:{p}"
                                  for h, p in ch.candidate_addrs())
                print(f"worker {db.multihost.process_id}: no coordinator "
                      f"reachable at [{addrs}] within "
                      "mh_connect_deadline — exiting",
                      file=sys.stderr, flush=True)
                return
            print(f"worker {db.multihost.process_id}: reconnected; "
                  "awaiting gang re-sync", file=sys.stderr, flush=True)


def _worker_idle_timeout(db) -> float | None:
    """With heartbeats on, total silence past the completion-ack bound
    means the coordinator is gone (pings would have arrived); without
    heartbeats the worker waits indefinitely for work."""
    if db.settings.mh_heartbeat_interval <= 0:
        return None
    return max(float(db.settings.mh_ack_deadline),
               10.0 * float(db.settings.mh_heartbeat_interval))


def _hbm_watermark(db) -> int:
    """Peak device bytes this process has observed, shipped in completion
    acks so the coordinator can drive ONE cluster-wide runaway verdict
    from the gang's aggregated watermarks. The mh_hbm_watermark fault
    point ('skip' type) substitutes a synthetic over-limit value so the
    gang test forces a verdict without a real multi-GB allocation."""
    if faults.check("mh_hbm_watermark"):
        return 1 << 40
    from greengage_tpu.runtime import memaccount

    st = memaccount.device_memory_stats()
    if st is None:
        return 0
    return int(st.get("peak_bytes_in_use", 0) or 0)


def _serve_one(db, ch) -> bool:
    """Handle one control frame; False = clean stop."""
    # worker process main loop: no statement registry on this side (the
    # coordinator cancels by quiescing/stopping the exchange)
    msg = ch.recv(_worker_idle_timeout(db))   # gg:ok(interrupts)
    op = msg.get("op")
    if op == "stop":
        return False
    if op == "ping":
        faults.check("heartbeat")   # sleep/suspend = hung worker analog
        ch.ack(True)
        return True
    if op == "fault":
        # gp_inject_fault dispatched to segments: arm/reset a named fault
        # point in THIS process so tests can force hangs deterministically
        try:
            if msg.get("reset"):
                faults.reset(msg.get("name"))
            else:
                faults.inject(msg["name"], msg.get("type", "error"),
                              segment=msg.get("segment"),
                              occurrences=int(msg.get("occurrences", 1)),
                              sleep_s=float(msg.get("sleep_s", 0.1)),
                              start_after=int(msg.get("start_after", 0)))
            ch.ack(True)
        except Exception as e:
            ch.ack(False, f"{type(e).__name__}: {e}")
        return True
    if op == "sync":
        # gang-rejoin replay: adopt the coordinator's committed catalog
        # and live settings, then report the topology version we see —
        # the coordinator verifies it against its own (FTS promotions
        # during the degraded window must be visible here)
        try:
            db.refresh()
            for k, v in (msg.get("settings") or {}).items():
                if not k.startswith("_"):
                    db.settings.set(k, v)
            ch.ack(True, topology_version=db.catalog.segments.version)
        except Exception as e:
            ch.ack(False, f"{type(e).__name__}: {e}")
        return True
    if op == "set":
        try:
            # mesh-steering settings stay in lockstep (spill passes,
            # retry tiers) — applied singly, never as batch re-parse
            db.settings.set(msg["name"], msg["value"])
            ch.ack(True)
        except Exception as e:
            ch.ack(False, f"{type(e).__name__}: {e}")
        return True
    if op == "exec":
        # gpssh role: run a shell command on every worker host over
        # the control plane; the ack's error slot carries the output

        try:
            out = subprocess.run(
                msg["cmd"], shell=True, capture_output=True,
                timeout=float(msg.get("timeout", 60)))
            ch.ack(out.returncode == 0,
                   (out.stdout + out.stderr).decode(
                       errors="replace")[-2000:])
        except Exception as e:
            ch.ack(False, f"{type(e).__name__}: {e}")
        return True
    if op == "runaway":
        # cluster-wide runaway verdict: the coordinator aggregated the
        # gang's HBM watermarks past the red zone and broadcast the kill.
        # Cancel whatever runs here through the interrupt registry (same
        # flag the single-host cleaner trips) and count it.
        counters.inc("statements_cancelled_runaway")
        interrupt.REGISTRY.cancel_all(
            "runaway", msg.get("reason")
            or "canceled by the runaway cleaner (cluster verdict)")
        ch.ack(True)
        return True
    if op == "sql_batch":
        # one batched serving window (exec/batchserve.py): same two-phase
        # contract as a classic statement — verify the window's plan hash
        # (every member shares the shape; the first member's hash stands
        # for the window), ack readiness, park for 'go', then run the
        # batched program CONCURRENTLY with the coordinator's dispatch
        faults.check("worker_ack")
        sqls = msg.get("sqls") or []
        try:
            db.refresh()
            # adopt the coordinator's applied calibration BEFORE planning:
            # plan hashes must match, and est_rows feed the plan text
            db.feedback.adopt(msg.get("fb"))
            want = msg.get("plan_hash")
            if want and sqls:
                got = db.plan_hash(sqls[0])
                if got != want:
                    raise RuntimeError(
                        f"plan-hash mismatch: coordinator {want} vs "
                        f"worker {got} — nondeterministic planning would "
                        "desync the batched collectives")
        except FaultError:
            raise
        except Exception as e:
            ch.ack(False, f"{type(e).__name__}: {e}")
            return True
        ch.ack(True)
        nxt = ch.recv(_worker_idle_timeout(db))   # gg:ok(interrupts)
        if nxt.get("op") == "stop":
            return False
        if nxt.get("op") != "go":
            return True        # coordinator skipped the window
        from greengage_tpu.runtime.trace import TRACES

        tr, _ = TRACES.enter(
            None, sqls[0] if sqls else "batch",
            enabled=bool(getattr(db.settings, "trace_enabled", True)))
        try:
            db.worker_sql_batch(sqls)
        except Exception as e:
            # incl. BatchFallback: the coordinator maps a not-ok
            # completion ack to its own fallback, and the members'
            # serial re-runs arrive as classic sql ops
            TRACES.exit(tr)
            ch.ack(False, f"{type(e).__name__}: {e}")
            return True
        spans = tr.export(limit=512) if tr is not None else None
        TRACES.exit(tr)
        faults.check("worker_ack")
        ch.ack(True, spans=spans, process_id=db.multihost.process_id,
               hbm=_hbm_watermark(db))
        return True
    if op != "sql":
        return True
    # phase 1: refresh + plan + verify, ack readiness. A FaultError from
    # the worker_ack point propagates (= injected worker death at the ack
    # site); its sleep/suspend types model the hung-not-dead worker.
    faults.check("worker_ack")
    try:
        db.refresh()
        # adopt the coordinator's applied calibration BEFORE the plan-hash
        # check: corrected est_rows appear in describe(), so both sides
        # must plan from identical scales (JSON floats round-trip exactly)
        db.feedback.adopt(msg.get("fb"))
        want = msg.get("plan_hash")
        if want:
            # plan_hash raises if this worker cannot re-plan — that
            # too must fail the readiness ack, not surface later
            # inside a half-entered collective
            got = db.plan_hash(msg["sql"])
            if got != want:
                raise RuntimeError(
                    f"plan-hash mismatch: coordinator {want} vs "
                    f"worker {got} — nondeterministic planning would "
                    "desync the mesh collectives")
        ch.ack(True)
    except FaultError:
        raise
    except Exception as e:
        ch.ack(False, f"{type(e).__name__}: {e}")
        return True
    nxt = ch.recv(_worker_idle_timeout(db))   # gg:ok(interrupts)
    if nxt.get("op") == "stop":
        return False
    if nxt.get("op") != "go":
        return True            # coordinator skipped the statement
    # phase 2: the mesh program (collectives rendezvous with the
    # coordinator's concurrent execution). The worker traces its side
    # (runtime/trace.py) and ships the span list in the completion ack so
    # the coordinator can graft it under its dispatch span — one trace
    # for the whole cluster's statement.
    from greengage_tpu.runtime.trace import TRACES

    tr, _ = TRACES.enter(
        None, msg["sql"],
        enabled=bool(getattr(db.settings, "trace_enabled", True)))
    # record the spill pass/bucket schedule this side actually runs: it
    # ships in the completion ack and the coordinator asserts it matches
    # its own (exec/session._mh_spill_parity — lockstep verification)
    db.executor.spill_schedule.begin()
    try:
        db.worker_sql(msg["sql"])
    except Exception as e:
        TRACES.exit(tr)
        ch.ack(False, f"{type(e).__name__}: {e}")
        return True
    # bounded export: one control-channel line carries the ack, and a
    # pathological pass count must not balloon it
    spans = tr.export(limit=512) if tr is not None else None
    TRACES.exit(tr)
    faults.check("worker_ack")
    ch.ack(True, spans=spans, process_id=db.multihost.process_id,
           spill_schedule=db.executor.spill_schedule.collect(),
           hbm=_hbm_watermark(db))
    return True
