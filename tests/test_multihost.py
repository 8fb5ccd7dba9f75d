"""Multi-host cluster: 2 processes x 4 virtual CPU devices = 8-segment mesh
spanning processes — VERDICT r1 item #6 (jax.distributed data plane +
statement-channel control plane; ic-proxy/libpq dispatch analog).

pytest's own process already owns a JAX backend, so both the coordinator
and the worker run as SUBPROCESSES sharing a cluster directory; the test
asserts the coordinator's results.
"""

import json
import os
import socket
import subprocess
import sys

import pytest

COORD_SCRIPT = r"""
import json, os, sys
port, cport, path = sys.argv[1], int(sys.argv[2]), sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.environ["GGTPU_REPO"])
from greengage_tpu.parallel.multihost import init_multihost
mh = init_multihost(f"127.0.0.1:{port}", 2, 0, cport, distributed=False)
import greengage_tpu
db = greengage_tpu.connect(path, multihost=mh)
out = {}
db.sql("create table f (k bigint, g int, v int) distributed by (k)")
db.sql("insert into f values " + ",".join(
    f"({i}, {i % 13}, {i % 7})" for i in range(4000)))
db.sql("create table d (g int, name text) distributed by (g)")
db.sql("insert into d values " + ",".join(f"({i}, 'g{i}')" for i in range(13)))
db.sql("analyze")
r = db.sql("select count(*), sum(v) from f")
out["scalar"] = [int(x) for x in r.rows()[0]]
# two-phase grouped agg: group key != distribution key => redistribute
r = db.sql("select g, count(*), sum(v) from f group by g order by g")
out["grouped"] = [[int(x) for x in row] for row in r.rows()]
out["grouped_segments"] = r.stats["segments"]
# cross-process join + broadcast of the dimension
r = db.sql("select d.name, count(*) from f join d on f.g = d.g "
           "group by d.name order by d.name limit 3")
out["join"] = [[row[0], int(row[1])] for row in r.rows()]
# DML with an internal mesh scan, then read back
db.sql("update f set v = 99 where k < 10")
r = db.sql("select count(*) from f where v = 99")
out["updated"] = int(r.rows()[0][0])
db.sql("delete from f where g = 12")
r = db.sql("select count(*) from f")
out["after_delete"] = int(r.rows()[0][0])
# parallel retrieve cursor: DECLARE broadcasts (workers join the
# collectives), RETRIEVE drains endpoints coordinator-side
db.sql("declare pc parallel retrieve cursor for select k from f where v = 99")
out["cursor_rows"] = sum(
    len(db.sql(f"retrieve all from endpoint {k} of pc").rows())
    for k in range(db.numsegments))
db.sql("close pc")
# spill under multihost: a big load (shared storage; host-side, no
# lockstep needed), then a grouped agg past a tight vmem limit — the SET
# broadcasts so both processes take the same pass-partitioned branch
import numpy as np
db.sql("create table f2 (k bigint, g int, v int) distributed by (k)")
n2 = 600_000
db.load_table("f2", {"k": np.arange(n2), "g": (np.arange(n2) % 13),
                     "v": (np.arange(n2) % 7)})
db.sql("analyze f2")
db.sql("set vmem_protect_limit_mb = 1")
r = db.sql("select g, count(*), sum(v) from f2 group by g order by g")
out["spilled"] = [[int(x) for x in row] for row in r.rows()]
out["spill_passes"] = int(r.stats.get("spill_passes", 0))
db.sql("set vmem_protect_limit_mb = 12288")
# round-5 analytic surface under lockstep: ROLLUP branches + the
# stat-agg moment expansion + percentile windows are deterministic
# rewrites, so both processes compile identical SPMD programs
r = db.sql("select g, count(*) c, grouping(g) lvl from f "
           "group by rollup(g) order by lvl, g")
out["rollup_total"] = [int(x) for x in r.rows()[-1][1:2]]
out["rollup_rows"] = len(r.rows())
r = db.sql("select stddev(v) from f")
out["stddev"] = round(float(r.rows()[0][0]), 9)
r = db.sql("select percentile_cont(0.5) within group (order by v) from f")
out["median"] = float(r.rows()[0][0])
# gpssh analog: run a command on every host over the control plane
ex = db.cluster_exec("echo host-$GGTPU_X; true")
out["exec_hosts"] = [e["ok"] for e in ex]
out["exec_n"] = len(ex)
ex2 = db.cluster_exec("exit 3")
out["exec_fail"] = [e["ok"] for e in ex2]
mh.channel.close()
print("RESULT:" + json.dumps(out), flush=True)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def test_two_process_cluster(tmp_path):
    port, cport = _free_port(), _free_port()
    path = str(tmp_path / "cluster")
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "GGTPU_REPO": os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "PYTHONPATH": os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    })
    # the worker writes to a file, not a pipe: nobody reads its output
    # until the coordinator is done, and with a warm compile cache XLA:CPU
    # logs two 1.8 KB lines a loaded executable — past the pipe's 64 KB the
    # worker would block in write() and the gang with it
    wlog = tmp_path / "worker.out"
    with open(wlog, "w") as wfile:
        worker = subprocess.Popen(
            [sys.executable, "-m", "greengage_tpu.mgmt.cli", "worker",
             "-d", path, "--coordinator", f"127.0.0.1:{port}",
             "--control-port", str(cport), "--num-processes", "2",
             "--process-id", "1", "--no-distributed"],
            env=env, stdout=wfile, stderr=subprocess.STDOUT, text=True)
    coord = subprocess.Popen(
        [sys.executable, "-c", COORD_SCRIPT, str(port), str(cport), path],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    try:
        cout, _ = coord.communicate(timeout=480)
        worker.wait(timeout=60)
        wout = wlog.read_text()
    except subprocess.TimeoutExpired:
        coord.kill()
        worker.kill()
        cout = coord.stdout.read() if coord.stdout else ""
        wout = wlog.read_text()
        raise AssertionError(
            f"multihost timeout\ncoordinator:\n{cout}\nworker:\n{wout}")
    assert coord.returncode == 0, f"coordinator:\n{cout}\nworker:\n{wout}"
    res = [ln for ln in cout.splitlines() if ln.startswith("RESULT:")]
    assert res, f"coordinator:\n{cout}\nworker:\n{wout}"
    out = json.loads(res[0][len("RESULT:"):])

    # oracle (rows 0..3999, g = i%13, v = i%7)
    rows = [(i, i % 13, i % 7) for i in range(4000)]
    assert out["scalar"] == [4000, sum(v for _, _, v in rows)]
    assert out["grouped_segments"] == 8
    want_grouped = {}
    for _, g, v in rows:
        c, s = want_grouped.get(g, (0, 0))
        want_grouped[g] = (c + 1, s + v)
    assert out["grouped"] == [[g, *want_grouped[g]] for g in sorted(want_grouped)]
    want_join = sorted((f"g{g}", want_grouped[g][0]) for g in want_grouped)[:3]
    assert out["join"] == [[n, c] for n, c in want_join]
    assert out["updated"] == 10 - sum(1 for i in range(10) if i % 7 == 99)
    n_g12 = sum(1 for i in range(4000) if i % 13 == 12)
    assert out["after_delete"] == 4000 - n_g12
    assert out["cursor_rows"] == 10   # the rows updated to v=99 (k<10)
    want_spill = {}
    for i in range(600_000):
        c, s = want_spill.get(i % 13, (0, 0))
        want_spill[i % 13] = (c + 1, s + i % 7)
    assert out["spilled"] == [[g, *want_spill[g]] for g in sorted(want_spill)]
    assert out["spill_passes"] >= 2, out["spill_passes"]
    assert out["exec_n"] == 2
    # the round-5 analytic rewrites under lockstep: compare against the
    # same data computed locally
    import numpy as np

    ks = np.arange(4000)
    alive = (ks % 13) != 12
    v = np.where(ks < 10, 99, ks % 7)[alive]
    assert out["rollup_total"] == [int(alive.sum())]
    assert out["rollup_rows"] == 12 + 1
    assert abs(out["stddev"] - float(np.std(v, ddof=1))) < 1e-6
    assert out["median"] == float(np.percentile(v, 50))
    assert out["exec_hosts"] == [True, True]
    assert out["exec_fail"] == [False, False]


# ---------------------------------------------------------------------------
# worker death: detection on the readiness round + degraded local service
# ---------------------------------------------------------------------------

COORD_DEATH_SCRIPT = r"""
import json, os, sys, time
port, cport, path, mark = sys.argv[1], int(sys.argv[2]), sys.argv[3], sys.argv[4]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.environ["GGTPU_REPO"])
from greengage_tpu.parallel.multihost import init_multihost
mh = init_multihost(f"127.0.0.1:{port}", 2, 0, cport, distributed=False)
import greengage_tpu
db = greengage_tpu.connect(path, multihost=mh)
out = {}
db.sql("create table f (k bigint, v int) distributed by (k)")
db.sql("insert into f values " + ",".join(f"({i}, {i % 7})" for i in range(2000)))
db.sql("analyze")
r = db.sql("select count(*), sum(v) from f")
out["pre"] = [int(x) for x in r.rows()[0]]
# this test pins the LEGACY degraded fallback (N-1 re-formation has its
# own tests): without the pin the coordinator would re-form and serve
db.sql("set mh_reform_enabled = off")
open(mark + ".phase1", "w").close()
while not os.path.exists(mark + ".killed"):
    time.sleep(0.05)
# the worker is gone: the readiness round must detect it BEFORE any
# collective, and the statement must still COMPLETE via the degraded
# single-process re-formation over the shared directory
r = db.sql("select count(*), sum(v) from f")
out["post"] = [int(x) for x in r.rows()[0]]
out["degraded"] = bool(db._mh_degraded)
r = db.sql("select count(*) from f where k < 10")
out["post2"] = int(r.rows()[0][0])
out["status_after"] = db.sql("delete from f where k < 100")
r = db.sql("select count(*) from f")
out["post3"] = int(r.rows()[0][0])
print("RESULT:" + json.dumps(out), flush=True)
# the degraded runtime's grpc teardown may error at interpreter exit
# (the dead peer can never complete its streams); results are already
# flushed, so exit without running teardown hooks
os._exit(0)
"""


def test_worker_death_detected_and_degraded_service(tmp_path):
    port, cport = _free_port(), _free_port()
    path = str(tmp_path / "cluster")
    mark = str(tmp_path / "mark")
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "GGTPU_REPO": os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "PYTHONPATH": os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    })
    worker = subprocess.Popen(
        [sys.executable, "-m", "greengage_tpu.mgmt.cli", "worker",
         "-d", path, "--coordinator", f"127.0.0.1:{port}",
         "--control-port", str(cport), "--num-processes", "2",
         "--process-id", "1", "--no-distributed"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    coord = subprocess.Popen(
        [sys.executable, "-c", COORD_DEATH_SCRIPT, str(port), str(cport),
         path, mark],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    import signal
    import time as _t
    try:
        deadline = _t.monotonic() + 300
        while not os.path.exists(mark + ".phase1"):
            assert _t.monotonic() < deadline, "coordinator never reached phase1"
            assert coord.poll() is None, coord.stdout.read()
            _t.sleep(0.05)
        os.kill(worker.pid, signal.SIGKILL)
        worker.wait(timeout=30)
        open(mark + ".killed", "w").close()
        cout, _ = coord.communicate(timeout=480)
    except subprocess.TimeoutExpired:
        coord.kill()
        raise AssertionError(
            f"coordinator hung after worker death:\n{coord.stdout.read()}")
    assert coord.returncode == 0, cout
    res = [ln for ln in cout.splitlines() if ln.startswith("RESULT:")]
    assert res, cout
    out = json.loads(res[0][len("RESULT:"):])
    want_sum = sum(i % 7 for i in range(2000))
    assert out["pre"] == [2000, want_sum]
    assert out["post"] == [2000, want_sum]     # completed AFTER the death
    assert out["degraded"] is True
    assert out["post2"] == 10
    assert out["status_after"] == "DELETE 100"  # degraded DML works too
    assert out["post3"] == 1900


def test_plan_hash_deterministic_across_sessions(devices8, tmp_path):
    import numpy as np

    import greengage_tpu
    path = str(tmp_path / "c")
    d1 = greengage_tpu.connect(path=path, numsegments=4)
    d1.sql("create table t (k int, g int, v int) distributed by (k)")
    d1.load_table("t", {"k": np.arange(1000), "g": np.arange(1000) % 7,
                        "v": np.arange(1000)})
    d1.sql("analyze")
    q = "select g, sum(v) from t group by g order by g"
    h1 = d1.plan_hash(q)
    d2 = greengage_tpu.connect(path=path, numsegments=4)
    h2 = d2.plan_hash(q)
    assert h1 is not None and h1 == h2
    assert d1.plan_hash("select 1") is None          # no FROM: host-side


# ---------------------------------------------------------------------------
# worker SIGKILL + cross-host mirrors: the gang RE-FORMS over the survivors
# (N-1 mesh — never the single-process degraded path) and serves every
# content from PROMOTED mirror trees on surviving roots; DML included
# (ftsprobe.c:968 / the tentpole acceptance matrix)
# ---------------------------------------------------------------------------

COORD_MIRROR_DEATH_SCRIPT = r"""
import glob, json, os, sys, time
port, cport, path, mark = sys.argv[1], int(sys.argv[2]), sys.argv[3], sys.argv[4]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.environ["GGTPU_REPO"])
from greengage_tpu.parallel.multihost import init_multihost
mh = init_multihost(f"127.0.0.1:{port}", 2, 0, cport, distributed=False)
import greengage_tpu
from greengage_tpu.runtime.logger import counters
db = greengage_tpu.connect(path, multihost=mh)
out = {}
r = db.sql("select count(*), sum(v) from f")
out["pre"] = [int(x) for x in r.rows()[0]]
reform0 = counters.get("mh_reform_total")
topo0 = counters.get("mh_topology_version")
open(mark + ".phase1", "w").close()
while not os.path.exists(mark + ".killed"):
    time.sleep(0.05)
# the dead worker's host took its data disk: contents 4..7 lose their
# primary trees; the re-formed topology must promote their mirrors
for content in (4, 5, 6, 7):
    for f in glob.glob(os.path.join(path, "data", "*", f"seg{content}", "*")):
        os.remove(f)
r = db.sql("select count(*), sum(v) from f")
out["post"] = [int(x) for x in r.rows()[0]]
out["degraded"] = bool(db._mh_degraded)
out["deg_stats"] = bool(getattr(r, "stats", {}).get("degraded"))
out["segments"] = r.stats.get("segments")
out["state"] = db.mh_state()["state"]
out["reform_delta"] = counters.get("mh_reform_total") - reform0
out["topo_bumped"] = counters.get("mh_topology_version") > topo0
out["promoted"] = sorted(
    c for c in range(8)
    if db.catalog.segments.acting_primary(c).preferred_role.value == "m")
# DML on the re-formed N-1 gang: manifest commits are coordinator-local,
# so writes flow without the dead worker
db.sql("delete from f where k < 100")
out["post_dml"] = int(db.sql("select count(*) from f").rows()[0][0])
print("RESULT:" + json.dumps(out), flush=True)
os._exit(0)
"""


def test_worker_death_promotes_cross_host_mirrors(tmp_path):
    import greengage_tpu
    from greengage_tpu.mgmt import cli

    port, cport = _free_port(), _free_port()
    path = str(tmp_path / "cluster")
    mark = str(tmp_path / "mark")
    # build the mirrored cluster with spread mirror roots up front
    # (width 8 = the 2-process x 4-device global mesh)
    d = greengage_tpu.connect(path, numsegments=8, mirrors=True)
    d.sql("create table f (k bigint, v int) distributed by (k)")
    d.sql("insert into f values " + ",".join(
        f"({i}, {i % 7})" for i in range(2000)))
    d.sql("analyze")
    d.close()
    cli.main(["mirrorroots", "-d", path, "--roots",
              f"{tmp_path / 'hostA'},{tmp_path / 'hostB'}"])

    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "GGTPU_REPO": os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "PYTHONPATH": os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    })
    worker = subprocess.Popen(
        [sys.executable, "-m", "greengage_tpu.mgmt.cli", "worker",
         "-d", path, "--coordinator", f"127.0.0.1:{port}",
         "--control-port", str(cport), "--num-processes", "2",
         "--process-id", "1", "--no-distributed"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    coord = subprocess.Popen(
        [sys.executable, "-c", COORD_MIRROR_DEATH_SCRIPT, str(port),
         str(cport), path, mark],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    import signal
    import time as _t
    try:
        deadline = _t.monotonic() + 300
        while not os.path.exists(mark + ".phase1"):
            assert _t.monotonic() < deadline, "coordinator never reached phase1"
            assert coord.poll() is None, coord.stdout.read()
            _t.sleep(0.05)
        os.kill(worker.pid, signal.SIGKILL)
        worker.wait(timeout=30)
        open(mark + ".killed", "w").close()
        cout, _ = coord.communicate(timeout=480)
    except subprocess.TimeoutExpired:
        coord.kill()
        raise AssertionError(
            f"coordinator hung after worker death:\n{coord.stdout.read()}")
    assert coord.returncode == 0, cout
    res = [ln for ln in cout.splitlines() if ln.startswith("RESULT:")]
    assert res, cout
    out = json.loads(res[0][len("RESULT:"):])
    want = [2000, sum(i % 7 for i in range(2000))]
    assert out["pre"] == want
    # the gang RE-FORMED over the survivors: never the single-process path
    assert out["degraded"] is False
    assert out["deg_stats"] is False
    assert out["state"] == "n-1"
    assert out["segments"] == 8           # full local mesh, not a subprocess
    assert out["reform_delta"] >= 1       # mh_reform_total counted it
    assert out["topo_bumped"] is True     # mh_topology_version advanced
    assert out["promoted"] == [4, 5, 6, 7]  # mirrors promoted for lost trees
    assert out["post"] == want            # served from mirror data
    assert out["post_dml"] == 1900        # DML commits on the N-1 gang


# ---------------------------------------------------------------------------
# deadline/heartbeat/rejoin layer (docs/ROBUSTNESS.md): channel-level tests
# run the REAL protocol objects in-process (pure TCP, no devices), so every
# phase is deterministic and fast — the isolation2 fts_errors.sql analog.
# ---------------------------------------------------------------------------

import threading
import time


def _channel_pair(n_workers=1, connect_deadline=10.0):
    """A real CoordinatorChannel + WorkerChannel(s) over loopback."""
    from greengage_tpu.parallel.multihost import (CoordinatorChannel,
                                                  WorkerChannel)

    port = _free_port()
    box = {}

    def serve():
        box["ch"] = CoordinatorChannel(port, n_workers,
                                       connect_deadline=connect_deadline)

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    workers = [WorkerChannel("127.0.0.1", port, process_id=i + 1,
                             connect_deadline=connect_deadline)
               for i in range(n_workers)]
    t.join(10)
    assert "ch" in box, "coordinator accept never completed"
    return box["ch"], workers


def test_accept_deadline_names_missing_workers():
    """A worker that never launches must fail startup with a joined-count,
    not hang accept() forever."""
    from greengage_tpu.parallel.multihost import CoordinatorChannel, WorkerDied

    t0 = time.monotonic()
    with pytest.raises(WorkerDied, match=r"0 of 2 workers joined"):
        CoordinatorChannel(_free_port(), 2, connect_deadline=0.4)
    assert time.monotonic() - t0 < 5.0


def test_silent_worker_classified_dead_within_deadline():
    """A connected-but-silent (hung) worker must classify as WorkerDied
    within the configured deadline on every ack phase."""
    from greengage_tpu.parallel.multihost import WorkerDied

    ch, (w,) = _channel_pair()
    try:
        t0 = time.monotonic()
        with pytest.raises(WorkerDied, match="timed out"):
            with ch.exchange():
                ch.send({"op": "sql", "sql": "select 1"})
                ch.collect_acks(deadline=0.4, phase="readiness")
        assert time.monotonic() - t0 < 5.0
    finally:
        ch.close()
        w.close()


def test_failed_send_releases_lock_and_close_does_not_deadlock():
    """Regression for the cross-method lock discipline: a send that fails
    (here via the dispatch_send fault point) must leave the per-exchange
    lock free so close() completes instead of deadlocking."""
    from greengage_tpu.parallel.multihost import WorkerDied
    from greengage_tpu.runtime.faultinject import faults

    ch, (w,) = _channel_pair()
    try:
        faults.inject("dispatch_send", "error", occurrences=1)
        with pytest.raises(WorkerDied, match="dispatch_send"):
            with ch.exchange():
                ch.send({"op": "ping"})
                ch.collect_acks(deadline=1.0)
    finally:
        faults.reset("dispatch_send")
    done = threading.Event()

    def closer():
        ch.close()
        done.set()

    threading.Thread(target=closer, daemon=True).start()
    assert done.wait(5.0), \
        "close() deadlocked on a lock left held by a failed send"
    w.close()


def test_worker_recv_distinguishes_stop_from_coordinator_death():
    """EOF without a stop frame is a CRASHED coordinator (CoordinatorLost,
    logged + rejoin attempt), never a silent clean exit."""
    from greengage_tpu.parallel.multihost import CoordinatorLost

    ch, (w,) = _channel_pair()
    with ch.exchange():
        ch.send({"op": "stop"})
    assert w.recv()["op"] == "stop"       # clean shutdown: a normal frame
    ch.close()
    w.close()

    ch2, (w2,) = _channel_pair()
    for p in ch2._workers:                # abrupt death: no stop frame
        p.close()
    with pytest.raises(CoordinatorLost, match="without a stop frame"):
        w2.recv()
    ch2.close()
    w2.close()


def test_heartbeat_detects_partition_and_marks_channel_dead():
    """Idle-time ping/pong: once a worker stops answering, hb_failure is
    recorded within ~one interval and every later send raises WorkerDied
    (the next statement degrades instead of dispatching)."""
    from greengage_tpu.config import Settings
    from greengage_tpu.parallel.multihost import WorkerDied

    ch, (w,) = _channel_pair()
    s = Settings()
    s.mh_heartbeat_interval = 0.1
    ch.settings = s
    answered = threading.Event()

    def pong_twice():
        for _ in range(2):
            if w.recv().get("op") == "ping":
                w.ack(True)
        answered.set()
        # then fall silent (partition analog) — keep the socket open

    t = threading.Thread(target=pong_twice, daemon=True)
    t.start()
    ch.start_heartbeat()
    assert answered.wait(5.0)
    end = time.monotonic() + 5.0
    while ch.hb_failure is None and time.monotonic() < end:
        time.sleep(0.02)
    assert ch.hb_failure is not None, \
        "silent worker never failed the heartbeat liveness check"
    with pytest.raises(WorkerDied, match="marked dead"):
        with ch.exchange():
            ch.send({"op": "sql", "sql": "select 1"})
    ch.close()
    w.close()


def test_quiesce_keeps_listener_and_gang_rejoins():
    """After quiesce (degrade) the listener stays open: a worker that
    reconnects + hellos is adopted and the channel serves exchanges
    again — the control-plane half of gang recovery."""
    from greengage_tpu.parallel.multihost import CoordinatorLost

    ch, (w,) = _channel_pair()
    ch.quiesce()
    with pytest.raises(CoordinatorLost):
        w.recv()                           # our connection was torn down
    assert w.reconnect(), "reconnect to the kept listener failed"
    end = time.monotonic() + 5.0
    while not ch.rejoin_ready() and time.monotonic() < end:
        time.sleep(0.02)
    assert ch.rejoin_ready(), "hello frame never completed the gang"
    ch.adopt_rejoined()

    def pong_once():
        if w.recv().get("op") == "ping":
            w.ack(True, topology_version=7)

    t = threading.Thread(target=pong_once, daemon=True)
    t.start()
    acks = ch.broadcast({"op": "ping"}, deadline=5.0)
    assert acks == [{"ok": True, "error": None, "topology_version": 7}]
    ch.close()
    w.close()


# ---------------------------------------------------------------------------
# session-level: a REAL Database dispatching through the protocol against a
# scripted worker thread (all 8 mesh devices are local to the coordinator,
# so results are complete without a second process). Covers hang/death at
# each phase — readiness, go, completion — with bounded-time degradation
# and rejoin, no sleeps longer than the configured deadlines.
# ---------------------------------------------------------------------------

def _scripted_gang(tmp_path, settings_json, n_workers=1):
    """Database(multihost=coordinator) + WorkerChannel(s) the test scripts.
    Setup statements are host-only (DDL / VALUES insert / analyze), so no
    worker needs to serve during them."""
    import json as _json

    import greengage_tpu
    from greengage_tpu.parallel.multihost import MultihostRuntime

    path = str(tmp_path / "cluster")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "settings.json"), "w") as f:
        f.write(_json.dumps(settings_json))
    ch, workers = _channel_pair(n_workers=n_workers)
    db = greengage_tpu.connect(path, numsegments=8,
                               multihost=MultihostRuntime(0, n_workers + 1,
                                                          ch))
    db.sql("create table t (k bigint, v int) distributed by (k)")
    db.sql("insert into t values " + ",".join(
        f"({i}, {i % 7})" for i in range(300)))
    db.sql("analyze")
    if n_workers == 1:
        return db, ch, workers[0]
    return db, ch, workers


def _serve_mesh(w, n=100):
    """Scripted worker: answer sync/ping/sql frames like worker_loop does
    (no device work — the coordinator owns every segment here)."""
    from greengage_tpu.parallel.multihost import CoordinatorLost

    try:
        for _ in range(n):
            msg = w.recv(idle_timeout=30.0)
            op = msg.get("op")
            if op == "stop":
                return
            if op == "sync":
                w.ack(True, topology_version=msg.get("topology_version"))
            elif op == "ping":
                w.ack(True)
            elif op == "sql":
                w.ack(True)                       # readiness
                if w.recv(idle_timeout=30.0).get("op") == "go":
                    w.ack(True)                   # completion
    except (CoordinatorLost, OSError):
        return


def _recover(db, timeout=10.0):
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if db.mh_try_recover():
            return True
        time.sleep(0.05)
    return False


def test_session_hang_at_readiness_degrades_and_rejoins(devices8, tmp_path):
    """Worker goes silent on the readiness round: detection within
    mh_ready_deadline, the statement completes degraded, the worker
    rejoins, and the session returns to mesh dispatch."""
    # mh_retry_window_s = 0 and mh_reform_enabled = 0: this test asserts
    # the LEGACY degraded fallback, so neither the transparent read-only
    # redispatch (test_dispatch_retry_*) nor N-1 re-formation
    # (test_session_worker_death_reforms_n1_*) may win the race against
    # the instantly-reconnecting scripted worker
    db, ch, w = _scripted_gang(tmp_path, {"mh_heartbeat_interval": 0,
                                          "mh_ready_deadline": 0.5,
                                          "mh_retry_window_s": 0,
                                          "mh_reform_enabled": 0})

    def script():
        from greengage_tpu.parallel.multihost import CoordinatorLost

        try:
            while True:
                if w.recv(idle_timeout=30.0).get("op") == "sql":
                    break                 # swallow it: hung worker
        except (CoordinatorLost, OSError):
            pass
        try:
            while True:
                w.recv(idle_timeout=30.0)  # wait for the quiesce teardown
        except (CoordinatorLost, OSError):
            pass
        if w.reconnect():
            _serve_mesh(w)

    t = threading.Thread(target=script, daemon=True)
    t.start()
    res = {}
    qt = threading.Thread(
        target=lambda: res.update(r=db.sql("select count(*), sum(v) from t")),
        daemon=True)
    t0 = time.monotonic()
    qt.start()
    while db._mh_degraded is None and time.monotonic() - t0 < 5.0:
        time.sleep(0.02)
    detect_s = time.monotonic() - t0
    assert db._mh_degraded, "hung worker never detected"
    assert detect_s < 5.0                 # 0.5s deadline + slack, no hang
    qt.join(240)                          # degraded subprocess completes it
    assert not qt.is_alive(), "degraded statement never completed"
    r = res["r"]
    assert [int(x) for x in r.rows()[0]] == [300, sum(i % 7 for i in range(300))]
    assert r.stats.get("degraded") is True
    assert _recover(db), "gang never recovered after worker rejoin"
    assert db._mh_degraded is None
    r = db.sql("select count(*), sum(v) from t")   # two-phase mesh again
    assert [int(x) for x in r.rows()[0]] == [300, sum(i % 7 for i in range(300))]
    assert r.stats.get("segments") == 8            # mesh, not degraded
    ch.close()
    t.join(10)


def test_session_death_at_go_phase_degrades_and_rejoins(devices8, tmp_path):
    """The go frame fails (dispatch_send fault, start_after=1 so the sql
    broadcast before it succeeds): nobody entered a collective, the
    statement completes degraded, and the gang re-forms."""
    from greengage_tpu.runtime.faultinject import faults

    # retry window + reform 0: assert the degraded fallback (see above)
    db, ch, w = _scripted_gang(tmp_path, {"mh_heartbeat_interval": 0,
                                          "mh_retry_window_s": 0,
                                          "mh_reform_enabled": 0})

    def script():
        from greengage_tpu.parallel.multihost import CoordinatorLost

        try:
            msg = w.recv(idle_timeout=30.0)
            assert msg.get("op") == "sql"
            w.ack(True)                   # readiness answered fine
            while True:
                w.recv(idle_timeout=30.0)  # go never arrives; EOF next
        except (CoordinatorLost, OSError):
            pass
        if w.reconnect():
            _serve_mesh(w)

    t = threading.Thread(target=script, daemon=True)
    t.start()
    faults.inject("dispatch_send", "error", occurrences=1, start_after=1)
    try:
        r = db.sql("select count(*) from t")
    finally:
        faults.reset("dispatch_send")
    assert int(r.rows()[0][0]) == 300
    assert r.stats.get("degraded") is True
    assert db._mh_degraded
    assert _recover(db), "gang never recovered after worker rejoin"
    r = db.sql("select count(*) from t")
    assert int(r.rows()[0][0]) == 300
    assert r.stats.get("segments") == 8
    ch.close()
    t.join(10)


def test_session_hang_at_completion_keeps_result_and_rejoins(devices8, tmp_path):
    """Worker answers readiness + go but never acks completion: the
    coordinator's own result stands (it already executed), the session
    degrades within mh_ack_deadline, then recovers on rejoin."""
    # reform off: this test asserts the LEGACY degraded fallback (the N-1
    # re-formation path has its own tests below)
    db, ch, w = _scripted_gang(tmp_path, {"mh_heartbeat_interval": 0,
                                          "mh_ack_deadline": 0.5,
                                          "mh_reform_enabled": 0})

    def script():
        from greengage_tpu.parallel.multihost import CoordinatorLost

        try:
            msg = w.recv(idle_timeout=30.0)
            assert msg.get("op") == "sql"
            w.ack(True)                   # readiness
            w.recv(idle_timeout=30.0)     # go — then never ack completion
            while True:
                w.recv(idle_timeout=30.0)  # hang until EOF from quiesce
        except (CoordinatorLost, OSError):
            pass
        if w.reconnect():
            _serve_mesh(w)

    t = threading.Thread(target=script, daemon=True)
    t.start()
    r = db.sql("select count(*), sum(v) from t")
    assert [int(x) for x in r.rows()[0]] == [300, sum(i % 7 for i in range(300))]
    assert r.stats.get("segments") == 8   # computed on the mesh, not degraded
    assert db._mh_degraded, "completion-ack hang did not degrade the gang"
    assert _recover(db), "gang never recovered after worker rejoin"
    assert db._mh_degraded is None
    r = db.sql("select count(*) from t")
    assert int(r.rows()[0][0]) == 300
    ch.close()
    t.join(10)


# ---------------------------------------------------------------------------
# full 2-process cluster: fault-injected worker HANG (not death) during the
# readiness round — bounded-time degradation, then the woken worker rejoins
# over the kept listener and the session resumes two-phase mesh dispatch
# through the real worker_loop. Control-plane-only gang (distributed=False):
# this jax's CPU backend has no cross-process collectives, so each process
# runs the lockstep program on its own full local mesh.
# ---------------------------------------------------------------------------

COORD_HANG_REJOIN_SCRIPT = r"""
import json, os, sys, time
port, cport, path = sys.argv[1], int(sys.argv[2]), sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.environ["GGTPU_REPO"])
from greengage_tpu.parallel.multihost import init_multihost
mh = init_multihost(f"127.0.0.1:{port}", 2, 0, cport, distributed=False)
import greengage_tpu
db = greengage_tpu.connect(path, multihost=mh)
out = {}
db.sql("create table f (k bigint, v int) distributed by (k)")
db.sql("insert into f values " + ",".join(f"({i}, {i % 7})" for i in range(2000)))
db.sql("analyze")
r = db.sql("select count(*), sum(v) from f")
out["pre"] = [int(x) for x in r.rows()[0]]
# this test pins the LEGACY degrade-then-rejoin path (the N-1 re-formation
# path is asserted by the reform tests): without the pin the coordinator
# would re-form over the survivors and never degrade
db.sql("set mh_reform_enabled = off")
# bound the readiness round tightly, then arm a one-shot 4s hang on the
# worker's ack path (gp_inject_fault dispatched over the control channel)
db.sql("set mh_ready_deadline = 1")
db.cluster_inject_fault("worker_ack", type="sleep", sleep_s=4, occurrences=1)
t0 = time.monotonic()
r = db.sql("select count(*), sum(v) from f")
out["stmt_s"] = time.monotonic() - t0
out["post"] = [int(x) for x in r.rows()[0]]
out["degraded_during"] = bool(db._mh_degraded)
out["deg_stats"] = bool(getattr(r, "stats", {}).get("degraded"))
# the worker wakes at ~4s, finds its connection gone, and redials the
# kept listener; recovery replays the settings/topology sync
rec = False
end = time.monotonic() + 90
while time.monotonic() < end:
    if db.mh_try_recover():
        rec = True
        break
    time.sleep(0.1)
out["recovered"] = rec
if rec:
    r = db.sql("select count(*), sum(v) from f")
    out["post_rejoin"] = [int(x) for x in r.rows()[0]]
    out["segments"] = r.stats.get("segments")
    out["degraded_after"] = bool(db._mh_degraded)
    db.sql("delete from f where k < 50")
    r = db.sql("select count(*) from f")
    out["post_dml"] = int(r.rows()[0][0])
    # idle-time partition: a one-shot 3s hang on the worker's ping reply
    # (heartbeat fault point) must mark the channel dead BETWEEN
    # statements, degrade the next (host-only) statement, and the gang
    # must recover a SECOND time once the worker wakes and redials
    db.cluster_inject_fault("heartbeat", type="sleep", sleep_s=3,
                            occurrences=1)
    end = time.monotonic() + 20
    while db.multihost.channel.hb_failure is None and time.monotonic() < end:
        time.sleep(0.1)
    out["hb_failure"] = bool(db.multihost.channel.hb_failure)
    db.sql("create table hb_marker (k int)")   # host-only: degrades locally
    out["hb_degraded"] = bool(db._mh_degraded)
    rec2 = False
    end = time.monotonic() + 90
    while time.monotonic() < end:
        if db.mh_try_recover():
            rec2 = True
            break
        time.sleep(0.1)
    out["recovered_again"] = rec2
    if rec2:
        r = db.sql("select count(*) from f")
        out["post_rejoin2"] = int(r.rows()[0][0])
mh.channel.close()   # clean stop frame: the worker exits instead of redialing
print("RESULT:" + json.dumps(out), flush=True)
os._exit(0)
"""


def test_cluster_worker_hang_bounded_degrade_then_rejoin(tmp_path):
    port, cport = _free_port(), _free_port()
    path = str(tmp_path / "cluster")
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "GGTPU_REPO": os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "PYTHONPATH": os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    })
    worker = subprocess.Popen(
        [sys.executable, "-m", "greengage_tpu.mgmt.cli", "worker",
         "-d", path, "--coordinator", f"127.0.0.1:{port}",
         "--control-port", str(cport), "--num-processes", "2",
         "--process-id", "1", "--no-distributed"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    coord = subprocess.Popen(
        [sys.executable, "-c", COORD_HANG_REJOIN_SCRIPT, str(port),
         str(cport), path],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    try:
        cout, _ = coord.communicate(timeout=480)
        wout, _ = worker.communicate(timeout=60)
    except subprocess.TimeoutExpired:
        coord.kill()
        worker.kill()
        cout = coord.stdout.read() if coord.stdout else ""
        wout = worker.stdout.read() if worker.stdout else ""
        raise AssertionError(
            f"hang/rejoin timeout\ncoordinator:\n{cout}\nworker:\n{wout}")
    assert coord.returncode == 0, f"coordinator:\n{cout}\nworker:\n{wout}"
    res = [ln for ln in cout.splitlines() if ln.startswith("RESULT:")]
    assert res, f"coordinator:\n{cout}\nworker:\n{wout}"
    out = json.loads(res[0][len("RESULT:"):])
    want = [2000, sum(i % 7 for i in range(2000))]
    assert out["pre"] == want
    assert out["post"] == want            # completed DURING the hang, degraded
    assert out["degraded_during"] is True
    assert out["deg_stats"] is True
    assert out["stmt_s"] < 120            # bounded: no unbounded readline
    assert out["recovered"] is True, f"worker never rejoined:\n{wout}"
    assert out["post_rejoin"] == want     # two-phase mesh dispatch again
    assert out["segments"] == 8
    assert out["degraded_after"] is False
    assert out["post_dml"] == 1950        # post-rejoin DML dispatches too
    # idle-time partition caught by heartbeats, then a SECOND recovery
    assert out["hb_failure"] is True, "heartbeat never flagged the hang"
    assert out["hb_degraded"] is True
    assert out["recovered_again"] is True, f"second rejoin failed:\n{wout}"
    assert out["post_rejoin2"] == 1950
    # the worker LOGGED the loss and the rejoin instead of exiting silently
    assert "connection lost" in wout and "reconnected" in wout, wout


# ---------------------------------------------------------------------------
# dispatch-failure retry matrix (docs/ROBUSTNESS.md statement lifecycle):
# read-only statements redispatch transparently once the gang re-forms;
# writes surface the error without re-execution (exactly-once)
# ---------------------------------------------------------------------------

def _die_then_rejoin(w):
    """Scripted worker: die on the first sql frame (close mid-dispatch),
    then redial the kept listener and serve mesh exchanges normally."""
    from greengage_tpu.parallel.multihost import CoordinatorLost

    try:
        msg = w.recv(idle_timeout=30.0)
        assert msg.get("op") == "sql"
    except (CoordinatorLost, OSError):
        pass
    w.close()
    end = time.monotonic() + 15
    while time.monotonic() < end:
        if w.reconnect():
            break
        time.sleep(0.05)
    else:
        return
    _serve_mesh(w)


def test_dispatch_retry_readonly_redispatches_after_rejoin(devices8, tmp_path):
    """A read-only statement that loses its worker mid-dispatch succeeds
    TRANSPARENTLY on the re-formed mesh — statements_retried == 1, no
    degraded subprocess, no client-visible error."""
    from greengage_tpu.runtime.logger import counters

    db, ch, w = _scripted_gang(tmp_path, {"mh_heartbeat_interval": 0,
                                          "mh_retry_window_s": 15})
    t = threading.Thread(target=_die_then_rejoin, args=(w,), daemon=True)
    t.start()
    base = counters.get("statements_retried")
    r = db.sql("select count(*), sum(v) from t")
    assert [int(x) for x in r.rows()[0]] == \
        [300, sum(i % 7 for i in range(300))]
    assert r.stats.get("segments") == 8       # mesh result, not degraded
    assert not r.stats.get("degraded")
    assert counters.get("statements_retried") == base + 1
    assert db._mh_degraded is None            # gang recovered in-line
    ch.close()
    t.join(10)


def test_dispatch_failure_write_not_retried(devices8, tmp_path):
    """The same mid-dispatch worker death on a WRITE surfaces the error
    without re-execution: nothing committed (row count unchanged by
    assertion), statements_retried untouched — exactly-once stays the
    DTM's decision, never the dispatcher's."""
    from greengage_tpu.runtime.logger import counters

    db, ch, w = _scripted_gang(tmp_path, {"mh_heartbeat_interval": 0,
                                          "mh_retry_window_s": 15})
    t = threading.Thread(target=_die_then_rejoin, args=(w,), daemon=True)
    t.start()
    base = counters.get("statements_retried")
    with pytest.raises(Exception, match="auto-retried"):
        db.sql("delete from t where k < 10")
    assert counters.get("statements_retried") == base
    assert _recover(db), "gang never recovered after worker rejoin"
    r = db.sql("select count(*) from t")      # exactly-once: no row lost
    assert int(r.rows()[0][0]) == 300
    ch.close()
    t.join(10)


# ---------------------------------------------------------------------------
# N-1 mesh re-formation (the tentpole; docs/ROBUSTNESS.md "Topology
# re-formation"): a worker SIGKILL re-forms the gang over the SURVIVORS —
# subsequent statements (DML included) dispatch on the shrunken topology,
# never the single-process degraded path — and a rejoin restores full
# strength. Scripted 3-process gang: coordinator + 2 worker channels.
# ---------------------------------------------------------------------------

class _ReformWorker:
    """Scripted gang member for the re-formation tests: serves sync/ping/
    sql frames, survives quiesce teardowns by redialing the kept listener
    (the survivor half of re-formation), and can be killed — an abrupt
    socket close with no stop frame, the SIGKILL analog — then later
    allowed back in (the rejoin half). Reads BLOCK like the real
    worker_loop; every control transition arrives as a socket error
    (short recv timeouts poison the channel's buffered reader)."""

    def __init__(self, w):
        self.w = w
        self.die = threading.Event()
        self.dead = threading.Event()   # the close actually landed
        self.rejoin = threading.Event()
        self.halt = threading.Event()
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def kill(self):
        """SIGKILL analog: shut the socket down under the serving thread —
        EOF with no stop frame. (shutdown, not close: closing the makefile
        from another thread deadlocks against an in-flight readline.) The
        thread parks until allow_rejoin()."""
        self.die.set()
        self._shutdown()

    def allow_rejoin(self):
        self.rejoin.set()

    def close(self):
        self.halt.set()
        self.rejoin.set()
        self._shutdown()
        self.thread.join(10)
        self.w.close()

    def _shutdown(self):
        try:
            self.w._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass

    def _redial(self):
        end = time.monotonic() + 15
        while time.monotonic() < end and not self.halt.is_set():
            if self.w.reconnect():
                return True
            time.sleep(0.05)
        return False

    def _run(self):
        from greengage_tpu.parallel.multihost import CoordinatorLost

        w = self.w
        while not self.halt.is_set():
            try:
                msg = w.recv()
                op = msg.get("op")
                if op == "stop":
                    return
                if op == "sync":
                    w.ack(True, topology_version=msg.get("topology_version"))
                elif op == "ping":
                    w.ack(True)
                elif op == "sql":
                    w.ack(True)                     # readiness
                    if w.recv().get("op") == "go":
                        w.ack(True)                 # completion
            except (CoordinatorLost, OSError):
                if self.halt.is_set():
                    return
                if self.die.is_set():               # killed: hold the EOF
                    self.dead.set()
                    self.rejoin.wait(60)
                    if self.halt.is_set():
                        return
                    self.die.clear()
                    self.rejoin.clear()
                    self.dead.clear()
                if not self._redial():              # quiesce/rejoin redial
                    return


def test_worker_sigkill_reforms_n1_then_rejoin_restores_full(devices8,
                                                             tmp_path):
    """The acceptance matrix: SIGKILL a worker mid-session -> the next
    statement (and DML) runs on the re-formed N-1 gang, counted in
    mh_reform_total with a bumped mh_topology_version; the worker's
    rejoin restores the full topology."""
    from greengage_tpu.runtime.logger import counters

    db, ch, (w1, w2) = _scripted_gang(
        tmp_path, {"mh_heartbeat_interval": 0, "mh_ready_deadline": 2,
                   "mh_reform_deadline_s": 5}, n_workers=2)
    g1, g2 = _ReformWorker(w1), _ReformWorker(w2)
    try:
        want = [300, sum(i % 7 for i in range(300))]
        r = db.sql("select count(*), sum(v) from t")
        assert [int(x) for x in r.rows()[0]] == want
        assert db.mh_state()["state"] == "full"
        base_reform = counters.get("mh_reform_total")
        topo0 = counters.get("mh_topology_version")

        g1.kill()                    # worker 1 dies: abrupt close, no stop
        assert g1.dead.wait(5), "scripted worker never closed its socket"
        r = db.sql("select count(*), sum(v) from t")
        assert [int(x) for x in r.rows()[0]] == want
        assert not r.stats.get("degraded"), \
            "worker death fell to the single-process path instead of N-1"
        assert r.stats.get("segments") == 8
        assert db._mh_degraded is None
        st = db.mh_state()
        assert st["state"] == "n-1"
        assert st["active_workers"] == 1 and st["expected_workers"] == 2
        assert counters.get("mh_reform_total") == base_reform + 1
        assert counters.get("mh_topology_version") > topo0
        assert counters.get("mh_topology_version") == \
            db.catalog.segments.version

        # DML on the re-formed gang: manifest commits are coordinator-local
        db.sql("delete from t where k < 5")
        r = db.sql("select count(*) from t")
        assert int(r.rows()[0][0]) == 295
        assert db.mh_state()["state"] == "n-1"

        topo_n1 = counters.get("mh_topology_version")
        g1.allow_rejoin()            # the lost worker returns
        end = time.monotonic() + 10
        while db.mh_state()["state"] != "full" and time.monotonic() < end:
            db.mh_try_recover()
            time.sleep(0.05)
        assert db.mh_state()["state"] == "full", \
            "rejoin never restored the full topology"
        assert counters.get("mh_topology_version") > topo_n1
        r = db.sql("select count(*), sum(v) from t")
        assert int(r.rows()[0][0]) == 295
        assert r.stats.get("segments") == 8
    finally:
        g1.close()
        g2.close()
        ch.close()


@pytest.mark.parametrize("fault", ["mesh_reform",
                                   "mirror_promote_during_reform"])
def test_reform_fault_falls_back_to_degraded(devices8, tmp_path, fault):
    """A re-formation that fails at either fault point (the reform step
    itself, or mirror promotion inside it) must take the legacy degraded
    path — bounded, never a hang or a half-formed gang — and the normal
    full-gang rejoin must still recover it."""
    from greengage_tpu.runtime.faultinject import faults
    from greengage_tpu.runtime.logger import counters

    db, ch, w = _scripted_gang(tmp_path, {"mh_heartbeat_interval": 0,
                                          "mh_retry_window_s": 0})
    t = threading.Thread(target=_die_then_rejoin, args=(w,), daemon=True)
    t.start()
    base = counters.get("mh_reform_total")
    faults.inject(fault, "error", occurrences=1)
    try:
        r = db.sql("select count(*) from t")
    finally:
        faults.reset(fault)
    assert int(r.rows()[0][0]) == 300
    assert r.stats.get("degraded") is True
    assert db._mh_degraded
    assert counters.get("mh_reform_total") == base
    assert _recover(db), "gang never recovered after worker rejoin"
    r = db.sql("select count(*) from t")
    assert int(r.rows()[0][0]) == 300
    assert r.stats.get("segments") == 8
    ch.close()
    t.join(10)

# ---------------------------------------------------------------------------
# chaos tier (slow; the tier1.yml non-blocking chaos step): repeated
# kill -> N-1 reform -> rejoin -> full cycles, with the reform fault
# points armed on later cycles so the degraded fallback and the recovery
# from it are exercised in the SAME session as successful re-formations
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_reform_rejoin_chaos_cycles(devices8, tmp_path):
    """Three kill/rejoin cycles against one session: every cycle must land
    in n-1 (never the single-process path), serve reads AND writes there,
    and restore full strength on rejoin — with monotonically advancing
    mh_reform_total / mh_topology_version. Cycle 2 arms a one-shot
    mesh_reform fault, so that cycle degrades instead, recovers via the
    full-gang rejoin, and the NEXT cycle still re-forms cleanly."""
    from greengage_tpu.runtime.faultinject import faults
    from greengage_tpu.runtime.logger import counters

    db, ch, (w1, w2) = _scripted_gang(
        tmp_path, {"mh_heartbeat_interval": 0, "mh_ready_deadline": 2,
                   "mh_reform_deadline_s": 5}, n_workers=2)
    g1, g2 = _ReformWorker(w1), _ReformWorker(w2)
    rows = 300
    try:
        for cycle, faulted in enumerate((False, True, False)):
            victim = (g1, g2)[cycle % 2]
            reform0 = counters.get("mh_reform_total")
            topo0 = counters.get("mh_topology_version")
            if faulted:
                faults.inject("mesh_reform", "error", occurrences=1)
            try:
                victim.kill()
                assert victim.dead.wait(5), \
                    f"cycle {cycle}: worker never closed its socket"
                r = db.sql("select count(*) from t")
            finally:
                if faulted:
                    faults.reset("mesh_reform")
            assert int(r.rows()[0][0]) == rows
            if faulted:
                assert r.stats.get("degraded") is True
                assert counters.get("mh_reform_total") == reform0
            else:
                assert not r.stats.get("degraded"), \
                    f"cycle {cycle} fell to the single-process path"
                assert db.mh_state()["state"] == "n-1"
                assert counters.get("mh_reform_total") == reform0 + 1
                assert counters.get("mh_topology_version") > topo0
                # writes flow on the shrunken gang every cycle
                db.sql(f"delete from t where k = {cycle}")
                rows -= 1
                assert int(db.sql("select count(*) from t")
                           .rows()[0][0]) == rows
            victim.allow_rejoin()
            end = time.monotonic() + 10
            while db.mh_state()["state"] != "full" \
                    and time.monotonic() < end:
                db.mh_try_recover()
                time.sleep(0.05)
            assert db.mh_state()["state"] == "full", \
                f"cycle {cycle}: rejoin never restored the full topology"
            r = db.sql("select count(*) from t")
            assert int(r.rows()[0][0]) == rows
            assert r.stats.get("segments") == 8
    finally:
        g1.close()
        g2.close()
        ch.close()


# ---------------------------------------------------------------------------
# multihost serving parity (ISSUE 18): a 2-process gang batch-serves
# concurrent same-shape statements through ONE broadcast window per
# dispatch — members_total > dispatch_total proves the amortization
# happened on the gang, not just on a single host
# ---------------------------------------------------------------------------

COORD_BATCH_SCRIPT = r"""
import json, os, sys, threading
port, cport, path = sys.argv[1], int(sys.argv[2]), sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.environ["GGTPU_REPO"])
from greengage_tpu.parallel.multihost import init_multihost
mh = init_multihost(f"127.0.0.1:{port}", 2, 0, cport, distributed=False)
import greengage_tpu
from greengage_tpu.runtime.faultinject import faults
from greengage_tpu.runtime.logger import counters
db = greengage_tpu.connect(path, multihost=mh)
out = {}
db.sql("create table t (k int, a int, v int) distributed by (k)")
db.sql("insert into t values " + ",".join(
    f"({i},{i},{i % 7})" for i in range(3000)))
db.sql("analyze")
def q(i):
    return f"select count(*), sum(v) from t where a > {i}"
# serial oracle BEFORE batching turns on (classic lockstep dispatch)
oracle = {i: [[int(x) for x in row] for row in db.sql(q(i)).rows()]
          for i in range(8)}
db.sql("set batch_serving_enabled = on")
db.sql("set batch_window_ms = 150")
db.sql(q(100))   # warm: plan cache + the width-1 bucket via the gang path
# hold the first dispatch on the "device" so a real multi-member window
# accumulates behind it (both processes sleep in their concurrent dispatch)
faults.inject("batch_dispatch", "sleep", sleep_s=0.4, occurrences=1)
c0 = counters.snapshot()
results, errors = {}, {}
def member(i):
    try:
        results[i] = [[int(x) for x in row] for row in db.sql(q(i)).rows()]
    except Exception as e:
        errors[i] = repr(e)
ts = [threading.Thread(target=member, args=(i,)) for i in range(8)]
for t in ts:
    t.start()
for t in ts:
    t.join(timeout=120)
d = counters.since(c0)
out["alive"] = sum(1 for t in ts if t.is_alive())
out["errors"] = errors
out["mismatch"] = [i for i in range(8) if results.get(i) != oracle[i]]
out["members"] = d.get("batch_members_total", 0)
out["dispatch"] = d.get("batch_dispatch_total", 0)
out["fallback"] = d.get("batch_fallback_total", 0)
# post-canary lockstep sanity: the gang still serves classic statements
r = db.sql("select count(*) from t")
out["post"] = int(r.rows()[0][0])
out["post_segments"] = r.stats.get("segments")
mh.channel.close()
print("RESULT:" + json.dumps(out), flush=True)
"""


def test_two_process_gang_batch_serving_canary(tmp_path):
    port, cport = _free_port(), _free_port()
    path = str(tmp_path / "cluster")
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "GGTPU_REPO": os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "PYTHONPATH": os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    })
    worker = subprocess.Popen(
        [sys.executable, "-m", "greengage_tpu.mgmt.cli", "worker",
         "-d", path, "--coordinator", f"127.0.0.1:{port}",
         "--control-port", str(cport), "--num-processes", "2",
         "--process-id", "1", "--no-distributed"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    coord = subprocess.Popen(
        [sys.executable, "-c", COORD_BATCH_SCRIPT, str(port), str(cport),
         path],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    try:
        cout, _ = coord.communicate(timeout=480)
        wout, _ = worker.communicate(timeout=60)
    except subprocess.TimeoutExpired:
        coord.kill()
        worker.kill()
        cout = coord.stdout.read() if coord.stdout else ""
        wout = worker.stdout.read() if worker.stdout else ""
        raise AssertionError(
            f"batch canary timeout\ncoordinator:\n{cout}\nworker:\n{wout}")
    assert coord.returncode == 0, f"coordinator:\n{cout}\nworker:\n{wout}"
    res = [ln for ln in cout.splitlines() if ln.startswith("RESULT:")]
    assert res, f"coordinator:\n{cout}\nworker:\n{wout}"
    out = json.loads(res[0][len("RESULT:"):])
    assert out["alive"] == 0, out
    assert out["errors"] == {}, out
    assert out["mismatch"] == [], out
    # the canary property: the gang amortized members across dispatches
    assert out["members"] > out["dispatch"], out
    assert out["members"] >= 8, out
    assert out["fallback"] == 0, out
    # and classic lockstep service survived the batched windows
    assert out["post"] == 3000, out
    assert out["post_segments"] == 8, out


# ---------------------------------------------------------------------------
# cluster-wide runaway enforcement: aggregated HBM watermarks, one verdict
# ---------------------------------------------------------------------------

COORD_RUNAWAY_SCRIPT = r"""
import json, os, sys
port, cport, path = sys.argv[1], int(sys.argv[2]), sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.environ["GGTPU_REPO"])
from greengage_tpu.parallel.multihost import init_multihost
mh = init_multihost(f"127.0.0.1:{port}", 2, 0, cport, distributed=False)
import greengage_tpu
from greengage_tpu.runtime.logger import counters
from greengage_tpu.runtime.runaway import RunawayCancelled
db = greengage_tpu.connect(path, multihost=mh)
out = {}
db.sql("create table f (k bigint, g int, v int) distributed by (k)")
db.sql("insert into f values " + ",".join(
    f"({i}, {i % 13}, {i % 7})" for i in range(2000)))
db.sql("analyze")
r = db.sql("select g, count(*) from f group by g order by g")
out["healthy_groups"] = len(r.rows())
# arm a synthetic 1 TB HBM watermark on every WORKER's completion ack
# (the coordinator's own peak stays honest), then set the global ceiling
db.cluster_inject_fault("mh_hbm_watermark", type="skip", occurrences=-1)
db.sql("set vmem_global_limit_mb = 64")
try:
    db.sql("select g, count(*), sum(v) from f group by g order by g")
    out["cancelled"] = False
except RunawayCancelled as e:
    out["cancelled"] = True
    out["reason"] = str(e)
except Exception as e:                          # noqa: BLE001
    out["cancelled"] = "wrong-type:" + type(e).__name__ + ":" + str(e)
out["coord_runaway_ctr"] = counters.get("statements_cancelled_runaway")
# disarm: the verdict killed the STATEMENT, not the gang
db.cluster_inject_fault("mh_hbm_watermark", type="skip", reset=True)
db.sql("set vmem_global_limit_mb = 0")
r = db.sql("select count(*) from f")
out["after"] = int(r.rows()[0][0])
mh.channel.close()
print("RESULT:" + json.dumps(out), flush=True)
"""


def test_cluster_runaway_aggregated_watermark_cancels_gangwide(tmp_path):
    """PR-20 acceptance: a multihost runaway is detected from AGGREGATED
    worker HBM watermarks (no worker is individually over), the
    cancellation broadcasts to the whole gang, and the client sees a
    typed RunawayCancelled — then the next statement serves normally."""
    port, cport = _free_port(), _free_port()
    path = str(tmp_path / "cluster")
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "GGTPU_REPO": os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "PYTHONPATH": os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    })
    worker = subprocess.Popen(
        [sys.executable, "-m", "greengage_tpu.mgmt.cli", "worker",
         "-d", path, "--coordinator", f"127.0.0.1:{port}",
         "--control-port", str(cport), "--num-processes", "2",
         "--process-id", "1", "--no-distributed"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    coord = subprocess.Popen(
        [sys.executable, "-c", COORD_RUNAWAY_SCRIPT, str(port), str(cport),
         path],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    try:
        cout, _ = coord.communicate(timeout=480)
        wout, _ = worker.communicate(timeout=60)
    except subprocess.TimeoutExpired:
        coord.kill()
        worker.kill()
        cout = coord.stdout.read() if coord.stdout else ""
        wout = worker.stdout.read() if worker.stdout else ""
        raise AssertionError(
            f"runaway gang timeout\ncoordinator:\n{cout}\nworker:\n{wout}")
    assert coord.returncode == 0, f"coordinator:\n{cout}\nworker:\n{wout}"
    res = [ln for ln in cout.splitlines() if ln.startswith("RESULT:")]
    assert res, f"coordinator:\n{cout}\nworker:\n{wout}"
    out = json.loads(res[0][len("RESULT:"):])
    assert out["healthy_groups"] == 13
    assert out["cancelled"] is True, out
    assert "red zone" in out["reason"]
    assert out["coord_runaway_ctr"] >= 1
    assert out["after"] == 2000           # the gang outlived the verdict
