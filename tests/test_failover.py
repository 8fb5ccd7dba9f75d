"""Automatic coordinator failover (docs/ROBUSTNESS.md "Coordinator
failover"): hot-standby raw-tail shipping, the promotion fence,
watcher auto-promotion, worker re-homing, and the kill -9 promotion
correctness matrix (mid-2PC, mid-intent-resolve, mid-stream) — the
promoted standby must show every committed row exactly once, roll
in-doubt work back, and resume ingest streams with zero loss and zero
duplicates."""

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import greengage_tpu
import test_crash_recovery as _tcr
from greengage_tpu.runtime import standby
from greengage_tpu.runtime.logger import counters
from greengage_tpu.storage.manifest import CoordinatorFenced, Manifest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def cluster(devices8, tmp_path):
    path = str(tmp_path / "primary")
    d = greengage_tpu.connect(path=path, numsegments=4)
    d.sql("create table t (k int, name text, v int) distributed by (k)")
    d.load_table("t", {"k": np.arange(100),
                       "name": greengage_tpu.types.Coded(
                           ["a", "b"], (np.arange(100) % 2).astype(np.int32)),
                       "v": np.arange(100)})
    return d, path, str(tmp_path / "standby")


# ---------------------------------------------------------------------------
# raw-tail shipping: the standby holds root + log + deltas that compose
# to exactly the primary's committed state (no composed-root shortcuts)
# ---------------------------------------------------------------------------

def test_raw_tail_ships_unfolded_commits(cluster):
    d, path, sb = cluster
    standby.init_standby(path, sb)
    d.sql("insert into t values (1000, 'a', 1)")
    d.sql("delete from t where k < 5")
    # composed standby state == composed primary state, commit for commit
    assert Manifest(sb).snapshot()["version"] == \
        d.store.manifest.snapshot()["version"]
    # byte-identical commit log: the tail shipped incrementally, and the
    # root went across RAW (its version is the fold watermark, BEHIND the
    # composed head while unfolded log lines exist — a composed root next
    # to this log would double-apply them)
    with open(os.path.join(path, "commits.log"), "rb") as f:
        plog = f.read()
    with open(os.path.join(sb, "commits.log"), "rb") as f:
        assert f.read() == plog
    with open(os.path.join(sb, "manifest.json")) as f:
        root = json.load(f)
    assert root.get("version", 0) <= Manifest(sb).snapshot()["version"]
    assert standby.lag(path) == 0


def test_failed_sync_counts_and_widens_lag(cluster):
    import shutil

    d, path, sb = cluster
    standby.init_standby(path, sb)
    shutil.rmtree(sb)                            # standby host dies
    base = counters.snapshot()
    d.sql("insert into t values (2000, 'b', 2)")   # write still succeeds
    assert d.sql("select count(*) from t").rows()[0][0] == 101
    # the formerly-silent swallow is a first-class signal now
    assert counters.since(base).get("standby_sync_fail_total", 0) >= 1
    assert counters.get("standby_lag_commits") >= 1
    st = d.mh_state()
    assert st["standby"]["lag_commits"] >= 1
    assert st["standby"]["sync_fail_total"] >= 1


# ---------------------------------------------------------------------------
# the promotion fence: exclusive hard-link claim, re-verified inside
# every manifest commit point
# ---------------------------------------------------------------------------

def test_fence_blocks_live_primary_commits(cluster):
    d, path, sb = cluster
    standby.init_standby(path, sb)
    standby.write_fence(path, sb)
    with pytest.raises(RuntimeError, match="fenced"):
        d.sql("insert into t values (3000, 'a', 3)")
    # the hard-link CAS: a second standby cannot steal the claim...
    with pytest.raises(RuntimeError, match="raced"):
        standby.write_fence(path, sb + "_other")
    # ... while re-fencing by the owner is idempotent
    assert standby.write_fence(path, sb)["standby"] == os.path.abspath(sb)
    standby.clear_fence(path)
    d.sql("insert into t values (3000, 'a', 3)")
    assert d.sql("select count(*) from t where k = 3000"
                 ).rows()[0][0] == 1


def test_promote_fences_old_primary_and_serves(cluster):
    d, path, sb = cluster
    standby.init_standby(path, sb)
    d.sql("insert into t values (4000, 'b', 4)")
    base = counters.snapshot()
    st = standby.promote(sb, reason="operator")
    assert st["role"] == "activated"
    assert st["promoted"]["reason"] == "operator"
    assert counters.since(base).get("standby_promote_total", 0) == 1
    assert standby.fenced(path)["standby"] == os.path.abspath(sb)
    # a paused-not-dead primary wakes into the fence, not split-brain
    with pytest.raises(RuntimeError, match="fenced"):
        d.sql("insert into t values (4001, 'a', 5)")
    assert standby.promote(sb)["role"] == "activated"   # idempotent
    try:
        d.close()
    except RuntimeError:
        pass                                   # fenced close-time flush
    d2 = greengage_tpu.connect(path=sb, numsegments=4)
    assert d2.sql("select count(*) from t").rows()[0][0] == 101
    assert d2.sql("select v from t where k = 4000").rows() == [(4,)]
    d2.sql("insert into t values (4002, 'a', 6)")
    assert d2.sql("select count(*) from t").rows()[0][0] == 102


def test_watcher_auto_promotes_on_primary_silence(cluster):
    d, path, sb = cluster
    standby.init_standby(path, sb)
    d.sql("insert into t values (5000, 'a', 7)")
    d.close()                    # coordinator gone; the beat goes stale
    base = counters.snapshot()
    fired = []
    w = standby.StandbyWatcher(sb, interval_s=0.05, deadline_s=0.4,
                               on_promote=fired.append)
    end = time.monotonic() + 15.0
    promoted = False
    while not promoted and time.monotonic() < end:
        promoted = w.poll_once()
        time.sleep(0.02)
    assert promoted, "watcher never promoted a silent primary"
    assert fired and fired[0]["role"] == "activated"
    assert "silent" in fired[0]["promoted"]["reason"]
    assert counters.since(base).get("standby_promote_total", 0) == 1
    # the split-brain invariant: the old primary's dir is fenced, so its
    # next locked commit point refuses
    assert standby.fenced(path) is not None
    with pytest.raises(CoordinatorFenced):
        Manifest(path)._check_fence()
    d2 = greengage_tpu.connect(path=sb, numsegments=4)
    assert d2.sql("select count(*) from t").rows()[0][0] == 101


def test_cli_standby_status_and_unfence(cluster, capsys):
    from greengage_tpu.mgmt import cli

    d, path, sb = cluster
    assert cli.main(["initstandby", "-d", path, "-s", sb]) == 0
    assert cli.main(["standby", "-s", sb]) == 0
    out = capsys.readouterr().out
    assert "role: standby" in out and "lag" in out
    standby.write_fence(path, sb)
    assert cli.main(["standby", "--unfence", path]) == 0
    assert standby.fenced(path) is None
    d.sql("insert into t values (42, 'a', 42)")   # unfenced primary serves
    assert d.sql("select count(*) from t").rows()[0][0] == 101


# ---------------------------------------------------------------------------
# client/worker contract: typed-retryable failures and the redial walk
# ---------------------------------------------------------------------------

def test_failover_errors_classify_as_57p01():
    from greengage_tpu.parallel.multihost import CoordinatorLost
    from greengage_tpu.runtime.server import _is_failover_error

    assert _is_failover_error(CoordinatorFenced("fenced"))
    assert _is_failover_error(CoordinatorLost("gone"))
    wrapped = RuntimeError("statement failed")
    wrapped.__cause__ = CoordinatorFenced("fenced")
    assert _is_failover_error(wrapped)          # one causal hop
    assert not _is_failover_error(RuntimeError("boom"))
    assert not _is_failover_error(ValueError("nope"))


def test_parse_addrs_order_dedupe_malformed():
    from greengage_tpu.parallel.multihost import WorkerChannel

    assert WorkerChannel.parse_addrs(
        "127.0.0.1:7001, 127.0.0.1:7002,127.0.0.1:7001") == \
        [("127.0.0.1", 7001), ("127.0.0.1", 7002)]
    # empty host defaults to loopback; malformed entries are dropped,
    # never crash a worker on a broadcast GUC value
    assert WorkerChannel.parse_addrs(":7003,oops,host:bad,") == \
        [("127.0.0.1", 7003)]
    assert WorkerChannel.parse_addrs("") == []
    assert WorkerChannel.parse_addrs(None) == []


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _kill_coordinator(ch):
    """Abrupt coordinator death: tear the connections and listener down
    with NO stop frame (close() sends a clean stop)."""
    for p in ch._workers:
        p.close()
    ch._srv.close()


def test_worker_redial_rehomes_to_standby_address():
    from greengage_tpu.config import Settings
    from greengage_tpu.parallel.multihost import (CoordinatorChannel,
                                                  CoordinatorLost,
                                                  WorkerChannel)

    port_a, port_b = _free_port(), _free_port()
    s = Settings()
    s.mh_coordinator_addrs = f"127.0.0.1:{port_a},127.0.0.1:{port_b}"
    box = {}

    def serve_a():
        box["a"] = CoordinatorChannel(port_a, 1, connect_deadline=10.0)

    t = threading.Thread(target=serve_a, daemon=True)
    t.start()
    w = WorkerChannel("127.0.0.1", port_a, process_id=1, settings=s,
                      connect_deadline=6.0)
    t.join(10)
    assert "a" in box, "coordinator accept never completed"
    _kill_coordinator(box["a"])           # dies without a stop frame
    with pytest.raises(CoordinatorLost):
        w.recv()

    def serve_b():
        box["b"] = CoordinatorChannel(port_b, 1, connect_deadline=15.0)

    t2 = threading.Thread(target=serve_b, daemon=True)
    t2.start()
    base = counters.snapshot()
    # the walk visits the dead current address (refused-at-rejoin fails
    # fast), then lands on the promoted standby's listener; retried until
    # the listener thread has bound
    end = time.monotonic() + 10.0
    ok = False
    while not ok and time.monotonic() < end:
        ok = w.reconnect()
        if not ok:
            time.sleep(0.05)
    assert ok, "candidate walk never reached the standby address"
    t2.join(10)
    assert "b" in box, "promoted listener never adopted the worker"
    assert (w.host, w.port) == ("127.0.0.1", port_b)
    assert counters.since(base).get("mh_rehome_total", 0) == 1
    box["b"].close()
    w.close()


def test_worker_redial_all_addresses_dead_is_bounded():
    from greengage_tpu.config import Settings
    from greengage_tpu.parallel.multihost import (CoordinatorChannel,
                                                  WorkerChannel)

    port_a, port_b = _free_port(), _free_port()
    box = {}

    def serve_a():
        box["a"] = CoordinatorChannel(port_a, 1, connect_deadline=10.0)

    t = threading.Thread(target=serve_a, daemon=True)
    t.start()
    s = Settings()
    s.mh_coordinator_addrs = f"127.0.0.1:{port_a},127.0.0.1:{port_b}"
    w = WorkerChannel("127.0.0.1", port_a, process_id=1, settings=s,
                      connect_deadline=4.0)
    t.join(10)
    _kill_coordinator(box["a"])
    t0 = time.monotonic()
    assert w.reconnect() is False        # every candidate is dead
    assert time.monotonic() - t0 < 10.0  # bounded: no deadline burn-out
    w.close()


# ---------------------------------------------------------------------------
# kill -9 promotion correctness: the crash matrix from
# test_crash_recovery, re-run with a registered standby and the promoted
# standby (not a restarted primary) doing the recovery
# ---------------------------------------------------------------------------

def test_kill9_mid_2pc_promoted_standby_rolls_back(tmp_path):
    path = str(tmp_path / "c")
    _tcr._setup(path)
    sb = str(tmp_path / "sb")
    standby.init_standby(path, sb)
    _tcr._run_child_until(
        path, "dtx_after_prepare",
        lambda: {fn.split(".")[0]
                 for fn in _tcr._staged_uncommitted_deltas(path)}
        >= {"t", "u"})
    # the promotion's final tail pull ships the in-doubt claims; the
    # promoted standby's recover() resolves them exactly as a restarted
    # primary would: ABORT, neither half applied
    st = standby.promote(sb)
    assert st["role"] == "activated"
    d = greengage_tpu.connect(path=sb, numsegments=4)
    assert not _tcr._staged_uncommitted_deltas(sb)
    assert d.sql("select count(*) from t").rows()[0][0] == 100
    assert d.sql("select count(*) from u").rows()[0][0] == 50
    d.sql("insert into t values (555, 555)")     # released claims admit
    assert d.sql("select count(*) from t").rows()[0][0] == 101
    assert standby.fenced(path) is not None      # zombie revival fenced


@pytest.mark.parametrize("window", [0, 1])
def test_kill9_mid_intent_promoted_standby_exactly_once(tmp_path, window):
    path = str(tmp_path / f"c{window}")
    _tcr._setup(path)
    sb = str(tmp_path / "sb")
    standby.init_standby(path, sb)

    if window == 0:
        def parked():
            return bool(_tcr._intent_files(path))
    else:
        def parked():
            return _tcr._merged_rows_for(path, "t") >= 1

    _tcr._run_child_until(path, "intent_resolve", parked,
                          child=_tcr.INTENT_CHILD,
                          extra_env={"GGTPU_INTENT_WINDOW": str(window)})
    standby.promote(sb)
    d = greengage_tpu.connect(path=sb, numsegments=4)
    # window 0: in-doubt intent rolled back; window 1: the durable merge
    # line survived promotion — either way EXACTLY one outcome
    assert not _tcr._intent_files(sb)
    expect = 100 if window == 0 else 101
    assert d.sql("select count(*) from t").rows()[0][0] == expect
    if window == 1:
        assert d.sql("select v from t where k = 100000").rows() == [(7,)]
    d.sql("insert into t values (100001, 8)")
    assert d.sql("select count(*) from t").rows()[0][0] == expect + 1
    assert d.store.manifest.recover() == []


def test_kill9_mid_stream_promoted_standby_resumes_exactly(tmp_path):
    path = str(tmp_path / "c")
    _tcr._setup(path)
    sb = str(tmp_path / "sb")
    standby.init_standby(path, sb)
    _tcr._run_child_until(
        path, "ingest_flush",
        lambda: os.path.exists(path + ".batch2")
        and _tcr._stream_mark(path, "t", "s1") >= 1,
        child=_tcr.STREAM_CHILD)
    standby.promote(sb)
    d = greengage_tpu.connect(path=sb, numsegments=4)
    # batch 1 (committed) crossed the failover; batch 2 (buffered) died
    assert d.sql("select count(*) from t").rows()[0][0] == 101
    assert d.sql("select v from t where k = 200000").rows() == [(1,)]
    assert d.sql("select count(*) from t where k = 200001").rows() \
        == [(0,)]
    # the durable resume watermark survived promotion intact: re-begin
    # names exactly what to re-send, replays dedup — zero loss, zero dup
    out = d.ingest.stream_begin("t", "s1")
    assert out["resume_seq"] == 1
    dup = d.ingest.stream_rows("s1", {"k": [200000], "v": [1]}, 1)
    assert dup["duplicate"] is True
    d.ingest.stream_rows("s1", {"k": [200001], "v": [2]}, 2)
    d.ingest.stream_end("s1")
    assert d.sql("select count(*) from t").rows()[0][0] == 102
    assert d.sql("select count(*) from t where k = 200001").rows() \
        == [(1,)]
    assert d.store.manifest.recover() == []


# ---------------------------------------------------------------------------
# the failover storm canary (slow, CI chaos tier): kill -9 a live
# coordinator mid mixed read/write storm with the watcher running
# concurrently; auto-promotion must land every acked commit exactly once
# ---------------------------------------------------------------------------

STORM_CHILD = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[2])
import greengage_tpu
db = greengage_tpu.connect(sys.argv[1], numsegments=4)
open(sys.argv[1] + ".ready", "w").close()
i = 300000
while True:
    db.sql(f"insert into t values ({i}, {i % 7})")
    if i % 3 == 0:
        db.sql("select count(*) from t")        # mixed storm
    print(f"ACK {i}", flush=True)
    i += 1
"""


@pytest.mark.slow
def test_storm_kill9_auto_promotion_exactly_once(tmp_path):
    path = str(tmp_path / "c")
    _tcr._setup(path)
    sb = str(tmp_path / "sb")
    standby.init_standby(path, sb)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    proc = subprocess.Popen(
        [sys.executable, "-c", STORM_CHILD, path, REPO],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    # the watcher runs CONCURRENTLY with the storm (deployment shape):
    # the live beat (post-commit + FTS cadence, <= ~5s stale) holds the
    # 10s deadline back until the kill actually lands
    base = counters.snapshot()
    fired = threading.Event()
    w = standby.StandbyWatcher(sb, interval_s=0.25, deadline_s=10.0,
                               on_promote=lambda st: fired.set())
    w.start()
    acked = []
    deadline = time.monotonic() + 240
    try:
        while len(acked) < 25 and time.monotonic() < deadline:
            line = proc.stdout.readline()
            if not line:
                raise AssertionError("storm child died early")
            if line.startswith("ACK "):
                acked.append(int(line.split()[1]))
        assert len(acked) >= 25, "storm never ramped up"
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)
        # acks already committed-and-printed but still in the pipe
        for line in (proc.stdout.read() or "").splitlines():
            if line.startswith("ACK "):
                acked.append(int(line.split()[1]))
        assert fired.wait(90), "watcher never promoted after the kill"
    finally:
        w.stop()
        if proc.poll() is None:
            proc.kill()
    assert counters.since(base).get("standby_promote_total", 0) == 1
    assert standby.fenced(path) is not None
    d = greengage_tpu.connect(path=sb, numsegments=4)
    ks = sorted(int(r[0]) for r in
                d.sql("select k from t where k >= 300000").rows())
    assert len(ks) == len(set(ks)), "duplicate rows after failover"
    missing = set(acked) - set(ks)
    assert not missing, f"acked commits lost in failover: {sorted(missing)}"
    # at most the ONE in-flight statement (committed, kill before print)
    extra = set(ks) - set(acked)
    assert len(extra) <= 1, f"phantom rows after failover: {sorted(extra)}"
    # the promoted coordinator keeps serving the storm's table
    d.sql("insert into t values (400000, 1)")
    assert d.sql("select count(*) from t where k = 400000").rows() \
        == [(1,)]
