"""Process-level crash recovery: kill -9 mid-2PC — VERDICT r3 #9, the
crash_recovery_dtm.sql analog
(/root/reference/src/test/isolation2/sql/crash_recovery_dtm.sql:1).

A real subprocess is SIGKILLed while parked on a fault point inside
Transaction.commit; the parent then asserts the distributed outcome is
EXACTLY one of commit/abort (never half), that the in-doubt per-table
delta claims block concurrent same-table writers until recovery, and that
recovery releases them. A second family kills the process mid-FOLD (the
delta-manifest checkpoint) and asserts no committed row is ever lost."""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import greengage_tpu
from greengage_tpu.storage.manifest import Manifest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[2])
from greengage_tpu.runtime.faultinject import faults
import greengage_tpu
db = greengage_tpu.connect(sys.argv[1], numsegments=4)
# connect ran recover() (which may fold/compact, moving the root version):
# signal the parent that every predicate baseline is safe to sample NOW
open(sys.argv[1] + ".ready", "w").close()
faults.inject(sys.argv[3], "sleep", sleep_s=120)
db.sql("begin")
db.sql("insert into t values (100000, 7)")
db.sql("delete from u where k < 5")
print("COMMITTING", flush=True)
db.sql("commit")
print("COMMITTED", flush=True)
"""


def _setup(path):
    d = greengage_tpu.connect(path=path, numsegments=4)
    d.sql("create table t (k int, v int) distributed by (k)")
    d.load_table("t", {"k": np.arange(100), "v": np.arange(100)})
    d.sql("create table u (k int, v int) distributed by (k)")
    d.load_table("u", {"k": np.arange(50), "v": np.arange(50)})
    d.close()
    return d


def _run_child_until(path, fault, wait_for, child=CHILD,
                     extra_env=None):
    """Spawn the committing child, wait for ``wait_for`` (a filesystem
    predicate), then SIGKILL it — the genuine kill -9 the thread-level
    concurrency tests could not deliver."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env.update(extra_env or {})
    proc = subprocess.Popen(
        [sys.executable, "-c", child, path, REPO, fault],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    deadline = time.monotonic() + 120
    try:
        # phase 1: the child's connect-time recover() may fold/compact
        # (both move the root version) — hold every predicate until the
        # child signals that startup is behind it, or the baselines race
        while time.monotonic() < deadline:
            if os.path.exists(path + ".ready"):
                break
            if proc.poll() is not None:
                raise AssertionError(
                    f"child exited early:\n{proc.stdout.read()}")
            time.sleep(0.05)
        else:
            raise AssertionError("child never finished connecting")
        while time.monotonic() < deadline:
            if wait_for():
                break
            if proc.poll() is not None:
                raise AssertionError(
                    f"child exited early:\n{proc.stdout.read()}")
            time.sleep(0.05)
        else:
            raise AssertionError("child never reached the fault point")
        os.kill(proc.pid, signal.SIGKILL)
    finally:
        proc.wait(timeout=30)
    assert proc.returncode == -signal.SIGKILL


def _committed_delta_keys(path):
    """(table, seq) pairs referenced by committed commit-log lines."""
    m = Manifest(path)
    root = m._root()
    lines, _end = m._log_lines(int(root.get("log_pos", 0)))
    out = set()
    for line in lines:
        for t, s in (line.get("t") or {}).items():
            out.add((t, int(s)))
    return out


def _staged_uncommitted_deltas(path):
    """Delta claims staged by an in-flight 2PC: files under deltas/ whose
    (table, seq) no committed log line references — the in-doubt state a
    kill -9 between prepare_delta and commit_delta leaves behind."""
    ddir = os.path.join(path, "deltas")
    if not os.path.isdir(ddir):
        return []
    committed = _committed_delta_keys(path)
    out = []
    for fn in os.listdir(ddir):
        if not fn.endswith(".delta"):
            continue
        stem, seq_s = fn[:-len(".delta")].rsplit(".", 1)
        if (stem, int(seq_s)) not in committed:
            out.append(fn)
    return out


def _staged_above_head(path):
    """Prepared-but-uncommitted ROOT stages (fold / structural commits)."""
    m = Manifest(path)
    head = m.snapshot().get("version", 0)
    return [fn for fn in os.listdir(path)
            if fn.startswith("manifest.") and fn.endswith(".prepared")
            and int(fn.split(".")[1]) > head]


def test_kill9_between_prepare_and_commit_rolls_back(tmp_path):
    path = str(tmp_path / "c")
    _setup(path)
    # wait for BOTH tables' claims: the predicate firing on the first
    # file would let the SIGKILL land mid-prepare_delta (t staged, u not
    # yet) instead of at the parked fault point
    _run_child_until(
        path, "dtx_after_prepare",
        lambda: {fn.split(".")[0]
                 for fn in _staged_uncommitted_deltas(path)} >= {"t", "u"})
    # in-doubt: the per-table delta claims exist without a commit record...
    staged = _staged_uncommitted_deltas(path)
    assert {fn.split(".")[0] for fn in staged} == {"t", "u"}
    m = Manifest(path)
    head_before = m.snapshot().get("version", 0)
    # ... and a concurrent writer to the SAME table cannot steal the
    # claimed sequence (the per-table CAS; cross-table writers — here a
    # fresh table name — are NOT blocked by the in-doubt claims)
    with pytest.raises(RuntimeError, match="write-write conflict"):
        tx = m.begin()
        tx["tables"]["t"] = dict(tx["tables"]["t"])
        m.prepare_delta(tx, ["t"])
    # recovery (runs inside connect) resolves the in-doubt tx: ABORT
    d = greengage_tpu.connect(path=path, numsegments=4)
    assert not _staged_uncommitted_deltas(path)      # claims released
    assert d.store.manifest.snapshot()["version"] >= head_before
    # outcome is exactly-abort: NEITHER half of the transaction applied
    assert d.sql("select count(*) from t").rows()[0][0] == 100
    assert d.sql("select count(*) from u").rows()[0][0] == 50
    # and the released claims admit new writers
    d.sql("insert into t values (555, 555)")
    assert d.sql("select count(*) from t").rows()[0][0] == 101


def test_kill9_after_commit_preserves_commit(tmp_path):
    path = str(tmp_path / "c")
    _setup(path)
    # the commit evidence is the durable commit-LOG line (the delta path's
    # commit record): the _setup loads commit via intent MERGE lines (no
    # delta claim), so the 2PC's line (t.1 — the first delta claim the
    # cluster ever makes for t) appearing is baseline-free ground truth —
    # a lazy baseline would race a fast child that commits before the
    # parent's first poll
    _run_child_until(path, "dtx_after_commit",
                     lambda: ("t", 1) in _committed_delta_keys(path))
    # the commit-log line was durable before the kill: recovery must KEEP
    # the commit (and fold it into the root)
    d = greengage_tpu.connect(path=path, numsegments=4)
    assert d.sql("select count(*) from t").rows()[0][0] == 101   # insert in
    assert d.sql("select count(*) from u").rows()[0][0] == 45    # delete in
    assert d.sql("select v from t where k = 100000").rows() == [(7,)]
    # the killed process never ran its deferred GC: orphan sweep is the
    # backstop and must not touch live files
    d.store.sweep_orphans(grace_s=0)
    assert d.sql("select count(*) from t").rows()[0][0] == 101
    assert d.sql("select count(*) from u").rows()[0][0] == 45


def test_kill9_with_concurrent_writer_exactly_one_outcome(tmp_path):
    """The crash_recovery_dtm shape: writer A dies mid-2PC while writer B
    (another process, i.e. this one) keeps writing. B must never see half
    of A, and B's own commits must survive A's recovery."""
    path = str(tmp_path / "c")
    _setup(path)
    _run_child_until(path, "dtx_after_prepare",
                     lambda: bool(_staged_uncommitted_deltas(path)))
    d = greengage_tpu.connect(path=path, numsegments=4)   # recovers A
    d.sql("insert into u values (777, 1)")                # writer B
    assert d.sql("select count(*) from t").rows()[0][0] == 100   # A aborted
    assert d.sql("select count(*) from u").rows()[0][0] == 51
    # a second recovery pass is idempotent
    assert d.store.manifest.recover() == []


# ---------------------------------------------------------------------------
# kill -9 during a delta FOLD (the checkpoint): the root replace is atomic
# and replayed deltas are sequence-guarded, so committed rows survive a
# crash in either fold window (staged-not-committed / committed-not-GC'd)
# ---------------------------------------------------------------------------

FOLD_CHILD = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[2])
from greengage_tpu.runtime.faultinject import faults
import greengage_tpu
db = greengage_tpu.connect(sys.argv[1], numsegments=4)
open(sys.argv[1] + ".ready", "w").close()         # startup recovery done
db.sql("set manifest_delta_fold_threshold = 1")   # fold on every commit
# start_after targets the fold window: 0 = parked after the fold root is
# STAGED (before the atomic replace), 1 = parked after the replace
# (before the folded delta files are GC'd)
faults.inject("delta_fold", "sleep", sleep_s=120,
              start_after=int(os.environ.get("GGTPU_FOLD_WINDOW", "0")))
db.sql("insert into t values (100000, 7)")
print("FOLDED", flush=True)
"""


@pytest.mark.parametrize("window", [0, 1])
def test_kill9_mid_fold_loses_no_committed_rows(tmp_path, window):
    path = str(tmp_path / f"c{window}")
    _setup(path)

    if window == 0:
        # parked between staging the fold root and the atomic replace:
        # the staged claim is visible above the committed head
        def parked():
            return bool(_staged_above_head(path))
    else:
        # parked after the replace: the new root folded the INSERT's
        # merge line, so its recorded INTENT sequence for t reached 2
        # (iseq 1 = the _setup load's merge, folded at the child's
        # startup compaction; iseq 2 = the insert — autocommit appends
        # commit via write intents, not delta claims). Baseline-free on
        # purpose — a lazy baseline races a fast child, which can fold
        # before the parent's first poll.
        def parked():
            seqs = Manifest(path)._root().get("intent_seqs", {})
            return int(seqs.get("t", 0)) >= 2

    _run_child_until(path, "delta_fold", parked, child=FOLD_CHILD,
                     extra_env={"GGTPU_FOLD_WINDOW": str(window)})
    # the INSERT's commit line was durable before the fold began: whatever
    # the fold got to, recovery must surface the committed row
    d = greengage_tpu.connect(path=path, numsegments=4)
    assert d.sql("select count(*) from t").rows()[0][0] == 101
    assert d.sql("select v from t where k = 100000").rows() == [(7,)]
    assert not _staged_above_head(path)          # fold claim resolved
    assert not _staged_uncommitted_deltas(path)
    # recovery compacted: the store keeps serving writes
    d.sql("insert into t values (100001, 8)")
    assert d.sql("select count(*) from t").rows()[0][0] == 102
    assert d.store.manifest.recover() == []


# ---------------------------------------------------------------------------
# kill -9 on the WRITE-INTENT path (docs/ROBUSTNESS.md "Write-intent
# commit & streaming ingest"): the intent_resolve fault point fires TWICE
# per commit, so start_after pins either crash window — before the merge
# line (in-doubt intent, rolled back like a stale delta claim) and after
# it is durable but before the marker unlink (the commit SURVIVES)
# ---------------------------------------------------------------------------

INTENT_CHILD = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[2])
from greengage_tpu.runtime.faultinject import faults
import greengage_tpu
db = greengage_tpu.connect(sys.argv[1], numsegments=4)
open(sys.argv[1] + ".ready", "w").close()         # startup recovery done
# window 0 = parked after the intent is staged, merge line NOT appended;
# window 1 = parked after the merge line is durable, marker NOT unlinked
faults.inject(sys.argv[3], "sleep", sleep_s=120,
              start_after=int(os.environ.get("GGTPU_INTENT_WINDOW", "0")))
db.sql("insert into t values (100000, 7)")
print("RESOLVED", flush=True)
"""


def _intent_files(path):
    idir = os.path.join(path, "intents")
    if not os.path.isdir(idir):
        return []
    return [fn for fn in os.listdir(idir) if fn.endswith(".intent")]


def _merge_lines_for(path, table):
    """Committed "w" merge lines for ``table`` past the root's log_pos."""
    m = Manifest(path)
    root = m._root()
    lines, _end = m._log_lines(int(root.get("log_pos", 0)))
    return [line["w"][table] for line in lines
            if table in (line.get("w") or {})]


def _merged_rows_for(path, table):
    return sum(int(n) for recs in _merge_lines_for(path, table)
               for _seg, _rels, n in recs)


@pytest.mark.parametrize("window", [0, 1])
def test_kill9_mid_intent_resolve_both_windows(tmp_path, window):
    path = str(tmp_path / f"c{window}")
    _setup(path)

    if window == 0:
        # parked between stage and resolve: the durable intent exists,
        # no merge line does — the in-doubt state recovery must roll back
        def parked():
            return bool(_intent_files(path))
    else:
        # parked after the fsynced merge line (the commit point), before
        # the marker unlink: the 1-row merge for t is ground truth (the
        # child's startup compaction folded the _setup load's 100 rows)
        def parked():
            return _merged_rows_for(path, "t") >= 1

    _run_child_until(path, "intent_resolve", parked, child=INTENT_CHILD,
                     extra_env={"GGTPU_INTENT_WINDOW": str(window)})
    assert _intent_files(path)           # both windows leave the marker
    if window == 0:
        assert _merged_rows_for(path, "t") == 0
    from greengage_tpu.runtime.logger import counters
    base = counters.snapshot()
    d = greengage_tpu.connect(path=path, numsegments=4)   # runs recover()
    # recovery swept the marker with the no-grace discipline either way:
    # window 0 rolls the writer back, window 1 clears committed garbage
    assert not _intent_files(path)
    assert counters.since(base).get("manifest_intent_swept_total", 0) >= 1
    expect = 100 if window == 0 else 101
    assert d.sql("select count(*) from t").rows()[0][0] == expect
    if window == 1:
        assert d.sql("select v from t where k = 100000").rows() == [(7,)]
    # the dead writer's segfiles: orphans (window 0) are reclaimed, live
    # files (window 1) are untouchable — either way counts are stable
    d.store.sweep_orphans(grace_s=0)
    assert d.sql("select count(*) from t").rows()[0][0] == expect
    # the manifest stays foldable past the crash
    d.sql("set manifest_delta_fold_threshold = 1")
    d.sql("insert into t values (100001, 8)")
    assert d.sql("select count(*) from t").rows()[0][0] == expect + 1
    assert d.store.manifest.recover() == []


# ---------------------------------------------------------------------------
# kill -9 mid-STREAM (the ingest_flush fault point parks a micro-batch
# after the client ack, before its intent commit): nothing past the last
# committed watermark survives, resume replays exactly the tail
# ---------------------------------------------------------------------------

STREAM_CHILD = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[2])
from greengage_tpu.runtime.faultinject import faults
import greengage_tpu
db = greengage_tpu.connect(sys.argv[1], numsegments=4)
open(sys.argv[1] + ".ready", "w").close()
db.sql("set ingest_batch_rows = 1")      # every batch commits inline
db.ingest.stream_begin("t", "s1")
db.ingest.stream_rows("s1", {"k": [200000], "v": [1]}, 1)   # committed
faults.inject(sys.argv[3], "sleep", sleep_s=120)
open(sys.argv[1] + ".batch2", "w").close()
# batch 2 is ACKED into the buffer, then parks before its intent commit
db.ingest.stream_rows("s1", {"k": [200001], "v": [2]}, 2)
print("NEVER", flush=True)
"""


def _stream_mark(path, table, sid):
    return int(Manifest(path).snapshot()["tables"]
               .get(table, {}).get("streams", {}).get(sid, 0))


def test_kill9_mid_stream_resumes_from_watermark(tmp_path):
    path = str(tmp_path / "c")
    _setup(path)
    _run_child_until(
        path, "ingest_flush",
        lambda: os.path.exists(path + ".batch2")
        and _stream_mark(path, "t", "s1") >= 1,
        child=STREAM_CHILD)
    # batch 1's watermark rode its merge line; batch 2 died in the buffer
    d = greengage_tpu.connect(path=path, numsegments=4)
    assert d.sql("select count(*) from t").rows()[0][0] == 101
    assert d.sql("select v from t where k = 200000").rows() == [(1,)]
    assert d.sql("select count(*) from t where k = 200001").rows() \
        == [(0,)]
    # the client re-begins with the SAME stream id: the durable watermark
    # names exactly what to re-send — and a replay of batch 1 dedups
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
