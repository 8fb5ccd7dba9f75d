"""Statement lifecycle guardrails (runtime/interrupt.py): cooperative
cancellation at every wait state, statement timeouts, the unified
counter family, and the server's cancel protocol + client_gone handling.
The CHECK_FOR_INTERRUPTS / statement_timeout / pg_cancel_backend analog
(tcop/postgres.c ProcessInterrupts)."""

import json
import socket
import threading
import time

import numpy as np
import pytest

import greengage_tpu
from greengage_tpu.runtime.faultinject import faults
from greengage_tpu.runtime.interrupt import (REGISTRY, StatementCancelled,
                                             StatementContext)
from greengage_tpu.runtime.logger import counters
from greengage_tpu.runtime.trace import TRACES


# ---------------------------------------------------------------------------
# pure-host primitives (no devices)
# ---------------------------------------------------------------------------

def test_context_check_raises_typed_cause():
    ctx = StatementContext(1, "select 1")
    ctx.check()                      # unflagged: no-op
    ctx.cancel("user")
    with pytest.raises(StatementCancelled) as ei:
        ctx.check()
    assert ei.value.cause == "user"
    assert "user request" in str(ei.value)
    ctx.cancel("timeout")            # first cause wins
    assert ctx.cause == "user"


def test_context_timeout_trips_flag():
    ctx = StatementContext(2, "select 1", timeout_s=0.05)
    assert ctx.remaining() <= 0.05
    time.sleep(0.08)
    assert ctx.cancelled
    with pytest.raises(StatementCancelled) as ei:
        ctx.check()
    assert ei.value.cause == "timeout"
    assert "statement timeout" in str(ei.value)


def test_context_listener_fires_on_cancel_and_immediately_when_late():
    ctx = StatementContext(3, "x")
    hits = []
    ctx.add_listener(lambda: hits.append("a"))
    ctx.cancel("user")
    assert hits == ["a"]
    ctx.add_listener(lambda: hits.append("b"))   # late: fires at once
    assert hits == ["a", "b"]


def test_registry_nesting_and_cancel_by_id():
    ctx, outer = REGISTRY.enter("select 1")
    try:
        assert outer
        inner, inner_outer = REGISTRY.enter("nested")
        assert inner is ctx and not inner_outer   # shared outermost ctx
        REGISTRY.exit(inner)
        assert REGISTRY.current() is ctx
        rows = REGISTRY.snapshot()
        assert any(r["id"] == ctx.statement_id for r in rows)
        assert REGISTRY.cancel(ctx.statement_id, "user")
        assert ctx.cancelled
        assert not REGISTRY.cancel(999999)        # unknown id: False
    finally:
        REGISTRY.exit(ctx)
    assert REGISTRY.current() is None


def test_registry_cancel_all_flags_everything():
    ctx, _ = REGISTRY.enter("select 1")
    try:
        assert REGISTRY.cancel_all("shutdown") >= 1
        assert ctx.cause == "shutdown"
    finally:
        REGISTRY.exit(ctx)


# ---------------------------------------------------------------------------
# engine-level cancellation at each wait state
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def db(devices8):
    d = greengage_tpu.connect(numsegments=4)
    n = 50_000
    d.sql("create table li (k int, g int, v int) distributed by (k)")
    d.load_table("li", {"k": np.arange(n), "g": (np.arange(n) % 11),
                        "v": (np.arange(n) % 7)})
    d.sql("analyze")
    yield d
    d.close()


def _cancel_sql(marker: str, cause: str = "user", timeout_s: float = 5.0):
    """Wait until a statement whose text carries ``marker`` shows in the
    registry, then cancel it; -> its id (None if never seen)."""
    end = time.monotonic() + timeout_s
    while time.monotonic() < end:
        for row in REGISTRY.snapshot():
            if marker in row["sql"]:
                REGISTRY.cancel(row["id"], cause)
                return row["id"]
        time.sleep(0.01)
    return None


def test_statement_timeout_cancels_in_staging(db):
    """statement_timeout_s arms at statement start and the statement dies
    at a staging-unit cancellation point — one a (segment, column)
    (scan_threads=1: units run serially on the statement thread, so the
    per-unit sleep fault makes the deadline trip deterministic)."""
    db.sql("set scan_threads = 1")
    db.sql("set statement_timeout_s = 0.3")
    faults.inject("cancel_in_staging", "sleep", sleep_s=0.2, occurrences=-1)
    base = counters.get("statements_cancelled_timeout")
    try:
        with pytest.raises(StatementCancelled) as ei:
            db.sql("select count(*) from li where v = 3 -- timeout-victim")
        assert ei.value.cause == "timeout"
        assert counters.get("statements_cancelled_timeout") == base + 1
    finally:
        faults.reset("cancel_in_staging")
        db.sql("set statement_timeout_s = 0")
        db.sql("set scan_threads = 0")
    # the registry is clean and the session still serves
    assert REGISTRY.current() is None
    assert db.sql("select count(*) from li").rows()[0][0] == 50_000


def test_staging_cancel_point_fires_once_a_read_unit(db):
    """Every (segment, column) unit is a cancellation point of its own:
    a two-column scan over four segments passes the point eight times,
    and a deadline that trips inside the first column's units stops the
    statement before the second column's are ever read."""
    q = "select sum(g), sum(v) from li -- two-column-victim"
    db.sql(q)   # compile outside the deadline below
    db.sql("set scan_threads = 1")
    try:
        db.executor.stager.stage_cache.clear()
        db.store.blockcache.clear()
        faults.inject("cancel_in_staging", "sleep", sleep_s=0.0,
                      occurrences=-1)
        assert db.sql(q).rows()[0][0] == sum(np.arange(50_000) % 11)
        assert [f["hits"] for f in faults.status()] == [8]
        faults.reset("cancel_in_staging")
        db.executor.stager.stage_cache.clear()
        db.store.blockcache.clear()
        db.sql("set statement_timeout_s = 0.3")
        faults.inject("cancel_in_staging", "sleep", sleep_s=0.2,
                      occurrences=-1)
        with pytest.raises(StatementCancelled) as ei:
            db.sql(q)
        assert ei.value.cause == "timeout"
        # column-major: the deadline fell among column g's four units, and
        # every later unit stopped at the point, before its read
        reads = [s["args"]["column"]
                 for s in [t for t in TRACES.between(0.0, float("inf"))
                           if t.sql == q][-1].export()
                 if s["name"] == "read:li"]
        assert reads and len(reads) < 4 and set(reads) == {"g"}
    finally:
        faults.reset("cancel_in_staging")
        db.sql("set statement_timeout_s = 0")
        db.sql("set scan_threads = 0")


def test_user_cancel_lands_mid_staging(db):
    """`gg cancel` semantics: a statement parked in cold staging reads is
    cancelled mid-flight (between read units), within a bounded time."""
    db.sql("set scan_threads = 1")
    faults.inject("cancel_in_staging", "sleep", sleep_s=0.25, occurrences=-1)
    err = {}

    def victim():
        try:
            db.sql("select sum(v) from li -- staging-victim")
            err["e"] = None
        except Exception as e:
            err["e"] = e

    base = counters.get("statements_cancelled_user")
    t = threading.Thread(target=victim)
    t0 = time.monotonic()
    t.start()
    try:
        assert _cancel_sql("staging-victim") is not None
        t.join(timeout=10)
        assert not t.is_alive()
        assert isinstance(err["e"], StatementCancelled), err["e"]
        assert err["e"].cause == "user"
        # one boundary interval: a couple of 0.25s units, never a hang
        assert time.monotonic() - t0 < 5.0
        assert counters.get("statements_cancelled_user") == base + 1
    finally:
        faults.reset("cancel_in_staging")
        db.sql("set scan_threads = 0")


def test_cancel_statement_parked_in_resource_queue(db):
    """A queued statement observes cancellation IMMEDIATELY (listener
    wakeup, not the next timeout slice), re-notifies so the racing
    release is never lost, and counts in queue_cancelled_total."""
    db.sql("set resource_queue_active = 1")
    # the slot holder sleeps at the pre-dispatch fault, keeping the queue
    # full while the victim parks in admit()
    faults.inject("cancel_before_dispatch", "sleep", sleep_s=1.5,
                  occurrences=1)
    res = {}

    def holder():
        try:
            res["holder"] = db.sql("select count(*) from li -- holder")
        except Exception as e:       # pragma: no cover
            res["holder"] = e

    def victim():
        try:
            db.sql("select sum(v) from li -- queue-victim")
            res["victim"] = None
        except Exception as e:
            res["victim"] = e

    qbase = counters.get("queue_cancelled_total")
    th = threading.Thread(target=holder)
    th.start()
    time.sleep(0.3)                  # holder admitted, now sleeping
    tv = threading.Thread(target=victim)
    t0 = time.monotonic()
    tv.start()
    try:
        assert _cancel_sql("queue-victim") is not None
        tv.join(timeout=10)
        assert not tv.is_alive(), "cancelled waiter never left the queue"
        waited = time.monotonic() - t0
        assert isinstance(res["victim"], StatementCancelled), res["victim"]
        assert res["victim"].cause == "user"
        assert waited < 1.4, f"queue exit took {waited:.2f}s (not immediate)"
        assert counters.get("queue_cancelled_total") == qbase + 1
        th.join(timeout=30)
        assert hasattr(res["holder"], "rows"), res["holder"]
        # the re-notify preserved the slot: a later statement admits fine
        assert db.sql("select count(*) from li").rows()[0][0] == 50_000
        assert db.resqueue.stats()["active"] == 0
    finally:
        faults.reset("cancel_before_dispatch")
        db.sql("set resource_queue_active = 0")


def test_cancel_between_spill_passes(db):
    """A spilling statement (pass-partitioned execution) is cancelled at
    a spill-pass boundary — the runaway cleaner's documented cancellation
    point, now shared by user cancels."""
    db.sql("set vmem_protect_limit_mb = 1")     # force the spill regime
    # slow each pass down at its pre-dispatch point so the cancel lands
    # while passes remain
    faults.inject("cancel_before_dispatch", "sleep", sleep_s=0.3,
                  occurrences=-1)
    err = {}

    def victim():
        try:
            db.sql("select g, count(*), sum(v) from li group by g"
                   " -- spill-victim")
            err["e"] = None
        except Exception as e:
            err["e"] = e

    t = threading.Thread(target=victim)
    t.start()
    try:
        assert _cancel_sql("spill-victim") is not None
        t.join(timeout=60)
        assert not t.is_alive()
        assert isinstance(err["e"], StatementCancelled), err["e"]
        assert err["e"].cause == "user"
    finally:
        faults.reset("cancel_before_dispatch")
        db.sql("set vmem_protect_limit_mb = 12288")
    assert db.sql("select count(*) from li").rows()[0][0] == 50_000


def test_statement_timeout_zero_disables(db):
    db.sql("set statement_timeout_s = 0")
    assert db.sql("select count(*) from li").rows()[0][0] == 50_000


# ---------------------------------------------------------------------------
# server protocol: cancel frame + client_gone on disconnect
# ---------------------------------------------------------------------------

def test_server_cancel_frame_and_typed_error(db, tmp_path):
    from greengage_tpu.runtime.server import SqlClient, SqlServer

    sock = str(tmp_path / "gg.sock")
    srv = SqlServer(db, sock)
    srv.start()
    faults.inject("cancel_before_dispatch", "sleep", sleep_s=2.0,
                  occurrences=1)
    try:
        err = {}

        def client_victim():
            c = SqlClient(sock)
            try:
                c.sql("select sum(v) from li -- wire-victim")
                err["e"] = None
            except Exception as e:
                err["e"] = e
            finally:
                c.close()

        t = threading.Thread(target=client_victim)
        t.start()
        # a SECOND connection finds and cancels it (the executing one is
        # blocked in its statement, like pg_cancel_backend from psql)
        c2 = SqlClient(sock)
        end = time.monotonic() + 5
        sid = None
        while time.monotonic() < end and sid is None:
            for row in c2.op({"op": "ps"}).get("rows", []):
                if "wire-victim" in row["sql"]:
                    sid = row["id"]
            time.sleep(0.02)
        assert sid is not None, "ps never showed the in-flight statement"
        assert c2.op({"op": "cancel", "id": sid}) == {"ok": True}
        assert c2.op({"op": "cancel", "id": 999999})["ok"] is False
        assert c2.op({"op": "bogus"})["ok"] is False
        c2.close()
        t.join(timeout=15)
        assert not t.is_alive()
        assert err["e"] is not None
        assert "cancel" in str(err["e"]).lower()
    finally:
        faults.reset("cancel_before_dispatch")
        srv.stop()


def test_client_disconnect_cancels_in_flight_statement(db, tmp_path):
    """The per-statement watcher observes the client's EOF while the
    handler thread is blocked in db.sql() and flags the statement
    client_gone — it dies at its next cancellation point instead of
    running to completion for nobody."""
    from greengage_tpu.runtime.server import SqlClient, SqlServer

    sock = str(tmp_path / "gg.sock")
    srv = SqlServer(db, sock)
    srv.start()
    faults.inject("cancel_before_dispatch", "sleep", sleep_s=3.0,
                  occurrences=1)
    base = counters.get("statements_cancelled_client_gone")
    try:
        s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        s.connect(sock)
        s.sendall((json.dumps(
            {"sql": "select sum(v) from li -- gone-victim"}) + "\n")
            .encode())
        time.sleep(0.5)           # statement parked at the fault sleep
        s.close()                 # client vanishes mid-statement
        end = time.monotonic() + 15
        while counters.get("statements_cancelled_client_gone") == base \
                and time.monotonic() < end:
            time.sleep(0.05)
        assert counters.get("statements_cancelled_client_gone") == base + 1
        # the statement left the registry and the server still serves
        end = time.monotonic() + 5
        while any("gone-victim" in r["sql"] for r in REGISTRY.snapshot()) \
                and time.monotonic() < end:
            time.sleep(0.05)
        assert not any("gone-victim" in r["sql"]
                       for r in REGISTRY.snapshot())
        c = SqlClient(sock)
        assert c.sql("select count(*) from li")["rows"][0][0] == 50_000
        c.close()
    finally:
        faults.reset("cancel_before_dispatch")
        srv.stop()


def test_server_survives_client_disconnect_mid_exchange(db, tmp_path):
    """A client that sends a statement and vanishes must not let the
    broken pipe escape into socketserver: the handler ends cleanly and
    the server keeps serving other clients."""
    from greengage_tpu.runtime.server import SqlClient, SqlServer

    sock = str(tmp_path / "gg.sock")
    srv = SqlServer(db, sock)
    srv.start()
    try:
        for _ in range(3):
            s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            s.connect(sock)
            s.sendall((json.dumps(
                {"sql": "select count(*) from li"}) + "\n").encode())
            s.close()                       # gone before reading the rows
        time.sleep(0.3)                     # let the handlers run into it
        c = SqlClient(sock)                 # the server still serves
        assert c.sql("select count(*) from li")["rows"][0][0] == 50_000
        c.close()
    finally:
        srv.stop()
