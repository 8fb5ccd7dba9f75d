"""Vectorized serving (ISSUE 11, docs/PERF.md "Vectorized serving"):
batch concurrent same-shape statements into ONE XLA dispatch behind the
async executor pipeline (exec/batchserve.py).

The contract under test:
  (a) demux correctness — every member of a batch gets exactly the rows
      a serial execution of its statement returns, across mixed
      literals (ints, floats, ORDER BY/LIMIT shapes);
  (b) width-bucketed compiles — N same-shape members compile once per
      observed pow2 width bucket (jit-count + counter verified), never
      once per width;
  (c) cancellation isolation — a cancelled member raises its typed
      StatementCancelled and its batch-mates' results are untouched;
  (d) window behavior — full windows flush on batch_max_width, partial
      windows flush on the batch_window_ms timer;
  (e) pipelining — stage(k+1) overlaps dispatch(k), asserted from the
      batch traces' span timestamps (a sleep fault pins the overlap
      deterministically);
  (f) the disabled path spawns no pipeline and serves classically.
"""

import threading
import time

import numpy as np
import pytest

import greengage_tpu
import greengage_tpu.exec.compile as C
from greengage_tpu.exec import batchserve
from greengage_tpu.runtime.faultinject import faults
from greengage_tpu.runtime.interrupt import REGISTRY, StatementCancelled
from greengage_tpu.runtime.logger import counters
from greengage_tpu.sql.parser import parse
from greengage_tpu.sql.paramize import ParamVector


@pytest.fixture()
def jits(monkeypatch):
    """Counts compiled programs: exec/compile.py wraps every traced
    query program in exactly one jax.jit call."""
    calls = {"n": 0}
    real = C.jax.jit

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(C.jax, "jit", counting)
    return calls


@pytest.fixture()
def db(devices8):
    d = greengage_tpu.connect(numsegments=4)
    d.sql("create table t (k int, a int, v double precision, g int) "
          "distributed by (k)")
    n = 3000
    vals = np.arange(n) * 0.5
    d.load_table("t", {"k": np.arange(n, dtype=np.int32),
                       "a": np.arange(n, dtype=np.int32),
                       "v": vals,
                       "g": np.arange(n, dtype=np.int32) % 7})
    yield d
    faults.reset("batch_dispatch")
    d.close()


def _q(i: int) -> str:
    return f"select count(*), sum(v) from t where a > {i}"


def _rows_match(got, want) -> bool:
    """Row-set equality with FP tolerance: a vmapped program's HLO may
    round differently at the ulp level (e.g. divide vs reciprocal
    multiply) than the classic program — SQL float semantics do not pin
    the associativity, so the oracle compare must not either."""
    if len(got) != len(want):
        return False
    for rg, rw in zip(got, want):
        if len(rg) != len(rw):
            return False
        for a, b in zip(rg, rw):
            if isinstance(a, float) or isinstance(b, float):
                if b != pytest.approx(a, rel=1e-9, abs=1e-12):
                    return False
            elif a != b:
                return False
    return True


def _serve(db, sqls: dict, timeout=60.0):
    """Run each sql on its own thread (the server's one-connection-one-
    thread shape); -> ({key: rows}, {key: exception})."""
    results, errors = {}, {}

    def worker(key, sql):
        try:
            results[key] = db.sql(sql).rows()
        except Exception as e:   # noqa: BLE001 — the assertion surface
            errors[key] = e

    ts = [threading.Thread(target=worker, args=(k, s))
          for k, s in sqls.items()]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=timeout)
    assert not any(t.is_alive() for t in ts), "serving threads hung"
    return results, errors


# ---------------------------------------------------------------------
# (a) demux correctness vs the serial oracle
# ---------------------------------------------------------------------
def test_demux_matches_serial_oracle(db):
    mixed = {
        # int literal spread
        **{f"i{i}": _q(100 + i) for i in range(6)},
        # float literal + projection arithmetic
        "f1": "select k, v * 2.5 from t where v < 10.0 and a >= 3",
        "f2": "select k, v * 7.5 from t where v < 4.0 and a >= 1",
        # ORDER BY + LIMIT exercises per-member merge keys + host trim
        "o1": "select k, v from t where a > 2990 order by v desc",
        "o2": "select k, v from t where a > 2980 order by v desc",
    }
    oracle = {k: db.sql(s).rows() for k, s in mixed.items()}

    db.sql("set batch_serving_enabled = on")
    db.sql("set batch_window_ms = 150")
    for s in mixed.values():
        db.sql(s)   # warm plan cache + width-1 buckets, serially
    # hold the first dispatch so a real multi-member window accumulates
    faults.inject("batch_dispatch", "sleep", sleep_s=0.4, occurrences=1)
    c0 = counters.snapshot()
    results, errors = _serve(db, mixed)
    d = counters.since(c0)
    assert not errors, errors
    for k in mixed:
        assert _rows_match(results[k], oracle[k]), k
    # amortization really happened: fewer dispatches than members
    assert d.get("batch_members_total", 0) > d.get("batch_dispatch_total", 0)
    assert d.get("batch_fallback_total", 0) == 0, d


# ---------------------------------------------------------------------
# (b) one compile per observed pow2 width bucket
# ---------------------------------------------------------------------
def test_compile_once_per_width_bucket(db, jits):
    stmt = parse(_q(100))[0]
    planned, consts, outs, ek = db._cached_plan(stmt)
    pv = consts["@params@"]

    def rows(vals):
        return [ParamVector((v,), pv.types) for v in vals]

    # oracle values FIRST: the first classic execution compiles the
    # classic (width-0) program, which must not count against buckets
    oracle = {v: db.sql(_q(v)).rows()
              for v in (100, 7, 9, 1, 2, 3, 4, 5)}

    n0 = jits["n"]
    res = batchserve.run_batch(db.executor, planned, consts, ek,
                               rows([100, 7, 9]))
    assert jits["n"] == n0 + 1          # bucket 4 compiles once
    for v, r in zip((100, 7, 9), res):
        assert r.rows() == oracle[v]
    c0 = counters.snapshot()
    res = batchserve.run_batch(db.executor, planned, consts, ek,
                               rows([1, 2, 3, 4]))
    assert jits["n"] == n0 + 1, "same bucket must not recompile"
    assert counters.since(c0).get("program_cache_hit", 0) == 1
    for v, r in zip((1, 2, 3, 4), res):
        assert r.rows() == oracle[v]
    batchserve.run_batch(db.executor, planned, consts, ek, rows([5] * 5))
    assert jits["n"] == n0 + 2          # bucket 8 is a new program

    # warm the remaining pow2 buckets (1, 2, 16), then drive 16
    # concurrent same-shape statements through the real pipeline:
    # whatever widths the windows happened to form, every bucket is
    # warm, so the storm must compile NOTHING (counter-verified)
    for w in (1, 2, 16):
        batchserve.run_batch(db.executor, planned, consts, ek,
                             rows([6] * w))
    n_all = jits["n"]
    db.sql("set batch_serving_enabled = on")
    db.sql("set batch_window_ms = 100")
    c0 = counters.snapshot()
    faults.inject("batch_dispatch", "sleep", sleep_s=0.3, occurrences=1)
    results, errors = _serve(db, {i: _q(600 + i) for i in range(16)})
    assert not errors, errors
    d = counters.since(c0)
    assert jits["n"] == n_all, \
        "a warm width bucket must serve every later batch of its width"
    assert d.get("batch_members_total", 0) == 16
    assert d.get("program_cache_miss", 0) == 0, d


# ---------------------------------------------------------------------
# (c) per-member cancellation isolation
# ---------------------------------------------------------------------
def test_member_cancel_leaves_mates_intact(db):
    oracle = {i: db.sql(_q(i)).rows() for i in (300, 301, 302, 303)}
    db.sql("set batch_serving_enabled = on")
    db.sql("set batch_window_ms = 200")
    db.sql(_q(300))   # warm
    # plug: one statement rides a dispatch held on-device by the fault,
    # so the three real members accumulate in the next window
    faults.inject("batch_dispatch", "sleep", sleep_s=0.6, occurrences=1)
    results, errors = {}, {}

    def worker(i):
        try:
            results[i] = db.sql(_q(i)).rows()
        except StatementCancelled as e:
            errors[i] = e.cause

    plug = threading.Thread(target=worker, args=(300,))
    plug.start()
    time.sleep(0.1)
    ts = [threading.Thread(target=worker, args=(i,))
          for i in (301, 302, 303)]
    for t in ts:
        t.start()
    time.sleep(0.15)   # members parked in the window / staged batch
    target = [r for r in REGISTRY.snapshot() if "> 302" in r["sql"]]
    assert target, "member 302 should be in flight"
    assert REGISTRY.cancel(target[0]["id"], "user")
    for t in ts:
        t.join(timeout=30)
    plug.join(timeout=30)
    # the cancelled member died with its typed cause; its batch-mates'
    # results match the serial oracle exactly
    assert errors == {302: "user"}
    for i in (300, 301, 303):
        assert results[i] == oracle[i], i


# ---------------------------------------------------------------------
# (d) window flush reasons: full vs timer
# ---------------------------------------------------------------------
def test_window_flush_full_vs_timer(db):
    db.sql("set batch_serving_enabled = on")
    db.sql("set batch_max_width = 4")
    # a wide window for the FULL-flush phase: the flush must come from
    # the width cap, and a straggling thread start must not let the
    # timer fire first and split the members across two partial windows
    db.sql("set batch_window_ms = 800")
    db.sql(_q(42))   # warm width-1
    try:
        # hold the pipeline so windows accumulate rather than flush idle
        faults.inject("batch_dispatch", "sleep", sleep_s=1.0, occurrences=1)
        plug = threading.Thread(target=db.sql, args=(_q(42),))
        plug.start()
        time.sleep(0.1)
        c0 = counters.snapshot()
        # exactly max_width members: the window must flush FULL (well
        # before its 800 ms deadline — the sleep holds the device)
        results, errors = _serve(db, {i: _q(700 + i) for i in range(4)})
        assert not errors, errors
        d = counters.since(c0)
        assert d.get("batch_window_flush_full", 0) >= 1, d
        plug.join(timeout=30)

        # a partial window behind a busy pipeline flushes on the TIMER
        db.sql("set batch_window_ms = 120")
        faults.inject("batch_dispatch", "sleep", sleep_s=0.5, occurrences=1)
        plug = threading.Thread(target=db.sql, args=(_q(43),))
        plug.start()
        time.sleep(0.1)
        c0 = counters.snapshot()
        results, errors = _serve(db, {i: _q(800 + i) for i in range(2)})
        assert not errors, errors
        d = counters.since(c0)
        assert d.get("batch_window_flush_timer", 0) >= 1, d
        assert d.get("batch_window_flush_full", 0) == 0, d
        plug.join(timeout=30)
    finally:
        db.sql("set batch_max_width = 16")


# ---------------------------------------------------------------------
# (e) pipelining: stage(k+1) overlaps dispatch(k)
# ---------------------------------------------------------------------
def test_pipeline_stage_overlaps_dispatch(db):
    db.sql("set batch_serving_enabled = on")
    db.sql("set batch_max_width = 4")
    db.sql("set batch_window_ms = 60")
    db.sql(_q(0))   # warm
    try:
        # every dispatch sleeps 0.4 s on the "device": while batch k
        # sleeps there, the stager must stage batch k+1
        faults.inject("batch_dispatch", "sleep", sleep_s=0.4,
                      occurrences=-1)
        results, errors = _serve(db, {i: _q(900 + i) for i in range(8)})
        assert not errors, errors
        faults.reset("batch_dispatch")
        batches = [b for b in db._batch_server.recent
                   if b.find_spans("dispatch")]
        assert len(batches) >= 2, "expected at least two flushed batches"

        def absolute(tr, name):
            spans = tr.find_spans(name)
            assert spans, (name, [s["name"] for s in tr.export()])
            s = spans[0]
            start = tr.wall0 + s["ts"] / 1e3
            return start, start + (s["dur"] or 0.0) / 1e3

        # the pipeline property: batch k+1's STAGE begins before batch
        # k's DISPATCH ends (each dispatch holds the device >=0.4 s via
        # the fault, so a serial stage-after-dispatch pipeline could
        # never produce this ordering). Staging that finished even
        # before the next dispatch STARTED is more overlapped, not less
        # — so the assertion is on the stage-start vs dispatch-end edge.
        batches.sort(key=lambda tr: absolute(tr, "dispatch")[0])
        pipelined = False
        for prev, nxt in zip(batches, batches[1:]):
            d0, d1 = absolute(prev, "dispatch")
            s0, _s1 = absolute(nxt, "stage")
            if s0 < d1:
                pipelined = True
        assert pipelined, \
            "every stage serialized behind the previous dispatch"
    finally:
        faults.reset("batch_dispatch")
        db.sql("set batch_max_width = 16")


# ---------------------------------------------------------------------
# (f) the disabled path is untouched
# ---------------------------------------------------------------------
def test_disabled_path_spawns_nothing(db):
    r = db.sql(_q(100))
    assert db._batch_server is None, \
        "batching off must not create the serving pipeline"
    assert "batched" not in (r.stats or {})
    assert r.rows()[0][0] == 2899


def test_fallback_routes_members_to_serial_path(db, monkeypatch):
    """Any overflow flag (value-dependent capacity need, duplicate join
    keys) sends the WHOLE window down the classic serial path: members
    still get correct results, the fallback is counted, and nothing
    surfaces to the client."""
    oracle = {i: db.sql(_q(i)).rows() for i in (400, 401, 402)}
    db.sql("set batch_serving_enabled = on")
    db.sql("set batch_window_ms = 150")
    db.sql(_q(400))   # warm
    monkeypatch.setattr(batchserve, "batch_overflowed",
                        lambda comp, flat: ["join_expand_overflow_0"])
    faults.inject("batch_dispatch", "sleep", sleep_s=0.3, occurrences=1)
    c0 = counters.snapshot()
    results, errors = _serve(db, {i: _q(i) for i in (400, 401, 402)})
    d = counters.since(c0)
    assert not errors, errors
    for i in (400, 401, 402):
        assert results[i] == oracle[i], i
    assert d.get("batch_fallback_total", 0) >= 1, d
    # the serial re-runs landed on the classic (bucket-0) warm program
    assert d.get("batch_members_total", 0) == 0, d


def test_stop_releases_waiting_members(db):
    """BatchServer.stop() (Database.close) must release members parked
    in open windows — each degrades to the classic serial path on its
    own thread instead of waiting out the stall timeout against a dead
    pipeline — and statements issued after stop still serve classically."""
    oracle = {i: db.sql(_q(i)).rows() for i in (500, 501, 502)}
    db.sql("set batch_serving_enabled = on")
    db.sql("set batch_window_ms = 800")
    db.sql(_q(500))   # warm + spawn the pipeline
    faults.inject("batch_dispatch", "sleep", sleep_s=1.0, occurrences=1)
    plug = threading.Thread(target=db.sql, args=(_q(500),))
    plug.start()
    time.sleep(0.1)
    results, errors = {}, {}

    def worker(i):
        try:
            results[i] = db.sql(_q(i)).rows()
        except Exception as e:   # noqa: BLE001
            errors[i] = e

    ts = [threading.Thread(target=worker, args=(i,)) for i in (501, 502)]
    t0 = time.monotonic()
    for t in ts:
        t.start()
    time.sleep(0.2)   # members parked in the open window
    db._batch_server.stop()
    for t in ts:
        t.join(timeout=30)
    plug.join(timeout=30)
    assert not any(t.is_alive() for t in ts)
    assert not errors, errors
    # released promptly (classic re-run), nowhere near the stall timeout
    assert time.monotonic() - t0 < 20
    for i in (501, 502):
        assert results[i] == oracle[i], i
    # post-stop statements still serve (classic path, dead pipeline)
    assert db.sql(_q(502)).rows() == oracle[502]


def test_batched_stats_and_trace_graft(db):
    """A batched member's Result carries the batch stats block and its
    statement trace contains the grafted batch-dispatch subtree."""
    from greengage_tpu.runtime.trace import TRACES

    db.sql("set batch_serving_enabled = on")
    db.sql(_q(55))   # warm; idle pipeline -> immediate width-1 flush
    r = db.sql(_q(56))
    assert r.stats and r.stats.get("batched") is True
    assert r.stats.get("batch_width") == 1
    assert r.stats.get("batch_bucket") == 1
    tr = TRACES.last()
    # the member's own trace shows the whole batch: wait span + grafted
    # batch-dispatch + the member child
    names = {s["name"] for s in tr.export()}
    assert "batch-wait" in names, names
    assert "batch-dispatch" in names, names
    assert "batch-member" in names, names
