"""Parameterized plan + executable cache (ISSUE 5): recompile-count
regression tests. XLA compiles are counted by monkeypatching the
jax.jit wrap in exec/compile.py — one jit() call per compiled program —
so the assertions are deterministic (never wall clocks).

The contract under test (docs/PERF.md "Plan cache"):
  (a) two SELECTs differing only in hoistable literals compile ONCE and
      both return value-correct results;
  (b) a DML that stays inside every capacity bucket does not invalidate
      the cached executable;
  (c) unsafe literals (partition-prune keys, LIMIT counts) correctly
      miss the cache — planning-relevant values never generalize.
"""

import numpy as np
import pytest

import greengage_tpu
import greengage_tpu.exec.compile as C
from greengage_tpu.runtime.logger import counters


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
    # pin the cache micro-contract in isolation: the self-tuning loop
    # (planner/feedback.py) deliberately re-plans a shape ONCE after a
    # calibration promotion, which would perturb the exact hit counts
    # asserted here; tests/test_feedback.py owns that interplay
    d.set("cost_feedback", False)
    d.sql("create table t (k int, a int, v double precision) "
          "distributed by (k)")
    d.load_table("t", {"k": np.arange(3000, dtype=np.int32),
                       "a": np.arange(3000, dtype=np.int32),
                       "v": np.arange(3000) * 0.5})
    return d


def test_repeated_shape_compiles_once(db, jits):
    """(a) Different hoistable literals: one plan, one executable, and
    value-correct results for every binding."""
    r1 = db.sql("select count(*) from t where a > 100")
    n1 = jits["n"]
    assert n1 >= 1 and r1.rows()[0][0] == 2899
    c0 = counters.snapshot()
    r2 = db.sql("select count(*) from t where a > 2000")
    r3 = db.sql("select count(*) from t where a > 100")
    assert jits["n"] == n1, "literal-only change must not recompile"
    assert r2.rows()[0][0] == 999
    assert r3.rows()[0][0] == 2899
    d = counters.since(c0)
    assert d.get("plan_cache_hit", 0) == 2
    assert d.get("program_cache_hit", 0) == 2
    assert not d.get("program_cache_miss")
    assert r2.stats["compiled"] is False
    assert r2.stats["plan_cache"] == {"hit": True, "params": 1,
                                      "fallback": False}


def test_float_and_arith_literals_hoist(db, jits):
    r1 = db.sql("select k, v * 2.5 from t where v < 10.0 and a >= 3")
    n1 = jits["n"]
    r2 = db.sql("select k, v * 7.5 from t where v < 4.0 and a >= 1")
    assert jits["n"] == n1
    assert len(r1) == 17 and len(r2) == 7
    vals = sorted(x[1] for x in r2.rows())
    assert vals[0] == pytest.approx(0.5 * 7.5)   # row a=1: v=0.5 -> 3.75


def test_dml_within_bucket_keeps_executable(db, jits):
    """(b) An INSERT that stays inside the pow2 capacity bucket re-binds
    the plan (manifest version moved) but REUSES the compiled program."""
    r1 = db.sql("select count(*), sum(v) from t where a > 10")
    n1 = jits["n"]
    assert r1.stats["compiled"] is True
    # 3000 rows / 4 segs ~ 750/seg -> bucket 1024; a handful more stays in
    db.sql("insert into t values (90001, 90001, 1.0)")
    r2 = db.sql("select count(*), sum(v) from t where a > 10")
    assert jits["n"] == n1, "within-bucket DML must not recompile"
    assert r2.stats["compiled"] is False
    assert r2.rows()[0][0] == r1.rows()[0][0] + 1   # sees the new row


def test_unsafe_literals_miss(devices8, jits):
    """(c) Partition-prune keys and LIMIT counts stay pinned: a changed
    value is a different cache entry (and a fresh compile)."""
    db = greengage_tpu.connect(numsegments=4)
    db.sql("create table pt (d int, m int) distributed by (m) "
           "partition by range (d) "
           "(partition p1 start (0) end (100), "
           " partition p2 start (100) end (200))")
    db.load_table("pt", {"d": np.arange(200, dtype=np.int32),
                         "m": np.arange(200, dtype=np.int32)})
    r1 = db.sql("select count(*) from pt where d < 50")
    n1 = jits["n"]
    r2 = db.sql("select count(*) from pt where d < 150")
    assert jits["n"] > n1, "partition-key literal must not generalize"
    assert r1.rows()[0][0] == 50 and r2.rows()[0][0] == 150
    # static pruning stayed value-exact: one child staged vs two
    assert r1.stats["partitions"]["pt"] == 1
    assert r2.stats["partitions"]["pt"] == 2
    # LIMIT is part of the shape
    db.sql("select m from pt limit 5")
    n2 = jits["n"]
    r = db.sql("select m from pt limit 7")
    assert jits["n"] > n2 and len(r) == 7


def test_distkey_equality_pinned_direct_dispatch(db, jits):
    """Equality on the hash-distribution key keeps direct dispatch (a
    value-generic plan would have to stage every segment)."""
    r1 = db.sql("select v from t where k = 17")
    r2 = db.sql("select v from t where k = 23")
    assert r1.stats["direct_dispatch"].get("t") is not None
    assert r2.stats["direct_dispatch"].get("t") is not None
    assert r1.rows()[0][0] == 8.5 and r2.rows()[0][0] == 11.5


def test_signature_covers_unpinned_capacity_merge(devices8, jits):
    """Conflicting direct pins (two point-scans naming different segments)
    disable direct dispatch, and compile() raises the staged capacity to
    cover EVERY segment; shape_signature must digest that same post-merge
    capacity, so DML growing a NON-pinned segment past its pow2 bucket
    recompiles instead of reusing a too-small executable."""
    db = greengage_tpu.connect(numsegments=4)
    db.sql("create table u (k int, v int) distributed by (k)")
    schema = db.catalog.get("u")

    def seg_of(kv):
        return db.store.segment_for_values(schema, {"k": kv})

    k0 = 0
    k1 = next(k for k in range(1, 64) if seg_of(k) != seg_of(k0))
    other = next(s for s in range(4) if s not in (seg_of(k0), seg_of(k1)))
    kb = next(k for k in range(64, 4096) if seg_of(k) == other)
    # the bulk segment sits exactly AT a pow2 bucket boundary (128)
    ks = np.array([k0] * 4 + [k1] * 4 + [kb] * 128, dtype=np.int32)
    db.load_table("u", {"k": ks, "v": np.ones(len(ks), dtype=np.int32)})

    q = (f"select count(*) c from u where k = {k0} "
         f"union all select count(*) c from u where k = {k1}")
    r1 = db.sql(q)
    assert r1.rows() == [(4,), (4,)]
    n1 = jits["n"]
    db.sql(q)
    assert jits["n"] == n1, "repeated conflicting-pin shape must reuse"
    # grow the NON-pinned bulk segment 128 -> 129: crosses the bucket the
    # pinned segments never see, so the cached executable is too small
    db.sql(f"insert into u values ({kb}, 1)")
    n2 = jits["n"]
    r3 = db.sql(q)
    assert r3.rows() == [(4,), (4,)]
    assert jits["n"] > n2, \
        "bucket cross on a non-pinned segment must recompile"


def test_zone_prune_resolves_param_values(devices8):
    """A hoisted literal still drives zone-map pruning — resolved at
    staging time — and pruning follows the CURRENT value, not the value
    that populated the cache."""
    db = greengage_tpu.connect(numsegments=2)
    db.set("cost_feedback", False)   # see the db fixture note
    db.sql("create table zt (k int, a int) distributed by (k)")
    # loaded in 'a' order: each segment's ~3 blocks (65536 rows each) get
    # tight zone ranges, so a selective value prunes
    n = 400_000
    db.load_table("zt", {"k": np.arange(n, dtype=np.int32),
                         "a": np.arange(n, dtype=np.int32)})
    r1 = db.sql("select count(*) from zt where a >= 399000")
    r2 = db.sql("select count(*) from zt where a >= 500")   # cache hit
    assert r1.rows()[0][0] == 1000
    assert r2.rows()[0][0] == n - 500, "stale prune value would drop rows"
    assert r2.stats["plan_cache"]["hit"] is True
    zp1 = r1.stats["zone_prune"]["zt"]
    zp2 = r2.stats["zone_prune"]["zt"]
    # the selective value pruned strictly more blocks than the broad one
    assert zp1[1] > 2 and zp1[0] < zp2[0], (zp1, zp2)


def test_plan_cache_lru_and_hint_lifetime(db):
    """Satellites: real LRU (not FIFO) in both caches, bounded by the
    plan_cache_size GUC; cap-hint bookkeeping dies with the last
    program of its statement."""
    db.sql("set plan_cache_size = 2")
    db.sql("select count(*) from t where a > 1")          # shape A
    db.sql("select sum(v) from t where a > 2")            # shape B
    db.sql("select count(*) from t where a > 3")          # touch A (LRU)
    db.sql("select max(a) from t where v < 9.0")          # shape C evicts B
    assert len(db.executor.programs._plan_cache) <= 2
    c0 = counters.snapshot()
    db.sql("select count(*) from t where a > 4")          # A again
    assert counters.since(c0).get("program_cache_hit", 0) == 1, \
        "LRU must have kept the recently-touched shape A"
    # bookkeeping for statements no longer cached is dropped
    live = {k[0] for k in db.executor.programs._plan_cache}
    assert set(db.executor.programs._cap_hints) <= live
    db.sql("set plan_cache_size = 256")


def test_plan_cache_params_off(db, jits):
    """The GUC restores classic value-pinned behavior."""
    db.sql("set plan_cache_params = off")
    db.sql("select count(*) from t where a > 7")
    n1 = jits["n"]
    db.sql("select count(*) from t where a > 8")
    assert jits["n"] > n1
    db.sql("set plan_cache_params = on")


def test_explain_analyze_reports_plan_cache(db):
    db.sql("select count(*) from t where a > 42")
    r = db.sql("explain analyze select count(*) from t where a > 43")
    line = [ln for ln in r.plan_text.split("\n") if "Plan cache" in ln]
    assert line and "hit" in line[0] and "params hoisted" in line[0]
