"""gg check: plan-invariant validator + codebase analysis suite.

Five layers:
  * plancheck over the REAL TPC-H / TPC-DS plan corpus (every corpus
    statement validates clean; deliberately mutated plans — a dropped
    Motion, a wrong distribution key, an interior Gather — are rejected
    with typed PlanInvariantErrors),
  * the per-statement plan_validate GUC hook,
  * the static analyzers against known-bad fixture snippets (a lock
    cycle, an unpolled wait loop, a tracer-sync violation) plus the
    runtime lock-order hook,
  * the ISSUE-14 thread-topology suite: cross-role race fixtures,
    shipped-tree mutations (a de-locked BlockCache / program LRU, an
    unregistered thread spawn, a dropped plan-cache GUC) that must each
    produce a typed finding, and the runtime access witness,
  * the merge gate itself: `gg check` over the shipped tree is clean.
"""

import dataclasses
import json

import pytest

import greengage_tpu
from greengage_tpu.analysis import astutil
from greengage_tpu.analysis.plancheck import (PlanInvariantError,
                                              validate_capacities,
                                              validate_plan)
from greengage_tpu.analysis.plancorpus import (TPCDS_QUERIES, TPCH_QUERIES,
                                               load_tpcds_mini,
                                               validate_corpus)
from greengage_tpu.planner.locus import Locus, LocusKind
from greengage_tpu.planner.logical import (Aggregate, Join, Motion,
                                           MotionKind)
from greengage_tpu.sql.parser import parse
from greengage_tpu.utils import tpch


@pytest.fixture(scope="module")
def db(devices8):
    d = greengage_tpu.connect(numsegments=8)
    tpch.load(d, sf=0.005)
    d.sql("analyze")
    return d


@pytest.fixture(scope="module")
def dsdb(devices8):
    d = greengage_tpu.connect(numsegments=8)
    load_tpcds_mini(d, n_fact=5_000)
    return d


def _find(plan, pred):
    stack = [plan]
    while stack:
        p = stack.pop()
        if pred(p):
            return p
        stack.extend(p.children)
    return None


# ---------------------------------------------------------------------
# plan corpus: every TPC-H / TPC-DS shape validates clean (I1-I7)
# ---------------------------------------------------------------------

def test_tpch_corpus_validates(db):
    failures = validate_corpus(db, TPCH_QUERIES)
    assert failures == [], failures


def test_tpcds_corpus_validates(dsdb):
    failures = validate_corpus(dsdb, TPCDS_QUERIES)
    assert failures == [], failures


# ---------------------------------------------------------------------
# mutated plans are rejected with typed errors naming the node path
# ---------------------------------------------------------------------

def test_dropped_motion_rejected(db):
    """Splice the state Redistribute out from under Q1's final
    aggregate: partial states stay Strewn, the final merge would
    double-count across segments — plancheck must refuse (I5)."""
    planned, _, _ = db._plan(parse(TPCH_QUERIES["q1_pricing_summary"])[0])
    final = _find(planned, lambda p: isinstance(p, Aggregate)
                  and p.phase == "final")
    moved = final.child
    assert isinstance(moved, Motion) \
        and moved.kind is MotionKind.REDISTRIBUTE
    final.child = moved.child          # the dropped Motion
    with pytest.raises(PlanInvariantError) as ei:
        validate_plan(planned, db.catalog)
    assert ei.value.invariant == "I5"
    assert "Aggregate(final)" in ei.value.path


def test_wrong_dist_key_rejected(db):
    """Re-label a moved join side as hashed on the WRONG key: the join's
    locality claim no longer holds (I4)."""
    planned, _, _ = db._plan(parse(TPCH_QUERIES["q3_shipping_priority"])[0])

    def both_hashed(p):
        return (isinstance(p, Join) and p.left.locus is not None
                and p.right.locus is not None
                and p.left.locus.kind is LocusKind.HASHED
                and p.right.locus.kind is LocusKind.HASHED)

    join = _find(planned, both_hashed)
    assert join is not None, "expected a co-located hashed join in Q3"
    other = [c.id for c in join.right.out_cols()
             if c.id not in join.right.locus.keys]
    join.right.locus = Locus.hashed((other[0],),
                                    join.right.locus.numsegments)
    with pytest.raises(PlanInvariantError) as ei:
        validate_plan(planned, db.catalog)
    assert ei.value.invariant == "I4"


def test_interior_gather_rejected(db):
    planned, _, _ = db._plan(parse(TPCH_QUERIES["q1_pricing_summary"])[0])
    final = _find(planned, lambda p: isinstance(p, Aggregate)
                  and p.phase == "final")
    funnel = Motion(MotionKind.GATHER, final.child)
    funnel.locus = Locus.entry()
    funnel.est_rows = final.child.est_rows
    final.child = funnel
    with pytest.raises(PlanInvariantError) as ei:
        validate_plan(planned, db.catalog)
    assert ei.value.invariant == "I3"


def test_bad_prune_predicate_rejected(db):
    planned, _, _ = db._plan(
        parse("select count(*) from orders where o_orderkey > 7")[0])
    scan = _find(planned, lambda p: getattr(p, "prune_preds", ()))
    assert scan is not None
    scan.prune_preds = (("no_such_column", ">", 7),)
    with pytest.raises(PlanInvariantError) as ei:
        validate_plan(planned, db.catalog)
    assert ei.value.invariant == "I6"


def test_capacity_bucketing_enforced(db):
    """I7 negative: a compiler whose scan bucketing is broken (returns a
    non-pow2 capacity) must be refused."""
    from greengage_tpu.exec.compile import Compiler

    planned, consts, _ = db._plan(
        parse("select count(*) from lineitem")[0])
    comp = Compiler(db.catalog, db.store, db.mesh, db.numsegments,
                    consts, db.settings)
    validate_capacities(comp, planned)   # the honest compiler passes
    comp2 = Compiler(db.catalog, db.store, db.mesh, db.numsegments,
                     consts, db.settings)
    comp2._bucket_cap = lambda table, cap: max(cap, 1) * 3   # de-bucketed
    with pytest.raises(PlanInvariantError) as ei:
        validate_capacities(comp2, planned)
    assert ei.value.invariant == "I7"


def test_window_ordered_global_spec_enforced(db):
    """I5 negative (ISSUE 12): an ordered-global window stripped of its
    gkey_spec — or carrying an over-budget packed spec — is refused."""
    from greengage_tpu.planner.logical import Window

    q = ("select o_orderkey, ntile(4) over (order by o_orderkey) nt "
         "from orders")
    planned, _, _ = db._plan(parse(q)[0])
    win = _find(planned, lambda p: isinstance(p, Window))
    assert win is not None and win.global_mode == "ordered"
    validate_plan(planned, db.catalog)
    spec = win.gkey_spec
    win.gkey_spec = None
    with pytest.raises(PlanInvariantError) as ei:
        validate_plan(planned, db.catalog)
    assert ei.value.invariant == "I5"
    # over-budget packed fields: the uint64 claim is false
    win.gkey_spec = {"mode": "packed",
                     "fields": [dict(f, bits=40) for f in spec["fields"]]
                     + [dict(spec["fields"][0], bits=40)]}
    with pytest.raises(PlanInvariantError) as ei:
        validate_plan(planned, db.catalog)
    assert ei.value.invariant == "I5"


def test_window_global_above_funnel_rejected(db):
    """I3 negative: a global-mode window sitting above a SingleQE funnel
    claims gather-freedom it does not have."""
    from greengage_tpu import expr as E
    from greengage_tpu import types as T
    from greengage_tpu.planner.locus import Locus as L
    from greengage_tpu.planner.logical import Window

    q = ("select o_orderkey, ntile(4) over (order by o_orderkey) nt "
         "from orders")
    planned, _, _ = db._plan(parse(q)[0])
    win = _find(planned, lambda p: isinstance(p, Window))
    funnel = Motion(MotionKind.REDISTRIBUTE, win.child,
                    hash_exprs=[E.Literal(0, T.INT64)])
    funnel.locus = L(LocusKind.SINGLE_QE, (), db.numsegments)
    funnel.est_rows = win.child.est_rows
    win.child = funnel
    with pytest.raises(PlanInvariantError) as ei:
        validate_plan(planned, db.catalog)
    assert ei.value.invariant == "I3"


def test_window_range_mode_needs_range_motion(db):
    """I5 negative: a range-mode window whose child lost its range
    Redistribute no longer owns whole key ranges."""
    from greengage_tpu.planner.logical import Window

    q = ("select o_orderkey, sum(o_totalprice) over "
         "(order by o_totalprice, o_orderkey) rs from orders")
    planned, _, _ = db._plan(parse(q)[0])
    win = _find(planned, lambda p: isinstance(p, Window))
    assert win is not None and win.global_mode == "range", win
    validate_plan(planned, db.catalog)
    moved = win.child
    assert isinstance(moved, Motion) and moved.range_spec is not None
    win.child = moved.child          # splice the range motion out
    with pytest.raises(PlanInvariantError) as ei:
        validate_plan(planned, db.catalog)
    assert ei.value.invariant == "I5"
    # a range Redistribute claiming a HASHED landing is an I2 violation
    win.child = moved
    moved.locus = Locus.hashed((moved.hash_exprs[0].name,),
                               db.numsegments)
    with pytest.raises(PlanInvariantError) as ei:
        validate_plan(planned, db.catalog)
    assert ei.value.invariant == "I2"


# ---------------------------------------------------------------------
# the plan_validate GUC hook
# ---------------------------------------------------------------------

def test_plan_validate_guc_hook(db, monkeypatch):
    import greengage_tpu.exec.session as S

    calls = []
    orig = S.validate_plan
    monkeypatch.setattr(
        S, "validate_plan",
        lambda p, cat=None: (calls.append(1), orig(p, cat))[1])
    db.sql("select count(*) + 17 from region")   # unique: forces a plan
    assert calls, "plan_validate on: _plan must run the validator"
    calls.clear()
    db.sql("set plan_validate = off")
    try:
        db.sql("select count(*) + 18 from region")
        assert not calls, "plan_validate off: validator must not run"
    finally:
        db.sql("set plan_validate = on")


# ---------------------------------------------------------------------
# static analyzers against known-bad fixtures
# ---------------------------------------------------------------------

def _sources(tmp_path, files: dict):
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(text)
    return astutil.SourceSet(roots=[str(tmp_path)])


def test_lock_cycle_detected(tmp_path):
    from greengage_tpu.analysis import lint_locks

    src = _sources(tmp_path, {"lockmod.py": (
        "import threading\n"
        "a = threading.Lock()\n"
        "b = threading.Lock()\n"
        "def f():\n"
        "    with a:\n"
        "        with b:\n"
        "            pass\n"
        "def g():\n"
        "    with b:\n"
        "        with a:\n"
        "            pass\n")})
    rep = lint_locks.run(src)
    assert len(rep.findings) == 1
    assert "lock-order cycle" in rep.findings[0].message


def test_lock_order_consistent_is_clean(tmp_path):
    from greengage_tpu.analysis import lint_locks

    src = _sources(tmp_path, {"lockmod.py": (
        "import threading\n"
        "a = threading.Lock()\n"
        "b = threading.Lock()\n"
        "def f():\n"
        "    with a:\n"
        "        with b:\n"
        "            pass\n"
        "def g():\n"
        "    with a:\n"
        "        with b:\n"
        "            pass\n")})
    assert lint_locks.run(src).findings == []


def test_lock_cycle_through_call_detected(tmp_path):
    """One interprocedural hop: f holds A and calls helper() which takes
    B; g nests them the other way round."""
    from greengage_tpu.analysis import lint_locks

    src = _sources(tmp_path, {"lockmod.py": (
        "import threading\n"
        "a = threading.Lock()\n"
        "b = threading.Lock()\n"
        "def helper_take_b():\n"
        "    with b:\n"
        "        pass\n"
        "def f():\n"
        "    with a:\n"
        "        helper_take_b()\n"
        "def g():\n"
        "    with b:\n"
        "        with a:\n"
        "            pass\n")})
    rep = lint_locks.run(src)
    assert len(rep.findings) == 1


def test_unpolled_wait_loop_detected(tmp_path):
    from greengage_tpu.analysis import lint_interrupts

    bad = ("import time\n"
           "def waiter(ready):\n"
           "    while not ready():\n"
           "        time.sleep(0.1)\n")
    good = ("import time\n"
            "from greengage_tpu.runtime.interrupt import check_interrupts\n"
            "def waiter(ready):\n"
            "    while not ready():\n"
            "        check_interrupts()\n"
            "        time.sleep(0.1)\n")
    rep = lint_interrupts.run(_sources(tmp_path / "bad", {"w.py": bad}))
    assert [f.key for f in rep.findings] == ["waiter:sleep-loop"]
    rep = lint_interrupts.run(_sources(tmp_path / "good", {"w.py": good}))
    assert rep.findings == []


def test_unpolled_condition_wait_detected(tmp_path):
    from greengage_tpu.analysis import lint_interrupts

    src = _sources(tmp_path, {"w.py": (
        "def admit(cond, full):\n"
        "    with cond:\n"
        "        while full():\n"
        "            cond.wait()\n")})
    rep = lint_interrupts.run(src)
    assert [f.key for f in rep.findings] == ["admit:condition-wait"]


def test_tracer_sync_violation_detected(tmp_path):
    from greengage_tpu.analysis import lint_tracer

    src = _sources(tmp_path, {"ops/kern.py": (
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "def bad(vals):\n"
        "    ident = jnp.array(0, vals.dtype)\n"     # the PR-5 bug class
        "    return ident.item()\n"
        "def good(vals):\n"
        "    ident = np.array(0, vals.dtype)\n"      # host-concrete: the fix
        "    return ident.item()\n"
        "def also_bad(vals):\n"
        "    s = jnp.sum(vals)\n"
        "    return float(s)\n")})
    rep = lint_tracer.run(src)
    keys = sorted(f.key for f in rep.findings)
    assert len(keys) == 2
    assert any("bad" in k and ".item()" in k for k in keys)
    assert any("also_bad" in k and "float()" in k for k in keys)


def test_tracer_lint_covers_scalar_library():
    """ISSUE 13: the device scalar library (ops/scalar.py) is inside the
    tracer lint's jit-traced scope — its byte-window/date kernels run
    under trace, so a host sync there is the PR-5 bug class. Guard the
    scope (the /ops/ glob must keep matching it) and its cleanliness."""
    from greengage_tpu.analysis import astutil, lint_tracer

    sources = astutil.SourceSet()
    rels = {s.rel.replace("\\", "/") for s in sources}
    assert any(r.endswith("ops/scalar.py") for r in rels), \
        sorted(r for r in rels if "/ops/" in r)
    rep = lint_tracer.run(sources)
    scalar_findings = [f for f in rep.findings
                       if f.path.endswith("ops/scalar.py")]
    assert scalar_findings == [], scalar_findings


def test_lockdebug_runtime_inversion():
    import threading

    from greengage_tpu.runtime import lockdebug

    prior = lockdebug.enabled()   # conftest enables suite-wide: restore,
    lockdebug.enable(True)        # never hard-disable for later tests
    try:
        a = lockdebug.named(threading.Lock(), "A")
        b = lockdebug.named(threading.Lock(), "B")
        with a:
            with b:
                pass
        with pytest.raises(lockdebug.LockOrderError):
            with b:
                with a:
                    pass
    finally:
        lockdebug.enable(prior)
        lockdebug.reset()   # drop this test's A->B edge from the table


# ---------------------------------------------------------------------
# the merge gate: the shipped tree is clean, and the CLI surfaces it
# ---------------------------------------------------------------------

def test_gg_check_shipped_tree_clean():
    from greengage_tpu.analysis.runner import run_checks

    rep = run_checks()
    assert rep.findings == [], rep.to_text()


def test_gg_check_cli_json():
    import io
    from contextlib import redirect_stdout

    from greengage_tpu.mgmt import cli

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cli.main(["check", "--json"])
    assert rc == 0
    payload = json.loads(buf.getvalue())
    assert payload["clean"] is True and payload["findings"] == []


def test_baseline_suppression(tmp_path):
    from greengage_tpu.analysis.report import Report, load_baseline

    rep = Report()
    rep.add("locks", "x.py", 3, "cycle:a>b", "boom")
    bl = tmp_path / "baseline.txt"
    bl.write_text("# comment\nlocks x.py::cycle:a>b\n")
    out = rep.suppressed(load_baseline(str(bl)))
    assert out.findings == []
    out2 = rep.suppressed(load_baseline(str(tmp_path / "missing.txt")))
    assert len(out2.findings) == 1


# ---------------------------------------------------------------------
# ISSUE 14: thread-topology race analysis (threads + races checks) and
# the runtime access witness — all pure-AST / host-only
# ---------------------------------------------------------------------

def _two_roles(entries_a, entries_b):
    from greengage_tpu.analysis.threadmodel import Role

    return {
        "alpha": Role("alpha", "fixture role A", (), tuple(entries_a)),
        "beta": Role("beta", "fixture role B", (), tuple(entries_b)),
    }


_RACY = (
    "import threading\n"
    "lock = threading.Lock()\n"
    "state = {}\n"
    "def writer_loop():\n"
    "    state['x'] = 1\n"
    "def reader_loop():\n"
    "    return state.get('x')\n")

_LOCKED = (
    "import threading\n"
    "lock = threading.Lock()\n"
    "state = {}\n"
    "def writer_loop():\n"
    "    with lock:\n"
    "        state['x'] = 1\n"
    "def reader_loop():\n"
    "    with lock:\n"
    "        return state.get('x')\n")


def test_cross_role_bare_write_detected(tmp_path):
    from greengage_tpu.analysis import lint_races

    src = _sources(tmp_path, {"racemod.py": _RACY})
    roles = _two_roles([("racemod.py", "", "writer_loop")],
                       [("racemod.py", "", "reader_loop")])
    rep = lint_races.run(src, roles=roles)
    assert len(rep.findings) == 1, rep.to_text()
    f = rep.findings[0]
    assert f.check == "races" and "racemod.state" in f.key
    # the typed finding carries BOTH access paths and names both roles
    assert "alpha" in f.message and "beta" in f.message
    assert f.message.count("racemod.py:") == 2


def test_cross_role_locked_and_single_role_clean(tmp_path):
    from greengage_tpu.analysis import lint_races

    src = _sources(tmp_path / "locked", {"racemod.py": _LOCKED})
    roles = _two_roles([("racemod.py", "", "writer_loop")],
                       [("racemod.py", "", "reader_loop")])
    assert lint_races.run(src, roles=roles).findings == []
    # same bare write, but only ONE role ever touches it: clean (the
    # analyzer is cross-role by design; intra-role races are the lock
    # lint's and the session's domain)
    src2 = _sources(tmp_path / "single", {"racemod.py": _RACY})
    roles2 = _two_roles([("racemod.py", "", "writer_loop"),
                         ("racemod.py", "", "reader_loop")], [])
    assert lint_races.run(src2, roles=roles2).findings == []


def _mutated(sources, rel_suffix, old, new):
    import ast as _ast

    src = sources.get(rel_suffix)
    text = src.text.replace(old, new)
    assert text != src.text, f"mutation anchor drifted in {rel_suffix}"
    src.text = text
    src.tree = _ast.parse(text)
    src.lines = text.splitlines()
    return sources


def test_mutation_unlocked_blockcache_read_flagged():
    """Strip the registry lock from BlockCache.get: the races check must
    name the structure and two real roles (staging pool vs statement /
    serving pipeline all reach the block cache)."""
    from greengage_tpu.analysis import lint_races

    src = astutil.SourceSet(exclude=("greengage_tpu/analysis/",))
    _mutated(src, "storage/blockcache.py",
             "        with reg._lock:\n            ent = self._d.get(key)",
             "        if True:\n            ent = self._d.get(key)")
    rep = lint_races.run(src)
    hit = [f for f in rep.findings if "BlockCache._d" in f.key]
    assert hit, rep.to_text()
    assert "written by role" in hit[0].message \
        and "no common lock" in hit[0].message


def test_mutation_unlocked_program_lru_flagged():
    """Strip _cache_mu from the program-LRU insert: the races check must
    flag _plan_cache between the serving stager and statement threads."""
    from greengage_tpu.analysis import lint_races

    src = astutil.SourceSet(exclude=("greengage_tpu/analysis/",))
    _mutated(src, "exec/programs.py",
             "        with self._cache_mu:\n"
             "            self._plan_cache[ck] = comp",
             "        if True:\n"
             "            self._plan_cache[ck] = comp")
    rep = lint_races.run(src)
    hit = [f for f in rep.findings if "ProgramCache._plan_cache" in f.key]
    assert hit, rep.to_text()


def test_thread_hygiene_both_ways():
    from greengage_tpu.analysis import threadmodel

    # shipped tree: every spawn site modelled, every model row live
    src = astutil.SourceSet(exclude=("greengage_tpu/analysis/",))
    rep = threadmodel.run(src)
    assert rep.findings == [], rep.to_text()
    assert rep.notes["thread_spawn_sites"] >= 12
    # an unregistered spawn site is a finding
    src2 = astutil.SourceSet(exclude=("greengage_tpu/analysis/",))
    _mutated(src2, "runtime/fts.py",
             "    def stop(self) -> None:",
             "    def rogue(self):\n"
             "        threading.Thread(target=self.probe_once).start()\n\n"
             "    def stop(self) -> None:")
    rep2 = threadmodel.run(src2)
    assert any("unregistered-spawn" in f.key for f in rep2.findings), \
        rep2.to_text()


def test_plan_cache_guc_lint_mutation():
    """ISSUE 14 satellite: dropping a binding-read GUC from the SET
    handler's _select_cache.clear() tuple is a finding; so is a tuple
    entry the binding path no longer reads."""
    from greengage_tpu.analysis import lint_registry

    src = astutil.SourceSet()
    _mutated(src, "exec/session.py",
             'if stmt.name in ("optimizer", "plan_cache_params",',
             'if stmt.name in ("plan_cache_params",')
    rep = lint_registry.run(src)
    assert any(f.key == "plan-cache-guc-unclears:optimizer"
               for f in rep.findings), rep.to_text()
    src2 = astutil.SourceSet()
    _mutated(src2, "exec/session.py",
             'if stmt.name in ("optimizer", "plan_cache_params",',
             'if stmt.name in ("optimizer", "motion_retry_tiers", '
             '"plan_cache_params",')
    rep2 = lint_registry.run(src2)
    assert any(f.key == "plan-cache-guc-stale:motion_retry_tiers"
               for f in rep2.findings), rep2.to_text()


def test_queue_get_timeout_and_thread_join_detected(tmp_path):
    """ISSUE 14 satellite: the PR-11 ready-queue wait (`.get(timeout=)`
    on any receiver) and the PR-12 prefetcher drain (`.join(timeout=)`
    on a thread) are blocking waits; polling variants are clean."""
    from greengage_tpu.analysis import lint_interrupts

    bad = ("def pump(dq):\n"
           "    while True:\n"
           "        item = dq.get(timeout=0.25)\n"
           "def drain(worker_thread):\n"
           "    worker_thread.join(timeout=60.0)\n")
    good = ("def pump(dq, ctx):\n"
            "    while True:\n"
            "        ctx.check()\n"
            "        item = dq.get(timeout=0.25)\n"
            "def drain(worker_thread, ctx):\n"
            "    if not ctx.cancelled:\n"
            "        worker_thread.join(timeout=60.0)\n")
    rep = lint_interrupts.run(_sources(tmp_path / "bad", {"w.py": bad}))
    assert sorted(f.key for f in rep.findings) == \
        ["drain:thread-join", "pump:queue-get"], rep.to_text()
    rep2 = lint_interrupts.run(_sources(tmp_path / "good", {"w.py": good}))
    assert rep2.findings == []


def test_race_witness_runtime():
    """The dynamic half: an injected bare cross-role access under the
    armed witness raises RaceWitnessError naming both roles; the same
    access under a common named lock is clean."""
    import threading

    from greengage_tpu.runtime import lockdebug

    prior = lockdebug.races_enabled()
    lockdebug.enable_races(True)
    try:
        c = lockdebug.shared({}, "test.witness")
        mu = lockdebug.named(threading.Lock(), "test.witness_mu")
        c["x"] = 1               # statement role (MainThread), bare
        got = []

        def bare():
            try:
                c["x"] = 2       # fts role by thread name, bare: races
            except lockdebug.RaceWitnessError as e:
                got.append(e)
        t = threading.Thread(target=bare, name="fts-prober")
        t.start()
        t.join()
        assert got and "fts" in str(got[0]) and "statement" in str(got[0])

        c2 = lockdebug.shared({}, "test.witness_locked")
        with mu:
            c2["x"] = 1
        ok = []

        def locked():
            with mu:
                c2["x"] = 2
            ok.append(True)
        t2 = threading.Thread(target=locked, name="fts-prober")
        t2.start()
        t2.join()
        assert ok, "common named lock must satisfy the witness"
    finally:
        lockdebug.enable_races(prior)


def test_gg_check_list_catalog():
    """`gg check --list` prints every registered check (threads/races
    included) with per-check finding counts; clean tree exits 0."""
    import io
    from contextlib import redirect_stdout

    from greengage_tpu.mgmt import cli

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cli.main(["check", "--list", "--json"])
    assert rc == 0
    payload = json.loads(buf.getvalue())
    names = {r["check"] for r in payload["checks"]}
    assert {"threads", "races", "locks", "interrupts", "registry",
            "tracer", "imports"} <= names
    assert all(r["findings"] == 0 for r in payload["checks"]), payload
