"""An inner join whose matches fit 1/32 of its probe slots compacts its
matched probe rows, and their build rows, before it gathers the build
columns (`exec/compile.Compiler._join_compact_k`): the gathers and the
nodes above it pay for the compacted slots. A compaction that overflows
runs again at the exact count on the same tier; LEFT, semi and multi joins
never compact. CPU: answers and counts, never a time."""

import numpy as np
import pytest

import greengage_tpu
from greengage_tpu.planner.logical import Join
from greengage_tpu.runtime.logger import counters
from greengage_tpu.sql.parser import parse

N = 1 << 16              # probe rows, keys 0 .. N - 1
MANY, FEW = 8192, 64     # build rows of `fm` and `fs`, keys 0 .. n - 1

SQL = {
    "inner": "select count(*), sum(w) from fq join fs on fq.k = fs.k",
    "left": "select count(*), count(w) from fq left join fs on fq.k = fs.k",
    "semi": "select count(*) from fq where k in (select k from fs)",
    "multi": "select count(*), sum(a.w) from fd a join fd b on a.k = b.k",
}
WANT = {"inner": [(FEW, FEW * (FEW - 1) // 2)], "left": [(N, FEW)],
        "semi": [(FEW,)], "multi": [(4 * FEW, 2 * FEW * (FEW - 1))]}


@pytest.fixture(scope="module")
def dbs(devices8):
    out = {}
    for nseg in (1, 4):
        db = greengage_tpu.connect(numsegments=nseg)
        db.sql("create table fq (k bigint, v int) distributed by (k)")
        for t in ("fm", "fs"):
            db.sql(f"create table {t} (k bigint, w int) distributed by (k)")
        # a distribution key reads as unique to the planner: not this one
        db.sql("create table fd (k bigint, w int) distributed by (w)")
        keys = np.arange(N, dtype=np.int64)
        db.load_table("fq", {"k": keys, "v": keys.astype(np.int32)})
        for t, n in (("fm", MANY), ("fs", FEW)):
            db.load_table(t, {"k": keys[:n], "w": keys[:n].astype(np.int32)})
        # every key twice: the planner cannot show the build side unique
        db.load_table("fd", {"k": np.tile(keys[:FEW], 2),
                             "w": np.tile(keys[:FEW], 2).astype(np.int32)})
        db.sql("analyze")
        out[nseg] = db
    yield out
    for db in out.values():
        db.close()


def _dispatched(db, monkeypatch) -> list:
    """The programs the executor dispatches from here on, in order."""
    ex, comps = db.executor, []
    dispatch = ex.dispatch

    def spy(comp, *a, **k):
        comps.append(comp)
        return dispatch(comp, *a, **k)
    monkeypatch.setattr(ex, "dispatch", spy)
    return comps


def _compactions(comp) -> list:
    return [f for f in comp.flag_names if f.startswith("compact_overflow")]


def _probe_cap(db) -> int:
    """The probe side's capacity: `fq`'s fullest segment, pow2."""
    per = db.store.segment_rowcounts("fq", db.store.manifest.snapshot())
    return 1 << (max(per) - 1).bit_length()


@pytest.mark.parametrize("nseg", [1, 4])
@pytest.mark.parametrize("kind", ["inner", "left", "semi", "multi"])
def test_only_an_inner_join_compacts_its_matches(dbs, monkeypatch, kind, nseg):
    """The same 64 build keys against 2^16 probe rows: the inner join's
    matches fit 1/32 of the probe slots, and it alone compacts them and
    gathers the build columns into those slots. The LEFT join keeps its
    null-extended rows at the probe's capacity, the semi join gathers
    nothing, and the duplicate-key table joined with itself (a multi join)
    gathers into its expansion."""
    db = dbs[nseg]
    comps = _dispatched(db, monkeypatch)
    c0 = counters.snapshot()
    r = db.sql(SQL[kind])
    d = counters.since(c0)
    assert r.rows() == WANT[kind] and r.stats["tiers_used"] == 1
    # the multi join's expansion may run again at its exact pair count
    comp, cap = comps[-1], _probe_cap(db)
    if kind == "inner":
        assert len(comps) == 1 and len(_compactions(comp)) == 1
        assert comp.join_gather_slots == (cap // 32,)
    else:
        assert all(_compactions(c) == [] for c in comps)
        assert comp.join_gather_slots == {
            "left": (cap,), "semi": (),
            "multi": tuple(c for c, _n in comp.expand_caps.values())}[kind]
    assert d.get("join_gather_slots", 0) == sum(comp.join_gather_slots)


@pytest.mark.parametrize("nseg", [1, 4])
def test_an_overflowing_join_compaction_runs_again_at_the_exact_count(
        dbs, monkeypatch, nseg):
    """An estimate of one row for a join that matches 8,192: the compaction
    into 1/32 of the probe slots drops rows and says so. The attempt runs
    again on the same tier with the exact count under the join's
    compaction override, which no longer fits: the join gathers into the
    probe's slots, and the answer is whole."""
    db = dbs[nseg]
    sql = "select count(*), sum(w) from fq join fm on fq.k = fm.k"
    planned, consts, outs, _key = db._cached_plan(parse(sql)[0])
    stack, joins = [planned], []
    while stack:
        p = stack.pop()
        stack.extend(p.children)
        joins += [p] if isinstance(p, Join) else []
    join, = joins
    monkeypatch.setattr(join, "est_rows", 1.0)
    ex, grown = db.executor, []
    grow = ex._grow

    def spy(st, comp, overflow, metrics, tier):
        nxt = grow(st, comp, overflow, metrics, tier)
        grown.append((list(overflow), comp, metrics, tier, nxt,
                      dict(st.cap_overrides)))
        return nxt
    monkeypatch.setattr(ex, "_grow", spy)
    comps = _dispatched(db, monkeypatch)
    r = ex.run(planned, consts, outs)
    assert r.rows() == [(MANY, MANY * (MANY - 1) // 2)]
    assert r.stats["tiers_used"] == 1
    (overflow, first, metrics, tier, nxt, overrides), = grown
    fid, = _compactions(first)
    assert overflow == [fid] and tier == nxt == 0
    nid, mid = first.flag_caps[fid]
    live = ex._peak(metrics[mid])
    cap = _probe_cap(db)
    assert live > cap // 32 and overrides == {nid: live + max(live // 16, 64)}
    if nseg == 1:
        assert live == MANY
    assert first.join_gather_slots == (cap // 32,)
    assert [c.join_gather_slots for c in comps] == [(cap // 32,), (cap,)]
    assert _compactions(comps[-1]) == []
