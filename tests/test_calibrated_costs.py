"""Calibrated cost model (planner/cost.py) — plan goldens that flip on
stats, the CCostModelGPDB / CEngine-alternatives analog (VERDICT r2 #4).

The round-2 model costed motions in raw bytes, which systematically
over-broadcast mid-size relations (a broadcast build is sort-built
FULL-SIZE on every chip at ~40 ns/row/operand — ~250x its ICI transfer
cost per row) and hard-coded two-phase aggregation even when the group
key's NDV ~ row count makes the partial pass pure overhead. These tests
pin the flips the measured v5e constants produce.
"""

import numpy as np
import pytest

import greengage_tpu
from greengage_tpu.planner import cost as C
from greengage_tpu.planner.logical import describe
from greengage_tpu.sql.parser import parse


@pytest.fixture(scope="module")
def db(devices8):
    d = greengage_tpu.connect(numsegments=8)
    rng = np.random.default_rng(3)
    nf = 200_000
    # fact: distributed by k; join columns fk_small/fk_mid are NOT the
    # distribution key, so a join on them always needs motion
    d.sql("create table fact (k int, u int, fk_small int, fk_mid int, v int) "
          "distributed by (k)")
    d.load_table("fact", {
        "k": np.arange(nf),
        "u": rng.permutation(nf).astype(np.int64),   # high-NDV, NOT the dist key
        "fk_small": rng.integers(0, 40, nf),
        "fk_mid": rng.integers(0, 4000, nf),
        "v": rng.integers(0, 1000, nf),
    })
    # dim tables distributed by a non-join column (m), so the dim side is
    # never pre-hashed on the join key either: the planner must choose
    # between broadcasting the dim and redistributing both sides
    d.sql("create table dim_small (pk int, m int, w int) distributed by (m)")
    d.load_table("dim_small", {
        "pk": np.arange(40), "m": np.arange(40), "w": np.arange(40)})
    d.sql("create table dim_mid (pk int, m int, w int) distributed by (m)")
    d.load_table("dim_mid", {
        "pk": np.arange(4000), "m": np.arange(4000), "w": np.arange(4000)})
    d.sql("analyze")
    return d


def _plan(db, sql: str) -> str:
    planned, _, _ = db._plan(parse(sql)[0])
    return describe(planned)


def _motion_above(plan_text: str, scan_substr: str) -> str:
    """The Motion line (if any) directly above the matching Scan line —
    i.e. the motion that feeds this scan into its join."""
    lines = plan_text.splitlines()
    for i, ln in enumerate(lines):
        if scan_substr in ln:
            for j in range(i - 1, -1, -1):
                if "Motion" in lines[j] or "Join" in lines[j]:
                    return lines[j]
    return ""


# ---------------------------------------------------------------------------
# broadcast vs redistribute: flips on the build side's size
# ---------------------------------------------------------------------------

def test_tiny_dim_is_broadcast(db):
    got = _plan(db, "select sum(f.v) from fact f, dim_small d "
                    "where f.fk_small = d.pk")
    assert "Motion Broadcast" in _motion_above(got, "Scan dim_small"), got


def test_mid_dim_is_redistributed_not_broadcast(db):
    # 4000-row build: raw-bytes costing says broadcast (4000*8 < 200k/7);
    # the calibrated model charges the full-size replicated sort build on
    # every chip and redistributes both sides instead
    got = _plan(db, "select sum(f.v) from fact f, dim_mid d "
                    "where f.fk_mid = d.pk")
    assert "Motion Redistribute" in _motion_above(got, "Scan dim_mid"), got
    assert got.count("Motion Redistribute") >= 2, got


def test_broadcast_flip_tracks_stats(db):
    # the same SQL shape flips purely on the build side's row count —
    # the "plan goldens that flip on stats" requirement
    small = _plan(db, "select sum(f.v) from fact f, dim_small d "
                      "where f.fk_small = d.pk")
    mid = _plan(db, "select sum(f.v) from fact f, dim_mid d "
                    "where f.fk_mid = d.pk")
    assert "Motion Broadcast" in _motion_above(small, "Scan dim_small")
    assert "Motion Redistribute" in _motion_above(mid, "Scan dim_mid")


def test_both_shapes_execute_correctly(db):
    want_small = db.sql("select sum(v) from fact").rows()[0][0]
    got = db.sql("select sum(f.v) from fact f, dim_small d "
                 "where f.fk_small = d.pk").rows()[0][0]
    assert got == want_small  # every fk_small in [0,40) matches exactly once
    got_mid = db.sql("select sum(f.v) from fact f, dim_mid d "
                     "where f.fk_mid = d.pk").rows()[0][0]
    assert got_mid == want_small


# ---------------------------------------------------------------------------
# aggregate placement: one-phase vs two-phase flips on group-key NDV
# ---------------------------------------------------------------------------

def test_low_ndv_group_uses_two_phase(db):
    # 40 groups: partial aggregation collapses 200k rows to <=320 states,
    # so the two-phase plan moves ~nothing
    got = _plan(db, "select fk_small, sum(v) from fact group by fk_small")
    assert "Aggregate partial" in got and "Aggregate final" in got, got


def test_high_ndv_group_skips_partial_phase(db):
    # group by a ~unique key (k): partial reduces nothing — the calibrated
    # choice ships raw rows and aggregates once after the motion
    got = _plan(db, "select u, sum(v) from fact group by u")
    assert "Aggregate partial" not in got, got
    assert "Aggregate single" in got, got
    assert "Motion Redistribute" in got, got


def test_agg_placement_results_identical(db):
    one = dict(db.sql("select u, sum(v) from fact group by u").rows())
    assert len(one) == 200_000
    two = dict(db.sql("select fk_small, sum(v) from fact group by fk_small")
               .rows())
    got = db.sql("select sum(v) from fact").rows()[0][0]
    assert sum(two.values()) == got
    assert sum(one.values()) == got


# ---------------------------------------------------------------------------
# cost-model unit sanity: the measured asymmetries the flips rely on
# ---------------------------------------------------------------------------

def test_replicated_build_dwarfs_its_ici_cost():
    rows, width, nseg = 4000, 16, 8
    ici = C.motion_cost("broadcast", rows, width, nseg)
    build_extra = (C.join_build_cost(rows, 1, nseg, replicated=True)
                   - C.join_build_cost(rows, 1, nseg))
    assert build_extra > 10 * ici


def test_gather_charges_host_fetch_floor():
    # even a 1-row gather pays the fixed device->host call
    assert C.motion_cost("gather", 1, 8, 8) >= C.NS_HOST_CALL


# ---------------------------------------------------------------------------
# stale stats: packed keys must self-heal via the pack-violation retry
# ---------------------------------------------------------------------------

def test_stale_bounds_group_by_still_exact(db):
    d = greengage_tpu.connect(numsegments=8)
    rng = np.random.default_rng(5)
    d.sql("create table st (k int, g int, v int) distributed by (k)")
    n = 4000
    d.load_table("st", {"k": np.arange(n),
                        "g": rng.integers(0, 30000, n).astype(np.int64),
                        "v": np.ones(n, np.int64)})
    d.sql("analyze st")
    # grow the key domain far past the analyzed max WITHOUT re-analyzing
    d.sql("insert into st values (999991, 900000, 1), (999992, 900001, 1)")
    rows = d.sql("select g, sum(v) from st group by g").rows()
    got = {g: s for g, s in rows}
    assert got[900000] == 1 and got[900001] == 1
    assert sum(got.values()) == n + 2


def test_stale_bounds_join_still_exact(db):
    d = greengage_tpu.connect(numsegments=8)
    d.sql("create table bl (pk int, m int) distributed by (m)")
    d.sql("create table pr (k int, fk int) distributed by (k)")
    d.load_table("bl", {"pk": np.arange(100), "m": np.arange(100)})
    d.load_table("pr", {"k": np.arange(500),
                        "fk": (np.arange(500) % 120).astype(np.int64)})
    d.sql("analyze")
    # stale build bounds: new build key outside the analyzed [0, 99]
    d.sql("insert into bl values (5000, 5000)")
    d.sql("insert into pr values (501, 5000)")
    n = d.sql("select count(*) from pr, bl where pr.fk = bl.pk").rows()[0][0]
    # fks 0..99 each appear ceil-ish times within 0..119 cycle + the 5000 row
    want = int(np.isin((np.arange(500) % 120), np.arange(100)).sum()) + 1
    assert n == want
