"""The TPC-H refresh functions around Q1 and Q6 (ISSUE 35), the cell
`refresh_power_1chip`: its six statements through `Database.sql` at SF 0.01
against the reference that follows state (`benchmark/queries/refresh.py`),
that reference against `oracle._q1` / `_q6` over rows inserted and deleted
by hand, a cluster closed and re-opened between rounds (durability, which a
benchmark run cannot show), what a DML statement answers with, and the
program cache across refresh statements. CPU: answers and counts, never a
time."""

import importlib.util
import os
import subprocess
import sys

import numpy as np
import pytest

import greengage_tpu
from greengage_tpu.runtime.logger import counters
from greengage_tpu.types import Coded

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SF, SEED, ROUNDS = 0.01, 20260135, 3
ROUND = ("rf1_lineitem", "rf1_orders", "q1_live", "q6_live",
         "rf2_lineitem", "rf2_orders")


def _bench_modules():
    """benchmark/'s generator and oracle, and a FRESH import of the refresh
    reference (its state is one per import, as run.py imports it)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import oracle
    import tpch_data
    spec = importlib.util.spec_from_file_location(
        "queries_refresh", os.path.join(BENCH, "queries", "refresh.py"))
    refresh = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(refresh)
    return tpch_data, oracle, refresh


def _sql(name: str) -> str:
    with open(os.path.join(BENCH, "queries", name + ".sql")) as f:
        return f.read()


class ByHand:
    """lineitem's rows (the columns Q1 and Q6 read, and the key) and
    orders' keys, with the refresh statements applied row by row."""

    COLS = ("l_orderkey", "l_quantity", "l_extendedprice", "l_discount",
            "l_tax", "l_returnflag", "l_linestatus", "l_shipdate")

    def __init__(self, data, block: int):
        self.li = {c: data["lineitem"][c] for c in self.COLS}
        self.okeys = data["orders"]["o_orderkey"].copy()
        self.block = block

    def _pick(self, m) -> dict:
        return {c: (Coded(v.vocab, v.codes[m]) if isinstance(v, Coded)
                    else v[m]) for c, v in self.li.items()}

    def _range(self, keys):
        lo = self.okeys.min()
        return (keys >= lo) & (keys <= lo + self.block - 1), self.okeys.max()

    def apply(self, query: str) -> int:
        if query == "rf1_lineitem":
            m, top = self._range(self.li["l_orderkey"])
            new = self._pick(m)
            new["l_orderkey"] = new["l_orderkey"] + top
            self.li = {c: (Coded(v.vocab, np.concatenate(
                [v.codes, new[c].codes])) if isinstance(v, Coded)
                else np.concatenate([v, new[c]])) for c, v in self.li.items()}
        elif query == "rf1_orders":
            m, top = self._range(self.okeys)
            self.okeys = np.concatenate([self.okeys, self.okeys[m] + top])
        elif query == "rf2_lineitem":
            m, _ = self._range(self.li["l_orderkey"])
            self.li = self._pick(~m)
        else:
            m, _ = self._range(self.okeys)
            self.okeys = self.okeys[~m]
        return int(m.sum())

    def answer(self, query: str, oracle) -> list:
        fn = oracle._q1 if query == "q1_live" else oracle._q6
        return fn({"lineitem": self.li})


@pytest.fixture(scope="module")
def cell(devices8, tmp_path_factory):
    """Three rounds on one segment; after the first the cluster is closed
    and re-opened. -> every statement's engine rows, reference rows, rows
    by hand, Result and counter deltas, and what the re-opened cluster
    answered before anything else ran on it."""
    tpch_data, oracle, refresh = _bench_modules()
    data = tpch_data.generate(SF, SEED)
    path = str(tmp_path_factory.mktemp("refresh") / "cluster")
    db = greengage_tpu.connect(path, numsegments=1)
    db.sql(tpch_data.DDL)
    for t in tpch_data.TABLES:
        db.load_table(t, data[t])
    db.sql("analyze")
    stored = {q: refresh.ORACLES[q].build(data) for q in ROUND}
    hand = ByHand(data, refresh.BLOCK)
    records, reopened = [], {}
    for rnd in range(ROUNDS):
        for q in ROUND:
            c0 = counters.snapshot()
            res = db.sql(_sql(q))
            want = refresh.ORACLES[q].rows(stored[q], {})
            by_hand = (hand.answer(q, oracle) if q.endswith("_live")
                       else hand.apply(q))   # rows, or a row count
            records.append({"round": rnd, "query": q, "res": res,
                            "rows": [list(r) for r in res.rows()],
                            "want": want, "by_hand": by_hand,
                            "counters": counters.since(c0)})
        if rnd == 0:
            before = {q: [list(r) for r in db.sql(_sql(q)).rows()]
                      for q in ("q1_live", "q6_live")}
            db.close()
            db = greengage_tpu.connect(path, numsegments=1)
            reopened = {
                "before": before,
                "after": {q: [list(r) for r in db.sql(_sql(q)).rows()]
                          for q in ("q1_live", "q6_live")},
                "counts": {t: db.sql(f"select count(*) from {t}").rows()[0][0]
                           for t in ("lineitem", "orders")},
                "by_hand": {"lineitem": len(hand.li["l_orderkey"]),
                            "orders": len(hand.okeys)}}
    yield {"records": records, "reopened": reopened, "oracle": oracle,
           "refresh": refresh, "data": data}
    db.close()


@pytest.mark.parametrize("rnd", range(ROUNDS))
def test_every_statement_is_the_references(cell, rnd):
    """Row counts of the refresh statements and Q1 / Q6 after them, as
    run.py's `check_answers` compares them; SF 0.01 with the cell's fixed
    text copies the whole table, so later rounds copy copies."""
    mine = [r for r in cell["records"] if r["round"] == rnd]
    assert [r["query"] for r in mine] == list(ROUND)
    for r in mine:
        cell["oracle"].compare(r["query"], r["rows"], r["want"])
    assert mine[0]["want"][0][1] > 0 and mine[4]["want"][0][1] > 0


def test_the_reference_is_brute_force_over_rows_made_by_hand(cell):
    compare = cell["oracle"].compare
    for r in cell["records"]:
        if r["query"].endswith("_live"):
            compare(r["query"], r["want"], r["by_hand"])
        else:
            assert r["want"][0][1] == r["by_hand"], r["query"]


def test_a_reopened_cluster_holds_every_acknowledged_write(cell):
    """Durability, the engine's default: nothing but `close()` between the
    last acknowledged write and the re-open. The rounds after it went on
    matching the reference (test_every_statement_is_the_references)."""
    got = cell["reopened"]
    assert got["after"] == got["before"]
    assert got["counts"] == got["by_hand"]


def test_a_dml_result_is_its_tag_with_rows_and_stats(cell):
    for r in cell["records"]:
        res = r["res"]
        if r["query"].endswith("_live"):
            continue
        assert isinstance(res, str) and res == str(res) == r["want"][0][0]
        assert res.rows() == [(str(res), r["want"][0][1])]
        st = res.stats
        assert {"compiled", "compile_ms", "stage_ms", "compute_ms",
                "fetch_ms", "plan_cache", "stage_units",
                "stage_units_in_slot", "stage_units_copy_files",
                "stage_units_copy_delmask", "zone_prune_skipped_delmask",
                "dml_scan_ms", "write_ms", "rows_written",
                "rows_deleted"} <= set(st), sorted(st)
        n = r["want"][0][1]
        assert (st["rows_written"], st["rows_deleted"]) == (
            (n, 0) if r["query"].startswith("rf1") else (0, n))
        assert r["counters"].get("manifest_commits") == 1
        assert r["counters"].get(
            "rows_inserted" if r["query"].startswith("rf1")
            else "rows_deleted", 0) == n


def test_scans_after_a_refresh_leave_the_in_place_path(cell):
    """What the cell is for: after RF1 a column is several data files,
    after RF2 the table has a deletion bitmap, which also switches the
    pushed predicates off; every read unit of Q1 and Q6 says so."""
    for r in cell["records"]:
        if not r["query"].endswith("_live"):
            continue
        st, first = r["res"].stats, r["round"] == 0
        units = 7 if r["query"] == "q1_live" else 4
        assert st["stage_units"] == units and st["stage_units_in_slot"] == 0
        assert st["stage_units_copy_files"] == (units if first else 0)
        assert st["stage_units_copy_delmask"] == (0 if first else units)
        assert st["zone_prune_skipped_delmask"] == (0 if first else 1)
        assert r["counters"].get("stage_cache_dropped", 0) >= (
            1 if r["query"] == "q1_live" else 0)


def test_the_reference_fails_loudly_past_what_it_stored(cell):
    refresh, oracle = cell["refresh"], cell["oracle"]
    stored = refresh._build_q6(cell["data"])
    short = {**stored, "prefix": stored["prefix"][:10]}
    p = refresh._Prefix(short)
    assert p.span(1, 9)[0] == stored["prefix"][9][0]
    assert (p.at(int(stored["n_orders"])) == stored["total"]).all()
    with pytest.raises(oracle.WrongAnswer, match="past the 9 whose"):
        p.span(3, 10)
    # Q1's averages are compared by tolerance under the live name too
    assert oracle.AVG_COLUMNS["q1_live"] == oracle.AVG_COLUMNS["q1"]


def test_the_cells_text_is_q1_and_q6_letter_for_letter():
    assert _sql("q1_live") == _sql("q1") and _sql("q6_live") == _sql("q6")
    _, _, refresh = _bench_modules()
    assert refresh.BLOCK == 7500   # SF5 x 1500, checked against the text


def test_refresh_statements_find_their_programs(devices8):
    """A refresh function of 100 orders (0.7 % of the table; the cell's is
    0.1 %), so no capacity bucket moves: from its third execution on a
    refresh statement compiles nothing, though its subqueries' values and
    the manifest version changed (the second may: it re-plans with what
    the first measured); the DELETE's predicate program is found through
    the program cache and counted there."""
    tpch_data, _oracle, _refresh = _bench_modules()
    data = tpch_data.generate(SF, SEED)
    db = greengage_tpu.connect(numsegments=1)
    try:
        db.sql(tpch_data.DDL)
        for t in ("lineitem", "orders"):
            db.load_table(t, data[t])
        db.sql("analyze")
        seen = {}
        for rnd in range(4):
            for q in ROUND:
                c0 = counters.snapshot()
                res = db.sql(_sql(q).replace("+ 7499", "+ 99"))
                delta = counters.since(c0, prefix="program_cache_")
                seen.setdefault(q, []).append((res.stats["compiled"], delta))
        for q in ROUND:
            if q.endswith("_live"):
                continue
            # the first two executions may compile (the second re-plans
            # with the first's measured cardinalities); none after them
            for compiled, delta in seen[q][2:]:
                assert not compiled and "program_cache_miss" not in delta, (
                    q, seen[q])
                # the statement's scan and its subqueries, each a hit
                assert delta["program_cache_hit"] >= 3, (q, seen[q])
    finally:
        db.close()


@pytest.mark.parametrize("lo", [1, 30_001, 600_001, 9_000_001])
def test_a_key_ranges_estimate_does_not_move_with_its_lower_bound(lo):
    """What sent a refresh statement to the compiler inside the window on
    the chip: `k >= lo and k <= lo + n` estimated as two independent
    filters grows with lo (15,000 orders read as 45,000 two refresh
    functions later), and the Gather's compaction is sized from it. The
    two bounds are one range (planner/cost._pair_ranges)."""
    from greengage_tpu import expr as E
    from greengage_tpu import types as T
    from greengage_tpu.planner import cost
    from greengage_tpu.planner.stats import ColumnStats

    n = 15_000_000
    cs = ColumnStats(ndv=n, min=1, max=n,
                     hist=[1 + i * (n - 1) / 100 for i in range(101)])
    k = E.ColRef("k#0", T.INT64)
    between = E.BoolOp("and", (
        E.Cmp(">=", k, E.Literal(lo, T.INT64)),
        E.Cmp("<=", k, E.Literal(lo + 14_999, T.INT64))))
    sel = cost.filter_selectivity(between, lambda name: cs)
    assert sel * n == pytest.approx(15_000, rel=0.01)
    # a third conjunct on another column still multiplies in
    other = E.Cmp("<", E.ColRef("v#1", T.INT64), E.Literal(7, T.INT64))
    both = E.BoolOp("and", between.args + (other,))
    assert cost.filter_selectivity(both, lambda name: cs if name == "k#0"
                                   else None) == pytest.approx(
        sel * cost.RANGE_SELECTIVITY)


def test_the_rehearsal_runs_the_cell(tmp_path):
    """`python benchmark/rehearse.py refresh_power_1chip`: run.py's own
    control flow at SF 0.01 on the CPU, warm-up, window and reference."""
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "rehearse.py"),
         "refresh_power_1chip"], capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-4000:]
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert len(lines) == 2 and all('"ok": true' in ln for ln in lines), lines
