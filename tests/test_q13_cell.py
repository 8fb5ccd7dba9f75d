"""TPC-H Q13 "Customer Distribution" and the cell `custdist_power_1chip`
(ISSUE 37): clause 2.4.13.2's text, unchanged, against the benchmark's numpy
reference on the benchmark's generator; the duplicate-key LEFT OUTER JOIN's
pair expansion (one pass over the slots since ISSUE 38), its counters and its
retry; the derived table's column-alias list; and the four older cells'
one-segment programs, whose lowered text is the parent's. CPU: answers and
counts, never a time.

    python tests/test_q13_cell.py --record

prints the lowered-text digests of whatever `greengage_tpu` is on the path
(PYTHONPATH=<a checkout of the parent>), for tests/goldens/.
"""

import hashlib
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
GOLDEN = os.path.join(ROOT, "tests", "goldens", "lowered_1seg_sf002.json")
SF, SEEDS = 0.02, (20260137, 2971215073)
OLDER = ("q1", "q3", "q6", "q18")

# clause 2.4.13.2, the functional query definition, as published
PUBLISHED = """
select
    c_count,
    count(*) as custdist
from
    (
        select
            c_custkey,
            count(o_orderkey)
        from
            customer left outer join orders on
                c_custkey = o_custkey
                and o_comment not like '%[WORD1]%[WORD2]%'
        group by
            c_custkey
    ) as c_orders (c_custkey, c_count)
group by
    c_count
order by
    custdist desc,
    c_count desc;
"""


def _bench_modules():
    """benchmark/'s generator, oracle and Q13 reference, imported the way
    run.py imports them (its directory on the path, queries/*.py by file)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import oracle
    import tpch_data
    spec = importlib.util.spec_from_file_location(
        "queries_q13", os.path.join(BENCH, "queries", "q13.py"))
    q13 = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(q13)
    return tpch_data, oracle, q13


def _sql(name: str) -> str:
    with open(os.path.join(BENCH, "queries", name + ".sql")) as f:
        return f.read()


def _q13_sql(words=None) -> str:
    sql = _sql("q13")
    if words is not None:
        sql = sql.replace("%special%requests%", "%{}%{}%".format(*words))
        assert sql != _sql("q13")
    return sql


def _without_a_third(data: dict) -> dict:
    """The orders of every third customer removed: those customers leave
    the LEFT OUTER JOIN null-extended."""
    from greengage_tpu.types import Coded

    o = data["orders"]
    keep = o["o_custkey"] % 3 != 0
    orders = {k: (Coded(v.vocab, v.codes[keep]) if isinstance(v, Coded)
                  else v[keep]) for k, v in o.items()}
    return {**data, "orders": orders}


def _connect(tpch_data, data: dict, nseg: int, tables=("customer", "orders")):
    import greengage_tpu

    db = greengage_tpu.connect(numsegments=nseg)
    db.sql(tpch_data.DDL)
    for t in tables:
        db.load_table(t, data[t])
    db.sql("analyze")
    return db


@pytest.fixture(scope="module")
def env(devices8):
    tpch_data, oracle, q13 = _bench_modules()
    datas = {seed: tpch_data.generate(SF, seed) for seed in SEEDS}
    datas["holes"] = _without_a_third(datas[SEEDS[0]])
    dbs = {(key, nseg): _connect(
        tpch_data, data, nseg,
        tpch_data.TABLES if (key, nseg) == (SEEDS[0], 1) else ("customer", "orders"))
        for key, data in datas.items() for nseg in (1, 4)}
    yield {"datas": datas, "dbs": dbs, "oracle": oracle, "q13": q13,
           "tpch_data": tpch_data}
    for db in dbs.values():
        db.close()


def _run_counted(db, sql: str):
    from greengage_tpu.runtime.logger import counters

    c0 = counters.snapshot()
    r = db.sql(sql)
    return r, counters.since(c0)


@pytest.mark.parametrize("words", [None, ("comment", "1")],
                         ids=["published", "matching"])
@pytest.mark.parametrize("nseg", [1, 4])
@pytest.mark.parametrize("key", [*SEEDS, "holes"])
def test_q13_equals_the_benchmark_reference(env, key, nseg, words):
    """The published text (and the same with words the harness's comments
    do hold) at one and four segments, two seeds, and over data in which a
    third of the customers have no orders."""
    data, q13 = env["datas"][key], env["q13"]
    want = q13.customer_distribution(data, *(words or ()))
    r, d = _run_counted(env["dbs"][key, nseg], _q13_sql(words))
    env["oracle"].compare("q13", [list(row) for row in r.rows()], want)
    # what the reference says the join did, against the program's counters
    o = data["orders"]
    n_cust = len(data["customer"]["c_custkey"])
    matched = q13.comment_matches(o["o_comment"].vocab,
                                  *(words or (q13.WORD1, q13.WORD2)))
    if words is None:
        assert not matched.any()      # the harness's comments hold neither word
    else:
        assert 0.2 < matched[o["o_comment"].codes].mean() < 0.6
    zero = dict((k, n) for k, n in want).get(0, 0)
    assert zero >= (n_cust // 3 if key == "holes" else 0)
    assert d.get("join_null_extended_rows", 0) == zero
    assert d.get("join_expand_retries", 0) == 0 and r.stats["tiers_used"] == 1
    if nseg == 1:   # one segment's expansion holds every pair
        orderless = n_cust - len(np.unique(o["o_custkey"]))
        assert d["join_expand_rows"] == len(o["o_custkey"]) + orderless
    cap = d["join_expand_capacity"]
    assert cap >= d["join_expand_rows"] and cap & (cap - 1) == 0


def test_reference_equals_a_loop_over_rows_made_by_hand():
    from greengage_tpu.types import Coded

    _tpch, _oracle, q13 = _bench_modules()
    vocab = ["plain", "special requests", "requests then special",
             "a special\nbulk requests b", "specialrequests", "SPECIAL REQUESTS"]
    custkey = [1, 1, 1, 2, 2, 4, 4, 4, 4, 5, 7, 7]
    codes = [0, 1, 2, 1, 3, 0, 0, 2, 5, 4, 0, 3]
    data = {"customer": {"c_custkey": np.arange(1, 8, dtype=np.int64)},
            "orders": {"o_orderkey": np.arange(1, 13, dtype=np.int64),
                       "o_custkey": np.array(custkey, dtype=np.int64),
                       "o_comment": Coded(vocab, np.array(codes, np.int32))}}
    # the LEFT OUTER JOIN, pair by pair
    c_count = {}
    for c in range(1, 8):
        n = 0
        for k, code in zip(custkey, codes):
            s = vocab[code]
            i = s.find("special")
            like = i >= 0 and s.find("requests", i + len("special")) >= 0
            if k == c and not like:
                n += 1
        c_count[c] = n
    dist = {}
    for n in c_count.values():
        dist[n] = dist.get(n, 0) + 1
    want = [[n, d] for d, n in sorted(((d, n) for n, d in dist.items()),
                                      reverse=True)]
    assert c_count == {1: 2, 2: 0, 3: 0, 4: 4, 5: 0, 6: 0, 7: 1}
    assert q13.customer_distribution(data) == want == [[0, 4], [4, 1], [2, 1], [1, 1]]
    data["customer"]["c_custkey"] = data["customer"]["c_custkey"][::-1]
    with pytest.raises(_oracle.WrongAnswer, match="1..n"):
        q13.customer_distribution(data)


def test_an_overflowed_expansion_retries_once_at_the_exact_total(env, monkeypatch):
    """A false estimate (1,024 slots for the multi join's expansion): the
    first attempt overflows and reports its exact total, the second is
    sized from it and holds every pair; the hint is kept."""
    from greengage_tpu.exec.compile import Compiler
    from greengage_tpu.planner.logical import Join

    real = Compiler._capacity_of

    def too_small(self, plan):
        if (isinstance(plan, Join) and getattr(plan, "multi", False)
                and self._nid(plan) not in self.cap_overrides):
            return 1024
        return real(self, plan)
    monkeypatch.setattr(Compiler, "_capacity_of", too_small)
    data, q13 = env["datas"][SEEDS[1]], env["q13"]
    o = data["orders"]
    db = _connect(env["tpch_data"], data, 1)
    try:
        r, d = _run_counted(db, _q13_sql())
        env["oracle"].compare("q13", [list(row) for row in r.rows()],
                              q13.customer_distribution(data))
        orderless = len(data["customer"]["c_custkey"]) - len(np.unique(o["o_custkey"]))
        assert d["join_expand_retries"] == 1 and r.stats["tiers_used"] == 2
        assert d["join_expand_rows"] == len(o["o_custkey"]) + orderless
        assert d["join_expand_capacity"] >= d["join_expand_rows"]
        r2, d2 = _run_counted(db, _q13_sql())
        assert r2.rows() == r.rows() and r2.stats["tiers_used"] == 1
        assert d2.get("join_expand_retries", 0) == 0
        assert d2["join_expand_capacity"] >= d2["join_expand_rows"] == d["join_expand_rows"]
    finally:
        db.close()


# ---------------------------------------------------------------- the parser

@pytest.fixture(scope="module")
def small(devices8):
    import greengage_tpu

    db = greengage_tpu.connect(numsegments=2)
    db.sql("create table t (a int, b int) distributed by (a)")
    db.sql("insert into t values (1, 10), (2, 20), (3, 30), (4, 40)")
    yield db
    db.close()


@pytest.mark.parametrize("alias", ["as d (k, v)", "d (k, v)", "as d(k, v)"])
def test_derived_table_takes_a_column_alias_list(small, alias):
    r = small.sql(f"select k, v from (select a, b from t) {alias} "
                  "where v >= 30 order by k")
    assert r.rows() == [(3, 30), (4, 40)]


def test_alias_list_names_an_unnamed_aggregate_and_a_union(small):
    r = small.sql("select n, count(*) from (select a, count(b) from t "
                  "group by a) as g (a, n) group by n")
    assert r.rows() == [(1, 4)]
    r = small.sql("select k from (select a, b from t where a < 2 union all "
                  "select a, b from t where a > 3) u (k, v) order by k")
    assert r.rows() == [(1,), (4,)]


@pytest.mark.parametrize("sql,words", [
    ("select k from (select a, b from t) as d (k)",
     'CTE "d" has 2 columns but 1 aliases were given'),
    ("select k from (select * from t) d (k, v)",
     'cannot apply column aliases to "d": SELECT \\* in CTE body'),
    ("with c (k) as (select a, b from t) select k from c",
     'CTE "c" has 2 columns but 1 aliases were given'),
    ("select k from (select a, b from t) as d (k, )", "expected name")])
def test_alias_list_errors_are_the_cte_forms(small, sql, words):
    from greengage_tpu.sql.parser import SqlError

    with pytest.raises(SqlError, match=words):
        small.sql(sql)


def test_cte_column_aliases_and_plain_aliases_are_unchanged(small):
    assert small.sql("with c (k, v) as (select a, b from t) select k, v "
                     "from c where v >= 30 order by k").rows() == [(3, 30), (4, 40)]
    assert small.sql("select d.a from (select a from t) as d order by 1"
                     ).rows() == [(1,), (2,), (3,), (4,)]
    assert small.sql("select x.a from (select a from t) x join t y "
                     "on x.a = y.a order by 1").rows() == [(1,), (2,), (3,), (4,)]


# ------------------------------------------------------- the benchmark's files

def test_q13_sql_is_the_published_text():
    want = PUBLISHED.replace("[WORD1]", "special").replace("[WORD2]", "requests")
    assert _sql("q13").split() == want.split()
    with open(os.path.join(BENCH, "queries", "q13.json")) as f:
        assert json.load(f)["reads"] == {
            "customer": ["c_custkey"],
            "orders": ["o_orderkey", "o_custkey", "o_comment"]}


def test_q13_cell_refuses_a_program_whose_parser_refuses_the_text(env, monkeypatch):
    """The driver runs a new cell on the parent's program too, under this
    PR's benchmark files: queries/q13.py turns it away at import, before any
    data is made, and only on a command line that names a Q13 cell."""
    from greengage_tpu.sql import parser

    q13 = env["q13"]
    assert q13.replays_q13("custdist_power_1chip")
    assert not q13.replays_q13("largevol_power_1chip") and not q13.replays_q13(None)
    assert q13.parse_error() is None
    # the parent's derived table: no alias list
    monkeypatch.setattr(parser.Parser, "_column_alias_list", lambda self: None)
    assert "SqlError" in q13.parse_error()
    for cell, refused in (("custdist_power_1chip", True), ("scan_power_1chip", False)):
        monkeypatch.setattr(sys, "argv", ["run.py", "--workload", cell, "--seed", "1"])
        if refused:
            with pytest.raises(SystemExit, match="parser refuses TPC-H Q13"):
                _bench_modules()
        else:
            _bench_modules()


def test_the_rehearsal_runs_the_cell():
    """`python benchmark/rehearse.py custdist_power_1chip`: run.py's own
    control flow at SF 0.01 on the CPU, warm-up, window and reference."""
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "rehearse.py"),
         "custdist_power_1chip"], capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-4000:]
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert len(lines) == 2 and all('"ok": true' in ln for ln in lines), lines


# ----------------------------------- the older cells' programs are the parent's

def _q18_cell_helpers():
    """tests/test_q18_cell's `_lowered` (a statement's program as lowered
    StableHLO) and `_ops` (its operation counts)."""
    if os.path.join(ROOT, "tests") not in sys.path:
        sys.path.insert(0, os.path.join(ROOT, "tests"))
    from test_q18_cell import _lowered, _ops

    return _lowered, _ops


def _lowered_digests(db) -> dict:
    """sha256 of each older statement's one-segment program as lowered,
    uncompiled StableHLO, in a fixed order on a database that has run
    nothing else (what a statement learns sizes the next one's program)."""
    lowered, _ops = _q18_cell_helpers()
    return {q: hashlib.sha256(lowered(db, _sql(q))[0].encode()).hexdigest()
            for q in OLDER}


@pytest.fixture(scope="module")
def older_digests(devices8):
    tpch_data, _oracle, _q13 = _bench_modules()
    db = _connect(tpch_data, tpch_data.generate(SF, SEEDS[0]), 1, tpch_data.TABLES)
    yield _lowered_digests(db)
    db.close()


@pytest.mark.parametrize("query", OLDER)
def test_older_one_segment_program_is_the_parents(older_digests, query):
    """The four cells the benchmark had hold no multi join: ISSUE 37's
    counters and scope must not pass through their programs. Recorded from
    the parent commit (3fc762c) by this file's --record."""
    with open(GOLDEN) as f:
        golden = json.load(f)
    assert older_digests[query] == golden["sha256"][query]


def test_q13_program_holds_the_expansion_and_nothing_of_a_motion(env):
    """The one-segment program: one `while`, the hop walk, and no
    collective. The expansion's search was the second until ISSUE 38: a
    scatter of each run's probe row at its first slot and two prefix maxes
    over the slots stand in its place (`ops/join.expand_slots`)."""
    lowered, ops = _q18_cell_helpers()
    got = ops(lowered(env["dbs"][SEEDS[0], 1], _q13_sql())[0])
    assert got["while"] == 1 and got["all_to_all"] == 0 and got["all_gather"] == 0


if __name__ == "__main__" and "--record" in sys.argv:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    _tpch, _o, _q = _bench_modules()
    _db = _connect(_tpch, _tpch.generate(SF, SEEDS[0]), 1, _tpch.TABLES)
    print(json.dumps({"recorded_from": sys.argv[-1], "sf": SF, "seed": SEEDS[0],
                      "sha256": _lowered_digests(_db)}, indent=1))
