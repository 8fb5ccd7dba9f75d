"""What the benchmark reads of the program (ISSUE 33): every `Result.stats`
key, counter, histogram and span that a file under `benchmark/metrics/`
names has to exist after a traced statement, and the program cache counts
one miss cold and one hit warm on both of its callers. The metric files are
read, never edited. Since ISSUE 35 the traced statements include a write:
an INSERT ... SELECT, a DELETE and the scans after each, for the metrics
of the write path and of the read path after a write; since ISSUE 37 a
duplicate-key LEFT JOIN whose expansion overflows once; since ISSUE 39 the
`trace_by_node` files, whose scopes are `exec/compile.py`'s and whose
`dispatch` spans name a program with a node map, read over a window whose
device line is made up by hand; since ISSUE 40 `agg_sort_input_slots`, the
slots each sort aggregate of a program sorts; and the counts every semi
and anti join reports, over a correlated EXISTS whose build repeats its
keys; `join_gather_slots`, the slots each inner or left join gathers its
build columns into (the LEFT JOIN above counts). Every cell of BENCHMARK.json finds its files through run.py's loader,
and a window of a hundred short statements is read back whole by time.
CPU: names and counts, never a time."""

import glob
import json
import os
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

import greengage_tpu
from greengage_tpu.exec import batchserve
from greengage_tpu.exec.compile import NODE_KINDS, PART_NAMES
from greengage_tpu.runtime import devprofile
from greengage_tpu.runtime.logger import counters, histograms
from greengage_tpu.runtime.trace import TRACES
from greengage_tpu.sql.parser import parse

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the kinds that read the program's own names; `trace_*` and `roofline`
# read the device's operations and the benchmark's own marks
PROGRAM_KINDS = ("stats_mean", "counter_delta", "counter_share",
                 "counter_per", "histogram_mean", "span_idle", "trace_by_node")
WRITE_SPANS = ("dml_scan", "write", "encode", "append", "delmask", "commit")
WRITE_COUNTERS = ("rows_inserted", "rows_deleted", "write_bytes",
                  "manifest_commits", "stage_cache_dropped",
                  "stage_units_copy_files", "stage_units_copy_delmask",
                  "zone_prune_skipped_delmask")


def _metric_files() -> list:
    out = []
    for path in sorted(glob.glob(os.path.join(ROOT, "benchmark", "metrics",
                                              "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        if spec["kind"] in PROGRAM_KINDS:
            out.append(pytest.param(spec, id=os.path.basename(path)[:-5]))
    return out


@pytest.fixture(scope="module")
def traced(devices8):
    """A grouped aggregate over two segments, run cold (compiles, reads
    the files) and again with the staged inputs dropped (finds the
    program, reads through the block cache): each run's stats and spans.
    Then the write path (`dml`): an INSERT ... SELECT and a DELETE, each
    followed by a scan under a pushed predicate. The registries last."""
    db = greengage_tpu.connect(numsegments=2)
    db.sql("create table bc (k bigint, v int) distributed by (k)")
    n = 6000
    db.load_table("bc", {"k": np.arange(n, dtype=np.int64) * 7919,
                         "v": (np.arange(n) % 11).astype(np.int32)})
    db.sql("analyze")
    sql = "select k, sum(v) from bc group by k"   # a sort-based aggregate
    runs = []
    for _ in range(2):
        db.executor.stager.stage_cache.clear()
        res = db.sql(sql)
        runs.append((res.stats, TRACES.last().export()))
    dml, scan = [], "select count(*) from bc where v < 5"
    for stmt in ("insert into bc select k + (select max(k) from bc), v "
                 "from bc where v = 3",
                 "delete from bc where k >= (select min(k) from bc) and v = 4"):
        res = db.sql(stmt)
        dml.append((res, res.stats, TRACES.last().export()))
        db.sql(scan)
    # ISSUE 37: a LEFT JOIN over a duplicate-key build side whose pairs
    # (200 x 201) overflow the expansion the estimate sized (400 rows):
    # one retry at the exact total, for the join_expand_* counters
    db.sql("create table jp (k bigint) distributed by (k)")
    db.sql("create table jb (k bigint, w int) distributed by (k)")
    db.load_table("jp", {"k": np.zeros(200, dtype=np.int64)})
    db.load_table("jb", {"k": np.concatenate([np.arange(200), np.zeros(200)])
                         .astype(np.int64),
                         "w": np.arange(400, dtype=np.int32)})
    db.sql("analyze")
    pairs = db.sql("select count(w) from jp left join jb on jp.k = jb.k")
    assert pairs.rows() == [(200 * 201,)] and pairs.stats["tiers_used"] == 2
    # a correlated EXISTS whose build side repeats its one key 200 times:
    # the semi_* counters (exec/compile.SEMI_COUNTERS)
    kept = db.sql("select count(*) from jb where exists "
                  "(select * from jp where jp.k = jb.k)")
    assert kept.rows() == [(201,)]
    yield {"runs": runs, "dml": dml, "counters": counters.snapshot(),
           "histograms": histograms.snapshot()}
    db.close()


@pytest.mark.parametrize("spec", _metric_files())
def test_metric_reads_a_name_the_program_produces(traced, spec):
    kind = spec["kind"]
    if kind == "stats_mean":
        # a SELECT's statistic, or one only a DML statement carries
        select = [stats for stats, _spans in traced["runs"]]
        write = [stats for _res, stats, _spans in traced["dml"]]
        mine = select if spec["stat"] in select[0] else write
        for stats in mine:
            assert spec["stat"] in stats, sorted(stats)
            if "where" in spec:
                assert spec["where"] in stats, sorted(stats)
        if "where" in spec:   # the cold run is the one it keeps
            assert mine[0][spec["where"]]
    elif kind in ("counter_delta", "counter_share", "counter_per"):
        names = [spec["name"]] if kind != "counter_share" \
            else spec["num"] + spec["den"]
        for name in names:
            assert traced["counters"].get(name, 0) > 0, name
    elif kind == "histogram_mean":
        for name in [spec["name"]] + spec.get("minus", []):
            if name != "client_latency_ms":   # the benchmark's own
                assert traced["histograms"][name]["count"] > 0, name
    elif kind == "trace_by_node":
        # what it sums by is spelled in exec/compile.py, what it reads of a
        # dispatch is one of three; every dispatch names a live program
        # whose executable gives the map from instruction to plan node
        assert set(spec) <= {"kind", "scopes", "read", "reduce"}, spec
        assert ("scopes" in spec) != ("read" in spec), spec
        assert spec.get("scopes", ["join"]) and set(spec.get(
            "scopes", [])) <= set(NODE_KINDS) | set(PART_NAMES), spec
        assert spec.get("read", "head") in ("head", "tail", "no_node_share")
        assert spec.get("reduce", "max") == "max"
        every = [spans for _stats, spans in traced["runs"]] + [
            spans for _res, _stats, spans in traced["dml"]]
        for spans in every:
            mine = [s for s in spans if s["name"] == "dispatch"]
            assert mine and all(devprofile.node_map_of(s["args"]["program"])
                                for s in mine), mine
    else:
        assert kind == "span_idle"
        for _stats, spans in traced["runs"]:
            names = {s["name"] for s in spans}
            mine = [s for s in spans if s["tid"] == spans[0]["tid"]]
            if spec["spans"] != "leaf":
                assert spec["spans"] in {s["name"] for s in mine}, names
                continue
            # the statement thread's leaves are what the idle time is held
            # against: the staging leaves and `dispatch` must be among them
            parents = {s["parent"] for s in mine}
            leaves = {s["name"] for s in mine if s["id"] not in parents}
            assert {"wait", "assemble", "put", "dispatch"} <= leaves, leaves
            assert "read:bc" in names and "stage" in names - leaves, names


def test_a_dml_statement_answers_like_a_select(traced):
    """ISSUE 35: the tag, one row, and a SELECT's statistics where they
    apply, summed over the statement's inner statements."""
    (ins, ins_stats, _), (dele, del_stats, _) = traced["dml"]
    n = 6000 // 11 + (3 < 6000 % 11)
    assert ins == f"INSERT 0 {n}" and ins.rows() == [(f"INSERT 0 {n}", n)]
    assert dele.rows() == [(str(dele), dele.nrows)] and dele.nrows > 0
    assert (ins_stats["rows_written"], ins_stats["rows_deleted"]) == (n, 0)
    assert (del_stats["rows_written"], del_stats["rows_deleted"]) == (
        0, dele.nrows)
    for stats in (ins_stats, del_stats):
        assert {"compiled", "compile_ms", "stage_ms", "compute_ms",
                "fetch_ms", "plan_cache", "stage_units",
                "stage_units_in_slot", "stage_units_copy_files",
                "stage_units_copy_delmask", "dml_scan_ms",
                "write_ms"} <= set(stats), sorted(stats)
        assert stats["inner_statements"] == 2   # a subquery, the scan


def test_the_write_path_has_its_spans_and_counters(traced):
    """Every span and counter docs/OBSERVABILITY.md lists for a write is
    there after one INSERT ... SELECT and one DELETE; a read unit that left
    the in-place path says why on its `read:` span."""
    names = [{s["name"] for s in spans} for _r, _s, spans in traced["dml"]]
    assert {"dml_scan", "write", "encode", "append", "commit"} <= names[0]
    assert {"dml_scan", "write", "delmask", "commit"} <= names[1]
    assert set(WRITE_SPANS) <= names[0] | names[1]
    for _res, _stats, spans in traced["dml"]:
        by_id = {s["id"]: s for s in spans}
        write = next(s for s in spans if s["name"] == "write")
        for s in spans:
            if s["name"] in ("encode", "append", "delmask", "commit"):
                assert by_id[s["parent"]] is write, s
    for name in WRITE_COUNTERS:
        assert traced["counters"].get(name, 0) > 0, name
    # the DELETE's own scan reads a table of two data files a column
    reads = [s for s in traced["dml"][1][2] if s["name"] == "read:bc"]
    assert reads and all(s["args"]["in_slot"] == "no"
                         and s["args"]["off_slot"] == "files" for s in reads)


def test_slot_counters_are_the_stats_summed_cold_and_warm(traced):
    """`stage_in_slot_share` reads two counters: the read units of both
    runs (two segments x two columns, decoded cold and copied from the
    block cache warm), every one of them in its staging slot."""
    for stats, spans in traced["runs"]:
        assert stats["stage_units"] == stats["stage_units_in_slot"] == 4
        assert len([s for s in spans if s["name"] == "read:bc"]) == 4
    assert traced["counters"]["stage_units"] >= 8
    assert traced["counters"]["stage_units_in_slot"] >= 8


def _cells() -> list:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("cell", _cells())
def test_every_cell_finds_its_files(cell):
    """run.py's own loader finds what the cell names: its configuration,
    traffic, each query's text and reads, a reference for each query, and a
    reader for each per-layer metric it reports."""
    run = _bench_module("run")
    c = run.load_cell(cell)
    assert c.config["numsegments"] >= 1 and c.queries
    for q in c.queries.values():
        assert q["sql"].strip() and isinstance(q["reads"], dict)
    assert set(c.queries) <= set(run.oracles())
    kinds = run.metric_kinds()
    assert "rows_per_s_chip" in c.end_to_end and "setup_s" in c.end_to_end
    for name in c.per_layer:
        assert run.read_json(run.HERE, "metrics", name + ".json")["kind"] in kinds


@pytest.fixture()
def db(devices8):
    d = greengage_tpu.connect(numsegments=4)
    d.sql("create table pc (k int, a int) distributed by (k)")
    d.load_table("pc", {"k": np.arange(500, dtype=np.int32),
                        "a": np.arange(500, dtype=np.int32)})
    yield d
    d.close()


def _bench_module(name: str):
    """A module of benchmark/, imported the way run.py imports it."""
    bench = os.path.join(ROOT, "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    return __import__(name)


# what the made-up device does inside every dispatch span, in shares of it
HEAD, NODE, GAP, NO_PATH, TAIL = 0.125, 0.25, 0.125, 0.25, 0.25
ON_PROFILER_CLOCK = 1000.0   # the profiler's clock against time.monotonic()


@pytest.fixture()
def window(db):
    """A window of four warm statements as run.py hands it to a metric
    reader: the records, and a `devtrace.Profile` whose marks are the
    records' times on another clock and whose one device ran, inside every
    `dispatch` span, a `while` of the aggregate's that encloses one of its
    operations, then one the compiler made."""
    sql = "select a % 5, count(*) from pc group by 1"
    db.sql(sql)
    records = []
    for _ in range(4):
        t0 = time.monotonic()
        db.sql(sql)
        records.append({"query": "qa", "t0": t0, "t1": time.monotonic()})
    kinds, parts = frozenset(NODE_KINDS), frozenset(PART_NAMES)
    ops, marks, spans_s = [], [], []
    for i, rec in enumerate(records):
        (tr,) = TRACES.between(rec["t0"], rec["t1"])
        (span,) = [s for s in tr.export() if s["name"] == "dispatch"]
        by_label: dict = {}
        for instr, path in sorted(devprofile.node_map_of(
                span["args"]["program"]).items()):
            by_label.setdefault(
                devprofile._innermost(path, kinds, parts), []).append(instr)
        label, agg = next(
            (label, v) for (label, part), v in sorted(by_label.items(), key=str)
            if label.startswith("agg-") and part is None and len(v) > 1)
        t0 = tr.t0 + span["ts"] * 1e-3 + ON_PROFILER_CLOCK
        dur = span["dur"] * 1e-3
        spans_s.append(dur)
        ops += [(f"%{agg[0]} = (s32[8]) while(...)", t0 + HEAD * dur, NODE * dur),
                (f"%{agg[1]} = s32[8] fusion(...)",
                 t0 + (HEAD + NODE / 4) * dur, NODE / 2 * dur),
                ("%copy.7 = s32[8] copy(...)",
                 t0 + (HEAD + NODE + GAP) * dur, NO_PATH * dur)]
        marks.append((f"qa.{i}", rec["t0"] + ON_PROFILER_CLOCK,
                      rec["t1"] - rec["t0"]))
    marks.append(("window", marks[0][1] - 1.0, marks[-1][1] + 2.0))
    profile = _bench_module("devtrace").Profile({"/device:TPU:0": ops}, marks)
    return (SimpleNamespace(profile=profile, window=records), spans_s,
            devprofile.kind_of(label))


def _trace_by_node_files() -> list:
    return [p for p in _metric_files() if p.values[0]["kind"] == "trace_by_node"]


@pytest.mark.parametrize("spec", _trace_by_node_files())
def test_trace_by_node_reads_a_window(window, spec, capfd):
    """Each of the files, through run.py's own loader, over that window."""
    ctx, spans_s, agg_kind = window
    read = _bench_module("run").metric_kinds()["trace_by_node"]
    got = read(spec, ctx)
    mean_ms = sum(spans_s) / len(spans_s) * 1e3
    if "scopes" in spec:   # a kind the window's programs lack: left out
        assert got == (pytest.approx(NODE * mean_ms, rel=1e-6)
                       if agg_kind in spec["scopes"] else None)
    elif spec["read"] == "no_node_share":
        assert got == pytest.approx(100 * NO_PATH / (NODE + NO_PATH), rel=1e-6)
    else:
        share = HEAD if spec["read"] == "head" else TAIL
        want = max(spans_s) * 1e3 if spec.get("reduce") else mean_ms
        assert got == pytest.approx(share * want, rel=1e-3, abs=2e-3)
    # the cell's table, once a window however many metrics read it
    assert read(spec, ctx) == got
    table = [ln for ln in capfd.readouterr().err.splitlines()
             if ln.startswith("[bynode] qa ")]
    assert len(table) == 3 and "(no node)" in table[1], table
    assert " head " in table[2] and " tail " in table[2] and \
        " between " in table[2] and table[2].endswith("over 4 statements")


def test_trace_by_node_leaves_the_metric_out_of_an_older_program(
        window, monkeypatch):
    """The driver lays this PR's benchmark files over the parent's checkout:
    a program without `runtime/devprofile` gives nothing to read."""
    import greengage_tpu.runtime as runtime

    ctx, _spans_s, _agg_kind = window
    read = _bench_module("run").metric_kinds()["trace_by_node"]
    monkeypatch.delattr(runtime, "devprofile")
    monkeypatch.setitem(sys.modules, "greengage_tpu.runtime.devprofile", None)
    for spec in ({"kind": "trace_by_node", "scopes": ["agg-dense", "agg-sort"]},
                 {"kind": "trace_by_node", "read": "tail", "reduce": "max"}):
        assert read(spec, ctx) is None


def test_agg_sort_input_slots_are_the_programs_sort_inputs(db):
    """`agg_sort_slots_per_stmt` reads `agg_sort_input_slots`: a statement
    adds the input capacity of each sort aggregate of its program, which is
    never below the group table that aggregate fills."""
    sql = "select a, count(*) from pc group by a"
    db.sql(sql)
    c0 = counters.snapshot()
    db.sql(sql)
    d = counters.since(c0)
    comp, = [c for _k, c in db.executor.programs.items() if c.agg_caps]
    caps = list(comp.agg_caps.values())
    assert all(slots >= cap for cap, slots in caps)
    assert d["agg_sort_input_slots"] == sum(s for _c, s in caps) > 0
    assert d["agg_sort_capacity"] == sum(c for c, _s in caps)


def test_join_gather_slots_are_the_programs_gather_capacities(db):
    """`join_gather_slots_per_stmt` reads `join_gather_slots`: a statement
    adds, for each inner or left join of its program, the slots its build
    columns were gathered into; a semi join gathers none."""
    for t in ("pd", "pe"):
        db.sql(f"create table {t} (k int, b int) distributed by (k)")
        db.load_table(t, {"k": np.arange(0, 1000, 2, dtype=np.int32),
                          "b": np.ones(500, dtype=np.int32)})
    db.sql("analyze")
    sql = ("select count(pd.b), count(pe.b) from pc join pd on pc.k = pd.k "
           "left join pe on pc.a + 1 = pe.k where pc.a in (select k from pe)")
    assert db.sql(sql).rows() == [(250, 0)]
    c0 = counters.snapshot()
    db.sql(sql)
    d = counters.since(c0)
    comp, = [c for _k, c in db.executor.programs.items() if c.join_gather_slots]
    assert len(comp.join_gather_slots) == 2 and min(comp.join_gather_slots) > 0
    assert d["join_gather_slots"] == sum(comp.join_gather_slots)


def test_a_window_of_short_statements_is_read_back_whole(db):
    """The `trace_by_node` and span readers find every statement of a
    window by time (`TRACES.between`): 45 s of a 0.44 s statement is ~100
    of them, all of which the ring (`trace_ring_size`) has to hold."""
    sql = "select count(*) from pc where a > 100"
    db.sql(sql)
    t0 = time.monotonic()
    for _ in range(120):
        db.sql(sql)
    found = TRACES.between(t0, time.monotonic())
    assert len(found) == 120
    assert all(any(s["name"] == "dispatch" for s in tr.export()) for tr in found)


@pytest.mark.parametrize("caller", ["classic", "batch"])
def test_one_miss_cold_and_one_hit_warm(db, caller):
    """Both callers of ProgramCache.find_or_compile, the same shape."""
    planned, consts, outs, ek = db._cached_plan(
        parse("select count(*) from pc where a > 100")[0])
    pv = consts["@params@"]

    def run():
        if caller == "classic":
            return [db.executor.run(planned, consts, outs, cache_key=ek)]
        return batchserve.run_batch(db.executor, planned, consts, ek,
                                    [pv, pv])

    for want in ({"program_cache_miss": 1}, {"program_cache_hit": 1}):
        c0 = counters.snapshot()
        res = run()
        assert counters.since(c0, prefix="program_cache_") == want
        assert all(r.rows() == [(399,)] for r in res)


@pytest.mark.parametrize("caller", ["classic", "batch"])
def test_unsignable_shape_is_counted_once_and_never_cached(db, caller,
                                                           monkeypatch):
    """The callers choose what an unsignable shape does: the classic loop
    compiles it uncached, a batch goes back to the serial path."""
    from greengage_tpu.exec.compile import Compiler

    planned, consts, outs, ek = db._cached_plan(
        parse("select count(*) from pc where a > 200")[0])

    def refuse(self, plan, snapshot):
        raise LookupError("dictionary unavailable")
    monkeypatch.setattr(Compiler, "shape_signature", refuse)
    c0 = counters.snapshot()
    if caller == "classic":
        res = db.executor.run(planned, consts, outs, cache_key=ek)
        assert res.rows() == [(299,)] and res.stats["compiled"]
    else:
        with pytest.raises(batchserve.BatchFallback):
            batchserve.run_batch(db.executor, planned, consts, ek,
                                 [consts["@params@"]])
    assert counters.since(c0, prefix="program_cache_") == {
        "program_cache_unsignable": 1}
    assert not db.executor.programs.items()
