"""What the host does inside `stage` (docs/OBSERVABILITY.md "Inside
stage"): `read:<table>` spans from the staging pool through an explicit
handle, the statement thread's `wait` / `assemble` / `put` leaves,
`Result.stats`' split of `stage_ms` as sums of those spans, which spans
still sample device memory, the `gg:` mirror on the profiler's clock, and
the benchmark's reader of idle time (`benchmark/metrics/spans.py`).
"""

import os
import sys
import threading
from types import SimpleNamespace

import numpy as np
import pytest

import greengage_tpu
from greengage_tpu.runtime import trace as trace_mod
from greengage_tpu.runtime.faultinject import faults
from greengage_tpu.runtime.trace import TRACES, Trace, TraceRegistry

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
N = 60_000
LEAVES = ("wait", "assemble", "put")
SPLIT_KEYS = ("stage_wait_ms", "stage_assemble_ms", "stage_put_ms",
              "stage_put_bytes", "stage_read_units", "read_io_ms",
              "read_decode_ms", "read_bytes")
Q = "select g, count(*), sum(v) from sp_a group by g order by g"


@pytest.fixture(scope="module")
def db(devices8):
    d = greengage_tpu.connect(numsegments=4)
    for t, n in (("sp_a", N), ("sp_b", 2 * N)):
        d.sql(f"create table {t} (k bigint, g int, v bigint) "
              "distributed by (k)")
        d.load_table(t, {"k": np.arange(n), "g": (np.arange(n) % 5)
                         .astype(np.int32), "v": np.arange(n) * 3})
    d.sql("analyze")
    d.sql(Q)   # compile once: the tests below look at warm programs
    d.sql(Q.replace("sp_a", "sp_b"))
    return d


def cold(db, q=Q):
    """One statement with nothing of its tables cached -> (result, trace)."""
    db.executor.stager.stage_cache.clear()
    db.store.blockcache.clear()
    r = db.sql(q)
    return r, trace_of(q)


def trace_of(q):
    """The newest retired trace of this statement text."""
    return [t for t in TRACES.between(0.0, float("inf")) if t.sql == q][-1]


def by_name(spans, name):
    return [s for s in spans if s["name"] == name]


def test_cold_scan_records_read_spans_from_pool_threads(db):
    r, tr = cold(db)
    spans = tr.export()
    stage = by_name(spans, "stage")[0]
    reads = by_name(spans, "read:sp_a")
    # one unit a (segment, column), each recorded on its gg-stage thread, in
    # the statement's own trace, hung under the statement thread's `stage`
    assert sorted((s["args"]["segment"], s["args"]["column"])
                  for s in reads) == [(seg, c) for seg in range(4)
                                      for c in ("g", "v")]
    assert all(s["tid"].startswith("gg-stage") for s in reads), reads
    assert all(s["parent"] == stage["id"] for s in reads)
    assert stage["tid"] == by_name(spans, "statement")[0]["tid"] != reads[0]["tid"]
    for s in reads:
        a = s["args"]
        assert a["files"] == 1 and a["cache_hits"] == 0   # its one column
        assert 0 < a["bytes_read"] < a["bytes_decoded"]
        assert 0 <= a["io_ms"] and 0 < a["decode_ms"]
        assert a["io_ms"] + a["decode_ms"] <= s["dur"] + 0.01
        assert "hbm_bytes" not in a
    # an int32 and an int64 column of every row, over the eight units
    assert sum(s["args"]["bytes_decoded"] for s in reads) == N * 12
    # the counter that says the mechanism engaged: segments x column units,
    # on the table's span and (counted from the `read:` spans) in the stats
    assert by_name(spans, "stage:sp_a")[0]["args"]["read_units"] == 8
    assert r.stats["stage_read_units"] == len(reads) == 8
    plan = cold(db, "explain analyze " + Q)[0].plan_text
    assert "Host data path: staging" in plan and "(8 read units)" in plan


def test_columns_of_one_segment_decode_side_by_side(db):
    # every unit sleeps before it reads, so with a thread a unit the two
    # columns of a segment must be in flight at once, on two threads
    db.sql("set scan_threads = 8")
    faults.inject("cancel_in_staging", "sleep", sleep_s=0.1, occurrences=-1)
    try:
        _r, tr = cold(db)
    finally:
        faults.reset("cancel_in_staging")
        db.sql("set scan_threads = 0")
    reads = by_name(tr.export(), "read:sp_a")
    for seg in range(4):
        g, v = sorted((s for s in reads if s["args"]["segment"] == seg),
                      key=lambda s: s["args"]["column"])
        assert g["tid"] != v["tid"] and g["tid"].startswith("gg-stage")
    assert len({s["tid"] for s in reads}) == 8


def test_first_column_lands_before_the_last_unit_is_read(db):
    """The statement thread consumes column by column: under a sleep in
    every unit and two pool threads, the units run two at a time in the
    order they were handed over (column-major: g of four segments, then
    v), so `wait` for column g must be over a unit's sleep or more before
    the last v unit ends — and g's assemble and put with it. Waiting for
    the whole table first would end the first `wait` after every read."""
    nap = 0.1
    db.sql("set scan_threads = 2")
    faults.inject("cancel_in_staging", "sleep", sleep_s=nap, occurrences=-1)
    try:
        _r, tr = cold(db)
    finally:
        faults.reset("cancel_in_staging")
        db.sql("set scan_threads = 0")
    spans = tr.export()
    table = by_name(spans, "stage:sp_a")[0]
    kids = [s for s in spans if s["parent"] == table["id"]]

    def end(s):
        return s["ts"] + s["dur"]

    reads = sorted(by_name(spans, "read:sp_a"), key=lambda s: s["ts"])
    assert [s["args"]["column"] for s in reads] == ["g"] * 4 + ["v"] * 4
    last_read = max(end(s) for s in reads)
    waits = by_name(kids, "wait")
    assert len(waits) == 2
    # four sleeps on two threads are behind column g, eight behind v
    assert waits[0]["dur"] >= 2 * nap * 1e3 - 1
    assert end(waits[0]) <= last_read - 0.9 * nap * 1e3, (waits, reads)
    first_put = by_name(kids, "put")[0]
    assert end(waits[0]) <= first_put["ts"] and end(first_put) < last_read
    assert end(waits[1]) >= last_read - 1


def test_prune_stats_count_a_segment_once(db):
    q = "select sum(v), sum(k) from sp_a where g < 3"
    r, tr = cold(db, q)
    reads = by_name(tr.export(), "read:sp_a")
    assert sorted({s["args"]["column"] for s in reads}) == ["g", "k", "v"]
    # what one read of each segment reports, summed over the segments
    snap = db.store.manifest.snapshot()
    want = [0, 0]
    for seg in range(4):
        db.store.read_segment("sp_a", seg, ["g"], snap,
                              prune=(("g", "<", 3),))
        want = [w + x for w, x in zip(want, db.store.last_prune)]
    assert want[1] >= 4
    assert tuple(r.stats["zone_prune"]["sp_a"]) == tuple(want)


def test_count_star_stages_through_one_unit_a_segment(db):
    q = "select count(*) from sp_b"
    r, tr = cold(db, q)
    assert r.rows() == [(2 * N,)]
    spans = tr.export()
    reads = by_name(spans, "read:sp_b")
    # the planner keeps one narrow column for a count(*)
    assert sorted((s["args"]["segment"], s["args"]["files"])
                  for s in reads) == [(seg, 1) for seg in range(4)]
    assert len({s["args"]["column"] for s in reads}) == 1
    table = by_name(spans, "stage:sp_b")[0]
    assert table["args"]["rows"] == 2 * N and table["args"]["read_units"] == 4
    kids = [s for s in spans if s["parent"] == table["id"]]
    assert len(by_name(kids, "wait")) == 1 and len(by_name(kids, "put")) == 2
    # and a spec with NO storage column still has a unit a segment, which
    # reads no file and carries the segment's row count
    snap = db.store.manifest.snapshot()
    got = [db.executor.stager._read_unit("sp_b", None, seg, [], snap, None, None)
           for seg in range(4)]
    assert all(c == {} and v == {} for c, v, *_ in got)
    assert sum(got_n for _c, _v, got_n, *_ in got) == 2 * N


def test_virtual_columns_of_one_raw_column_share_a_unit(db):
    from greengage_tpu.exec.staging import column_units

    assert column_units(["a", "b"]) == [["a"], ["b"]]
    assert column_units([]) == [[]]
    hp = "@hp:s:7b7d"
    assert column_units(["@rl:s", "@rp:s:0", "@rp:s:1", "k", hp, "@rw:t:2",
                         "s", "@rc:t"]) == [
        ["@rl:s", "@rp:s:0", "@rp:s:1", hp, "s"], ["k"], ["@rw:t:2", "@rc:t"]]
    db.sql("create table sp_raw (k int, s text) distributed by (k)")
    object.__setattr__(db.catalog.get("sp_raw").column("s"), "encoding", "raw")
    strs = np.array([f"row-{i:05d}" for i in range(2000)], dtype=object)
    db.load_table("sp_raw", {"k": np.arange(2000), "s": strs})
    q = "select k from sp_raw where s = 'row-00007'"
    r, tr = cold(db, q)
    assert r.rows() == [(7,)]
    reads = by_name(tr.export(), "read:sp_raw")
    by_seg = {}
    for s in reads:
        by_seg.setdefault(s["args"]["segment"], []).append(s["args"]["column"])
    assert sorted(by_seg) == [0, 1, 2, 3]
    for units in by_seg.values():
        # k alone; every '@rp:s:<w>' lane and '@rl:s' in ONE unit
        raw = [u for u in units if u != "k"]
        assert sorted(units) == sorted(raw + ["k"]) and len(raw) == 1
        cols = raw[0].split(",")
        assert "@rl:s" in cols and any(c.startswith("@rp:s:") for c in cols)
    assert r.stats["stage_read_units"] == 8


def test_leaves_account_for_the_table_stage_span(db):
    _r, tr = cold(db)
    spans = tr.export()
    table = by_name(spans, "stage:sp_a")[0]
    assert table["args"]["kind"] == "read"
    kids = [s for s in spans if s["parent"] == table["id"]]
    assert {s["name"] for s in kids} == set(LEAVES)
    # one `wait` a column unit (g, v): the first hands the units over
    assert len(by_name(kids, "wait")) == 2
    # g, v and the `present` column: one assemble and one put each, then
    # the assemble that lets go of the table's host copies
    assert len(by_name(kids, "put")) == 3
    assert [s["args"] for s in by_name(kids, "assemble")] == [
        {}, {}, {}, {"release": True}]
    assert sum(s["args"]["bytes"] for s in by_name(kids, "put")) \
        == table["args"]["bytes"]
    covered = sum(s["dur"] for s in kids)
    assert covered <= table["dur"] + 0.01
    assert table["dur"] - covered <= max(0.02 * table["dur"], 1.0), (
        table["dur"], covered)


def test_stats_split_equals_the_span_sums(db):
    r, tr = cold(db)
    spans = tr.export()
    st = r.stats
    for leaf in LEAVES:
        assert st[f"stage_{leaf}_ms"] == round(
            sum(s["dur"] for s in by_name(spans, leaf)), 3)
    reads = by_name(spans, "read:sp_a")
    assert st["stage_put_bytes"] == sum(
        s["args"]["bytes"] for s in by_name(spans, "put"))
    assert st["read_io_ms"] == round(sum(s["args"]["io_ms"] for s in reads), 3)
    assert st["read_decode_ms"] == round(
        sum(s["args"]["decode_ms"] for s in reads), 3)
    assert st["read_bytes"] == sum(s["args"]["bytes_read"] for s in reads) > 0
    assert st["finalize_ms"] == by_name(spans, "finalize")[0]["dur"]
    # the split is of stage_ms: what the leaves leave over is the stage
    # prologue and Python between the spans
    split = st["stage_wait_ms"] + st["stage_assemble_ms"] + st["stage_put_ms"]
    assert split <= st["stage_ms"] + 0.05
    assert st["stage_ms"] - split <= max(0.02 * st["stage_ms"], 1.0)


def test_stage_cache_hit_records_no_leaf_span(db):
    cold(db)
    r = db.sql(Q)   # staged arrays are cached now
    spans = TRACES.last().export()
    assert by_name(spans, "stage:sp_a")[0]["args"]["kind"] == "hit"
    assert not [s for s in spans
                if s["name"] in LEAVES or s["name"].startswith("read:")]
    assert all(r.stats[k] == 0 for k in SPLIT_KEYS)
    # a hoisted literal is put on the mesh every statement: that `put` hangs
    # under `stage` itself, and is all the leaf a hit statement records
    qp = Q.replace("group by", "where v > 3 group by")
    db.sql(qp)
    r = db.sql(qp)
    spans = TRACES.last().export()
    leaves = [s for s in spans
              if s["name"] in LEAVES or s["name"].startswith("read:")]
    assert leaves and {s["name"] for s in leaves} == {"put"}
    assert all(s["parent"] == by_name(spans, "stage")[0]["id"] for s in leaves)
    assert r.stats["stage_put_bytes"] == sum(s["args"]["bytes"] for s in leaves)
    assert r.stats["stage_wait_ms"] == r.stats["read_bytes"] == 0


def test_untraced_statement_has_no_split(db):
    db.sql("set trace_enabled = off")
    try:
        r, _ = cold(db)
    finally:
        db.sql("set trace_enabled = on")
    assert r.stats["stage_ms"] > 0
    assert not [k for k in SPLIT_KEYS + ("finalize_ms",) if k in r.stats]


def test_inline_pool_keeps_the_split_exhaustive(db):
    db.sql("set scan_threads = 1")
    try:
        r, tr = cold(db)
    finally:
        db.sql("set scan_threads = 0")
    spans = tr.export()
    reads, wait = by_name(spans, "read:sp_a"), by_name(spans, "wait")[0]
    # the units ran on the statement thread, inside `wait`, and still hang
    # under `stage`
    assert len(reads) == 8 and all(s["tid"] == wait["tid"] for s in reads)
    assert all(s["parent"] == by_name(spans, "stage")[0]["id"] for s in reads)
    # all eight inside the FIRST `wait`, where the units are handed over
    assert sum(s["dur"] for s in reads) <= wait["dur"] + 0.01
    assert all(wait["ts"] <= s["ts"] and s["ts"] + s["dur"]
               <= wait["ts"] + wait["dur"] + 0.01 for s in reads)
    assert r.stats["read_bytes"] == sum(s["args"]["bytes_read"] for s in reads)
    assert r.stats["stage_read_units"] == 8
    st = r.stats
    split = st["stage_wait_ms"] + st["stage_assemble_ms"] + st["stage_put_ms"]
    assert st["stage_ms"] - split <= max(0.02 * st["stage_ms"], 1.0)


def test_concurrent_statements_do_not_share_read_accounts(db):
    qa, qb = Q, Q.replace("sp_a", "sp_b")
    solo = {q: cold(db, q)[0].stats["read_bytes"] for q in (qa, qb)}
    assert 0 < solo[qa] < solo[qb]
    db.executor.stager.stage_cache.clear()
    db.store.blockcache.clear()
    gate, out = threading.Barrier(2), {}

    def run(q):
        gate.wait(timeout=30)
        out[q] = db.sql(q).stats

    threads = [threading.Thread(target=run, args=(q,)) for q in (qa, qb)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads) and len(out) == 2
    for q, table in ((qa, "read:sp_a"), (qb, "read:sp_b")):
        st = out[q]
        # compressed bytes are a count, so a unit that summed into the
        # other statement's account (or a process-wide one) shows exactly
        assert st["read_bytes"] == solo[q]
        # and each statement's trace holds only its own table's units,
        # whose times are what its stats report
        reads = [s for s in trace_of(q).export()
                 if s["name"].startswith("read:")]
        assert {s["name"] for s in reads} == {table}
        assert st["read_io_ms"] == round(
            sum(s["args"]["io_ms"] for s in reads), 3)
        assert st["read_decode_ms"] == round(
            sum(s["args"]["decode_ms"] for s in reads), 3) > 0


@pytest.mark.parametrize("q", [Q, "select sum(v), sum(k) from sp_a where g < 3"],
                         ids=["plain", "predicate"])
@pytest.mark.parametrize("warm", [False, True], ids=["miss", "hit"])
def test_a_unit_fills_its_slot_on_its_own_thread(db, q, warm):
    """The in-place protocol as the trace shows it: `in_slot` on every
    `read:` span says how the rows reached the staging slot, decoded there
    or (a block-cache hit) copied there by the pool thread that ran the
    unit; no `assemble` span of the statement thread holds a copy."""
    r, tr = cold(db, q)
    if warm:
        db.executor.stager.stage_cache.clear()
        r, tr = db.sql(q), trace_of(q)
    spans = tr.export()
    reads, stage = by_name(spans, "read:sp_a"), by_name(spans, "stage")[0]
    assert {s["args"]["in_slot"] for s in reads} \
        == {"copy" if warm else "decode"}
    for s in reads:
        a = s["args"]
        assert s["tid"].startswith("gg-stage") and s["parent"] == stage["id"]
        assert (a["cache_hits"], a["files"]) == ((1, 0) if warm else (0, 1))
        assert a["copy_ms"] >= 0 and (a["decode_ms"] == 0) == warm
        assert a["copy_ms"] + a["decode_ms"] + a["io_ms"] <= s["dur"] + 0.01
    # the statement thread's `assemble` spans stay leaves: no unit ran
    # inside one
    parents = {s["parent"] for s in spans}
    assert not [s for s in by_name(spans, "assemble") if s["id"] in parents]
    table = by_name(spans, "stage:sp_a")[0]["args"]
    assert table["units_in_slot"] == table["read_units"] == len(reads)
    assert r.stats["stage_units_in_slot"] == r.stats["stage_units"] \
        == r.stats["stage_read_units"] == len(reads)


def test_explicit_parent_and_subtree():
    tr = Trace(7, "probe")
    root = tr.begin("stage")
    got = {}

    def unit():
        assert tr.top() is None   # a pool thread's own stack is empty
        sid = tr.begin("read:t", cat="stage", parent=root, segment=0)
        got["inner"] = tr.begin("inner")    # default: this thread's stack
        tr.end(got["inner"])
        tr.end(sid, io_ms=1.5)
        got["sid"] = sid

    t = threading.Thread(target=unit, name="gg-stage_9")
    t.start()
    t.join(timeout=10)
    other = tr.begin("put")
    tr.end(other)
    assert tr.top() == root
    tr.end(root)
    loose = tr.begin("finalize")
    tr.end(loose)
    spans = {s["id"]: s for s in tr.export()}
    assert spans[got["sid"]]["parent"] == root
    assert spans[got["sid"]]["tid"] == "gg-stage_9"
    assert spans[got["inner"]]["parent"] == got["sid"]
    assert [s["id"] for s in tr.subtree(root)] == [
        root, got["sid"], got["inner"], other]
    assert [s["name"] for s in tr.subtree(loose)] == ["finalize"]
    assert tr.subtree(999) == []


@pytest.mark.parametrize("name,samples", [
    ("stage", True), ("stage:lineitem", True), ("dispatch", True),
    ("fetch", True), ("spill-pass", True), ("spill-merge", True),
    ("motion-stage", True), ("motion-compute", True), ("batch-dispatch", True),
    ("statement", False), ("parse", False), ("paramize", False),
    ("plan", False), ("bind", False), ("admission", False),
    ("compile", False), ("finalize", False), ("wait", False),
    ("assemble", False), ("put", False), ("read:lineitem", False),
    ("batch-wait", False), ("batch-member", False)])
def test_only_spans_across_which_hbm_can_change_sample(monkeypatch, name,
                                                       samples):
    calls = []
    monkeypatch.setattr(trace_mod, "MEM_SAMPLER",
                        lambda: calls.append(1) or 1 << 20)
    tr = Trace(1, "probe")
    tr.end(tr.begin(name))
    span = tr.export()[0]
    assert len(calls) == (2 if samples else 0)
    assert ("hbm_bytes" in span["args"]) == samples
    assert ("hbm_delta" in span["args"]) == samples


def test_cold_scan_never_samples_on_leaf_or_pool_spans(db, monkeypatch):
    where = []
    monkeypatch.setattr(
        trace_mod, "MEM_SAMPLER",
        lambda: where.append(threading.current_thread().name) or 1 << 20)
    _r, tr = cold(db)
    spans = tr.export()
    sampled = {s["name"] for s in spans if "hbm_bytes" in s["args"]}
    assert sampled == {"stage", "stage:sp_a", "dispatch", "fetch"}
    assert len(where) == 2 * len(sampled)
    assert not [w for w in where if w.startswith("gg-stage")]
    # a span that still samples carries the watermark and its delta
    st = by_name(spans, "stage:sp_a")[0]["args"]
    assert st["hbm_end_bytes"] - st["hbm_bytes"] == st["hbm_delta"] == 0


class FakeAnnotation:
    log: list = []

    def __init__(self, name, **kw):
        self.name, self.kw = name, kw

    def __enter__(self):
        self.log.append(("enter", self.name, self.kw,
                         threading.current_thread().name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, self.kw,
                         threading.current_thread().name))


def test_spans_mirror_into_profiler_annotations(monkeypatch):
    FakeAnnotation.log = []
    monkeypatch.setattr(trace_mod, "_ANNOTATION", FakeAnnotation)
    tr = Trace(42, "probe")
    a = tr.begin("stage:t", cat="stage")
    b = tr.begin("put", bytes=8)
    tr.end(b)
    tr.end(a)
    tr.end(a)   # ending twice leaves the annotation once
    # grafted worker spans are records, not annotations
    tr.graft([{"id": 1, "parent": None, "name": "dispatch", "ts": 0.0,
               "dur": 1.0}], a, tid="worker-1")
    me = threading.current_thread().name
    assert FakeAnnotation.log == [
        ("enter", "gg:stage:t", {"trace_id": 42}, me),
        ("enter", "gg:put", {"trace_id": 42}, me),
        ("exit", "gg:put", {"trace_id": 42}, me),
        ("exit", "gg:stage:t", {"trace_id": 42}, me)]
    assert not tr._mirrors
    # and with no jax.profiler to mirror into, spans record as before
    monkeypatch.setattr(trace_mod, "_ANNOTATION", False)
    tr.end(tr.begin("fetch"))
    assert tr.export()[-1]["dur"] is not None and len(FakeAnnotation.log) == 4


def test_real_annotation_is_harmless_without_a_profiler_session():
    import jax.profiler

    trace_mod._ANNOTATION = None   # resolve the real class anew
    tr = Trace(1, "probe")
    tr.end(tr.begin("dispatch"))
    assert trace_mod._ANNOTATION is jax.profiler.TraceAnnotation
    assert tr.export()[0]["dur"] >= 0


def test_registry_between():
    reg = TraceRegistry(ring_size=8)
    made = []
    for i in range(3):
        tr, outer = reg.enter(i + 1, f"q{i}")
        tr.t0 = 100.0 + 10 * i
        reg.exit(tr)
        made.append(tr)
    live, _ = reg.enter(9, "in flight")
    live.t0 = 105.0
    assert reg.between(100.0, 110.0) == made[:2]
    assert reg.between(100.1, 119.9) == made[1:2]
    assert reg.between(0.0, 99.0) == []
    reg.exit(live)
    assert reg.between(104.0, 106.0) == [live]


# ---- benchmark/metrics/spans.py on a profile worked out by hand ----------

def load_reader():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_metrics_spans", os.path.join(BENCH, "metrics", "spans.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def hand_built(monkeypatch, t0s=(1000.0, 1010.0), offset=-995.0):
    """Two statements. On time.monotonic() the first runs 1000..1008, the
    second 1010..1016; the profiler's clock is `offset` away, so the marks
    sit at 5..13 and 15..21. Spans (ms from the statement's start):

      q1.0  statement 0..8000: parse 0..500, stage 1000..6000 holding
            stage:t 1000..6000 with wait 1000..4000, assemble 4000..4500,
            put 4500..5800 (200 ms of stage:t under no leaf), dispatch
            6000..7500, a read:t on a pool thread 1100..3900
      q6.0  statement 0..6000: stage 0..2000 holding stage:t (a cache hit,
            a leaf itself), dispatch 2500..6000

    Device 0 is busy 11.2..12.4 (inside q1.0's dispatch), 12.9..13.5 (past
    q1.0's end at 13), 17.5..21 (q6.0's dispatch) and 16.5..16.8 (inside
    q6.0's stage).
    """
    from devtrace import Profile

    reg = TraceRegistry(ring_size=8)
    plans = [
        [("statement", None, 0, 8000), ("parse", 1, 0, 500),
         ("stage", 1, 1000, 5000), ("stage:t", 3, 1000, 5000),
         ("wait", 4, 1000, 3000), ("assemble", 4, 4000, 500),
         ("put", 4, 4500, 1300), ("dispatch", 1, 6000, 1500)],
        [("statement", None, 0, 6000), ("stage", 1, 0, 2000),
         ("stage:t", 2, 0, 2000), ("dispatch", 1, 2500, 3500)]]
    for i, (plan, t0) in enumerate(zip(plans, t0s)):
        tr, _ = reg.enter(i + 1, "q")
        tr.t0 = t0
        tr.graft([{"id": j + 1, "parent": p, "name": n, "ts": ts, "dur": d}
                  for j, (n, p, ts, d) in enumerate(plan)], None,
                 tid="MainThread")
        if i == 0:
            tr.graft([{"id": 1, "parent": None, "name": "read:t",
                       "ts": 100, "dur": 2800}], 3, tid="gg-stage_0")
        reg.exit(tr)
    monkeypatch.setattr(trace_mod, "TRACES", reg)
    ops = [("fusion.1", 11.2, 1.2), ("fusion.2", 12.9, 0.6),
           ("fusion.3", 16.5, 0.3), ("fusion.4", 17.5, 3.5)]
    marks = [("window", 4.0, 18.0), ("q1.0", 1000.0 + offset, 8.0),
             ("q6.0", 1010.0 + offset, 6.0)]
    window = [{"query": "q1", "t0": 1000.0, "t1": 1008.0},
              {"query": "q6", "t0": 1010.0, "t1": 1016.0}]
    return SimpleNamespace(profile=Profile({"/device:TPU:0": ops}, marks),
                           window=window)


@pytest.mark.parametrize("spec,want_ms", [
    # q1.0: stage 6..11, no op inside: 5.0 s idle. q6.0: stage 15..17 less
    # the 0.3 s of fusion.3: 1.7 s. (5.0 + 1.7) / 2 statements
    ({"kind": "span_idle", "spans": "stage"}, 3350.0),
    # q1.0: mark 5..13, busy 11.2..12.4 and 12.9..13 -> idle 6.7 s; leaves
    # parse 5..5.5, wait 6..9, assemble 9..9.5, put 9.5..10.8, dispatch
    # 11..12.5 cover 6.8 s of the mark, 1.2 s of it busy -> 5.6 s of the
    # idle time is named, 1.1 s is not (5.5..6, 10.8..11, 12.5..12.9).
    # q6.0: mark 15..21, busy 0.3 + 3.5 -> idle 2.2 s; leaves stage:t
    # 15..17 (1.7 s idle) and dispatch 17.5..21 (all busy) -> 0.5 s not
    # named (17..17.5). (1.1 + 0.5) / 2
    ({"kind": "span_idle", "spans": "leaf", "inside": False}, 800.0),
    ({"kind": "span_idle", "spans": "leaf"}, (5.6 + 1.7) / 2 * 1e3),
    ({"kind": "span_idle", "spans": "dispatch"}, (0.3 + 0.0) / 2 * 1e3)])
def test_read_span_idle_by_hand(monkeypatch, spec, want_ms):
    mod = load_reader()
    ctx = hand_built(monkeypatch)
    assert mod.read_span_idle(spec, ctx) == pytest.approx(want_ms, abs=1e-6)


def test_read_span_idle_refuses_clocks_that_disagree(monkeypatch):
    mod = load_reader()
    ctx = hand_built(monkeypatch)
    # the second statement's mark sits 2 ms later on the profiler's clock
    # than its record says: no offset lays the clocks over one another
    ctx.profile.marks[1] = ("q6.0", 15.002, 6.0)
    with pytest.raises(ValueError, match="disagree"):
        mod.read_span_idle({"kind": "span_idle", "spans": "stage"}, ctx)
    ctx.profile.marks[1] = ("q6.0", 15.0005, 6.0)   # half a millisecond: fine
    assert mod.read_span_idle({"kind": "span_idle", "spans": "stage"}, ctx) > 0


def test_read_span_idle_reads_nothing_where_nothing_is(monkeypatch):
    mod = load_reader()
    spec = {"kind": "span_idle", "spans": "stage"}
    ctx = hand_built(monkeypatch)
    ctx.window[1]["t0"] += 0.5   # the second trace started before "its" record
    assert mod.read_span_idle(spec, ctx) is None
    # a statement/mark mismatch is a fault of the harness: loud
    ctx = hand_built(monkeypatch)
    ctx.window.pop()
    with pytest.raises(ValueError, match="statement marks"):
        mod.read_span_idle(spec, ctx)
    ctx = hand_built(monkeypatch)
    ctx.window[0]["query"] = "q3"
    with pytest.raises(ValueError, match="pairs with"):
        mod.read_span_idle(spec, ctx)
    # the parent program has no TRACES.between: nothing to read, no raise
    ctx = hand_built(monkeypatch)
    monkeypatch.setattr(trace_mod, "TRACES", object())
    assert mod.read_span_idle(spec, ctx) is None
    assert mod.read_span_idle(spec, SimpleNamespace(profile=None)) is None
