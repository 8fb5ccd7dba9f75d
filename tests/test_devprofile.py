"""Device time by plan node (ISSUE 39): every plan node's scope names the
node (`<kind>#<n>`), the program's own executable gives {instruction: scope
path} (`CompileResult.node_map()`), every `dispatch` span says which program
it ran, and `runtime/devprofile.py` reduces a device line's operations to
self time by node with each dispatch's head and tail. `EXPLAIN ANALYZE`
prints what was measured where a capture gives one. CPU: names, counts and
arithmetic on hand-made operation tuples, never a time."""

import os
import random
import sys

import numpy as np
import pytest

import greengage_tpu
from greengage_tpu.exec import compile as compile_mod
from greengage_tpu.exec.compile import NODE_KINDS, PART_NAMES
from greengage_tpu.runtime import devprofile
from greengage_tpu.runtime.devprofile import NO_NODE, by_node, self_times
from greengage_tpu.runtime.trace import TRACES
from greengage_tpu.sql.parser import parse

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TWO_JOINS = ("select dw.w, dz.z, sum(f.v), count(*) from f "
             "join dw on f.k = dw.k join dz on f.k = dz.k "
             "where f.v > 10 group by dw.w, dz.z order by 1, 2")


@pytest.fixture(scope="module")
def db(devices8):
    d = greengage_tpu.connect(numsegments=2)
    d.sql("create table f (k int, v int) distributed by (k)")
    d.sql("create table dw (k int, w int) distributed by (k)")
    d.sql("create table dz (k int, z int) distributed by (k)")
    n = 2000
    d.load_table("f", {"k": np.arange(n, dtype=np.int32) % 500,
                       "v": np.arange(n, dtype=np.int32)})
    d.load_table("dw", {"k": np.arange(500, dtype=np.int32),
                        "w": (np.arange(500) % 7).astype(np.int32)})
    d.load_table("dz", {"k": np.arange(500, dtype=np.int32),
                        "z": (np.arange(500) % 3).astype(np.int32)})
    # a build side with duplicate keys: the join expands pair by pair
    d.sql("create table dup (k int, u int) distributed by (k)")
    d.load_table("dup", {"k": (np.arange(1000) % 500).astype(np.int32),
                         "u": np.arange(1000, dtype=np.int32)})
    d.sql("analyze")
    yield d
    d.close()


def _walk(plan):
    yield plan
    for c in plan.children:
        yield from _walk(c)


def _program_of(db, sql):
    """The statement run, and the cached CompileResult its last `dispatch`
    span names."""
    res = db.sql(sql)
    span = [s for s in TRACES.last().export() if s["name"] == "dispatch"][-1]
    comp = next(c for _k, c in db.executor.programs.items()
                if c.program_id == span["args"]["program"])
    return res, comp


def _innermost_labels(node_map: dict) -> set:
    kinds, parts = frozenset(NODE_KINDS), frozenset(PART_NAMES)
    return {devprofile._innermost(p, kinds, parts) for p in node_map.values()}


# ---------------------------------------------------------------------------
# (a) labels, the node map, the program id
# ---------------------------------------------------------------------------

def test_every_plan_node_has_its_own_label_in_the_executable(db, monkeypatch):
    parses = []
    parse_text = devprofile.parse_node_map
    monkeypatch.setattr(devprofile, "parse_node_map",
                        lambda text: parses.append(1) or parse_text(text))
    _res, comp = _program_of(db, TWO_JOINS)
    planned = db._cached_plan(parse(TWO_JOINS)[0])[0]
    # one label a node, over the whole tree EXPLAIN renders, Gather included
    nodes = list(_walk(planned))
    assert sorted(comp.node_labels.values()) == sorted(id(p) for p in nodes)
    assert len(set(comp.node_labels)) == len(nodes) == len(
        db.sql("explain " + TWO_JOINS).plan_text.strip().splitlines()) - 1
    for label in comp.node_labels:
        kind, _, n = label.partition("#")
        assert kind in NODE_KINDS and n.isdigit(), label
    assert sum(k.startswith("join#") for k in comp.node_labels) == 2
    # the executable's text has every node that computes something as the
    # innermost scope of an instruction (a Scan or a renaming Project hands
    # its inputs on and emits none), and no label the plan does not have
    node_map = comp.node_map()
    found = {label for label, _part in _innermost_labels(node_map)}
    assert found - {NO_NODE} <= set(comp.node_labels), found
    working = {k for k in comp.node_labels
               if k.split("#")[0] not in ("scan", "project")}
    assert working <= found, working - found
    # a warm program-cache hit is the same program: one id, one parse
    _res, again = _program_of(db, TWO_JOINS)
    assert again is comp and again.node_map() is node_map
    assert devprofile.node_map_of(comp.program_id) is node_map
    assert parses == [1]


def test_a_part_stays_bare_inside_its_node(db):
    _res, comp = _program_of(
        db, "select count(u) from dw left join dup on dw.k = dup.k")
    inner = _innermost_labels(comp.node_map())
    joins = {label for label, part in inner if part == "join-expand"}
    assert len(joins) == 1 and joins.pop().startswith("join#"), inner
    assert "join-expand" in PART_NAMES and "compact" in PART_NAMES
    assert not set(PART_NAMES) & set(NODE_KINDS)


def test_every_plan_class_has_a_kind():
    from greengage_tpu.planner import logical

    plans = [c for c in vars(logical).values() if isinstance(c, type)
             and issubclass(c, logical.Plan) and c is not logical.Plan]
    named = {c.__name__.lower() for c in plans} - {"join", "aggregate"}
    assert named | {"join", "semi", "agg-sort", "agg-dense"} == set(NODE_KINDS)


def test_a_program_without_an_executable_has_no_map(db):
    _res, comp = _program_of(db, "select count(*) from dz")
    bare = compile_mod.CompileResult(
        device_fn=None, input_spec=[], out_cols=[], flag_names=[],
        gather_child_locus=None, merge_keys=None, host_limit=None, capacity=0)
    assert bare.program_id > comp.program_id and bare.node_map() is None
    assert devprofile.node_map_of(bare.program_id) is None
    pid = bare.program_id
    del bare   # the registry is weak: nothing else held it
    assert devprofile.node_map_of(pid) is None
    assert devprofile.node_map_of(comp.program_id)


def test_scopes_are_metadata_only(db):
    """The labels ride the operations' debug info: the lowered text the
    compile cache keys (and tests/goldens/ digests) does not hold them."""
    _res, comp = _program_of(db, TWO_JOINS)
    args = db.executor.stager.shapes(comp)
    lowered = comp.device_fn.lower(*args)
    assert "agg-sort#" not in lowered.as_text() and "motion#0" not in \
        lowered.as_text()
    assert "agg-sort#" in lowered.as_text(debug_info=True)
    assert "module @jit_seg_fn" in lowered.as_text()


# ---------------------------------------------------------------------------
# (b) self times
# ---------------------------------------------------------------------------

def test_a_while_over_two_fusions_sums_to_the_while():
    ops = [("%while.3 = (s32[8]) while(...)", 10.0, 6.0),
           ("%fusion.1 = s32[8] fusion(...)", 10.5, 2.0),
           ("%fusion.2 = s32[8] fusion(...)", 13.0, 2.5),
           ("%fusion.9 = s32[8] fusion(...)", 17.0, 1.0)]
    assert self_times(ops) == [1.5, 2.0, 2.5, 1.0]
    assert sum(self_times(ops)) == 7.0   # not 6 + 2 + 2.5 + 1
    # whatever the order they are given in, and nested two deep
    deep = [("b", 1.0, 1.0), ("a", 0.0, 10.0), ("c", 1.25, 0.5)]
    assert self_times(deep) == [0.5, 9.0, 0.5]
    assert self_times([]) == []


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_self_times_sum_to_the_busy_union(seed):
    if os.path.join(ROOT, "benchmark") not in sys.path:
        sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    from devtrace import merge

    rng = random.Random(seed)
    ops = [(f"op.{i}", s, rng.random() * 3)
           for i, s in enumerate(rng.random() * 50 for _ in range(200))]
    union = sum(b - a for a, b in merge([(s, s + d) for _n, s, d in ops]))
    selfs = self_times(ops)
    assert all(0.0 <= x <= d + 1e-12 for x, (_n, _s, d) in zip(selfs, ops))
    assert sum(selfs) == pytest.approx(union, rel=1e-12)


# ---------------------------------------------------------------------------
# (c) by node
# ---------------------------------------------------------------------------

def test_by_node_by_arithmetic():
    first = {"fusion.12": "jit(seg_fn)/motion#0/sort#1/agg-sort#2/join#4/gather",
             "fusion.13": "jit(seg_fn)/motion#0/sort#1/agg-sort#2/join#4/"
                          "join-expand/gather",
             "while.3": "jit(seg_fn)/motion#0/sort#1/agg-sort#2/join#4/while",
             "fusion.7": "jit(seg_fn)/motion#0/sort#1/agg-sort#2/join#4/"
                         "while/body/add",
             "sort.2": "jit(seg_fn)/motion#0/sort#1/agg-sort#2/jit(sort)/sort",
             "fusion.20": "jit(seg_fn)/motion#0/broadcast_in_dim"}
    # compiled by a program older than the index: bare kinds, and an
    # operation of its own called like a kind
    second = {"fusion.12": "jit(seg_fn)/sort/agg-dense/reduce_sum",
              "sort.2": "jit(seg_fn)/sort/jit(sort)/sort",
              "fusion.1": "jit(seg_fn)/scan"}
    ops = [("%fusion.12 = u32[8] fusion(...)", 10.5, 1.0),
           ("%fusion.13 = u32[8] fusion(...)", 11.5, 2.0),
           ("%while.3 = (s32[8]) while(...)", 14.0, 3.0),
           ("%fusion.7 = s32[8] fusion(...)", 14.5, 1.0),
           ("%fusion.7 = s32[8] fusion(...)", 15.75, 1.0),
           ("%sort.2 = s32[8] sort(...)", 17.0, 0.5),
           ("%fusion.20 = s32[1] fusion(...)", 17.5, 0.25),
           ("%copy-done.1 = s32[8] copy-done(...)", 17.75, 0.25),  # no path
           ("%fusion.12 = f32[3] fusion(...)", 21.0, 0.5),
           ("%sort.2 = s32[8] sort(...)", 21.5, 0.25),
           ("%fusion.1 = s32[8] fusion(...)", 21.75, 0.25),
           ("%fusion.12 = f32[3] fusion(...)", 31.0, 2.0),   # a map-less one
           ("%fusion.12 = f32[3] fusion(...)", 40.0, 1.0)]   # nobody's
    got = by_node(ops, [(10.0, 19.0, first), (20.0, 22.5, second),
                        (30.0, 34.0, None), (50.0, 51.0, first)])
    assert got.seconds == {
        ("join#4", None): 1.0 + (3.0 - 2.0) + 2.0,   # the while's own second
        ("join#4", "join-expand"): 2.0,
        ("agg-sort#2", None): 0.5,
        ("motion#0", None): 0.25,
        ("agg-dense", None): 0.5,
        ("sort", None): 0.25,
        (NO_NODE, None): 0.25 + 0.25 + 2.0 + 1.0}
    assert got.busy_s() == 7.0 + 1.0 + 2.0 + 1.0
    d0, d1, d2, d3 = got.dispatches
    assert (d0.span_s, d0.busy_s, d0.head_s, d0.tail_s) == (9.0, 7.0, 0.5, 1.0)
    assert d0.between_s == 0.5   # 13.5 to 14.0
    assert (d1.busy_s, d1.head_s, d1.tail_s, d1.between_s) == (
        1.0, 1.0, 0.5, 0.0)
    assert (d2.busy_s, d2.head_s, d2.tail_s, d2.between_s) == (
        2.0, 1.0, 1.0, 0.0)
    # a dispatch none of whose operations ran on this device: all head
    assert (d3.busy_s, d3.head_s, d3.tail_s) == (0.0, 1.0, 0.0)


def test_a_skewed_device_clock_moves_the_line_not_the_operations():
    """A trace's device clock sits within a few ms of the host's: the
    first operation of a program may read as started before its dispatch.
    It still belongs to that dispatch, whose head is then nothing and whose
    tail gives the difference back, so head + busy + between + tail stays
    the span; two spans closer than the slack part in the middle."""
    node_map = {"fusion.1": "jit(seg_fn)/motion#0/agg-dense#1/reduce_sum"}
    early = by_node([("%fusion.1 = f32[] fusion()", 99.998, 1.0),
                     ("%fusion.1 = f32[] fusion()", 101.0, 1.5)],
                    [(100.0, 103.0, node_map)])
    assert early.seconds == {("agg-dense#1", None): 2.5}
    (d,) = early.dispatches
    assert (d.head_s, d.busy_s) == (0.0, 2.5)
    assert d.tail_s == pytest.approx(0.5 - 0.002) and \
        d.between_s == pytest.approx(0.002)
    late = by_node([("%fusion.1 = f32[] fusion()", 100.5, 2.501)],
                   [(100.0, 103.0, node_map)])
    (d,) = late.dispatches
    assert d.tail_s == 0.0 and d.head_s == pytest.approx(0.499)
    # beyond the slack an operation is nobody's
    far = by_node([("%fusion.1 = f32[] fusion()", 99.5, 0.25)],
                  [(100.0, 103.0, node_map)])
    assert far.seconds == {(NO_NODE, None): 0.25}
    assert far.dispatches[0].head_s == 3.0
    # 4 ms apart: the operation at 103.003 is nearer the second span
    two = by_node([("%fusion.1 = f32[] fusion()", 103.0009765625, 0.0009765625),
                   ("%fusion.1 = f32[] fusion()", 103.0029296875, 0.5)],
                  [(103.00390625, 104.0, None), (100.0, 103.0, node_map)])
    assert two.seconds == {("agg-dense#1", None): 0.0009765625,
                           (NO_NODE, None): 0.5}
    assert [d.busy_s for d in two.dispatches] == [0.5, 0.0009765625]


def test_parse_node_map_reads_what_the_compiler_prints():
    text = '''HloModule jit_seg_fn, is_scheduled=true
%fused_computation (p: s32[8]) -> s32[8] {
  %p = s32[8]{0} parameter(0)
  ROOT %add.8 = s32[8]{0} add(%p, %p), metadata={op_name="jit(seg_fn)/motion#0/agg-sort#1/add" stack_frame_id=5}
}
%region_2.3 (a: s32[], b: s32[]) -> s32[] {
  %a = s32[] parameter(0), metadata={op_name="reduce_window_sum"}
  %b = s32[] parameter(1), metadata={op_name="reduce_window_sum"}
  ROOT %sum.5 = s32[] add(%a, %b), metadata={op_name="reduce_window_sum" stack_frame_id=6}
}
ENTRY %main.12 (x.1: s32[8]) -> s32[8] {
  %x.1 = s32[8]{0:T(1024)} parameter(0), metadata={op_name="x"}
  %copy-start = (s32[8]{0}, s32[8]{0}, u32[]) copy-start(%x.1)
  %copy-done = s32[8]{0} copy-done(%copy-start)
  %reduce-window.8 = s32[8]{0} reduce-window(%copy-done, %c), window={size=8}, to_apply=%region_2.3, backend_config={"x":"%not.an.operand"}
  %fusion.9 = s32[8]{0} fusion(%reduce-window.8), kind=kLoop, calls=%fc.1, metadata={op_name="reduce_window_sum" stack_frame_id=6}
  %fusion.2 = s32[8]{0:T(1024)} fusion(%fusion.9), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(seg_fn)/motion#0/agg-sort#1/add" stack_frame_id=5}, backend_config={"a":"="}
  %dead.1 = s32[8]{0} copy(%x.1)
  ROOT %while = (s32[8]{0}) while(%fusion.2), condition=%cond.1, body=%body.1, metadata={op_name="jit(seg_fn)/motion#0/join#2/while"}
}'''
    add, loop = ("jit(seg_fn)/motion#0/agg-sort#1/add",
                 "jit(seg_fn)/motion#0/join#2/while")
    assert devprofile.parse_node_map(text) == {
        "add.8": add, "p": add, "fusion.2": add, "while": loop,
        # made by the compiler, for the aggregate's add: a bare operation
        # name or no path at all, up the chain of consumers to the fusion
        "fusion.9": add, "reduce-window.8": add, "copy-done": add,
        "copy-start": add, "x.1": add,
        # a reducer's own instructions run as no operation: nobody's
        "a": "reduce_window_sum", "b": "reduce_window_sum",
        "sum.5": "reduce_window_sum"}
    assert devprofile.instruction_of(
        "%fusion.2 = s32[8]{0:T(1024)} fusion(s32[8] %x.1), kind=kLoop"
    ) == "fusion.2"


# ---------------------------------------------------------------------------
# (d) every dispatch says which program it ran
# ---------------------------------------------------------------------------

def _dispatch_spans():
    return [s for s in TRACES.last().export()
            if s["name"] == "dispatch" and s["cat"] == "device"]


@pytest.mark.parametrize("statement", ["select", "spill", "insert_select"])
def test_every_dispatch_span_carries_its_program(db, statement):
    if statement == "select":
        db.sql("select w, count(*) from dw group by w")
        spans = _dispatch_spans()
        assert len(spans) == 1
    elif statement == "spill":
        db.sql("create table big (k int, fk int, v int) distributed by (k)")
        rng = np.random.default_rng(8)
        nb = 120_000
        db.load_table("big", {"k": np.arange(nb, dtype=np.int32),
                              "fk": rng.integers(0, 500, nb).astype(np.int32),
                              "v": rng.integers(0, 100, nb).astype(np.int32)})
        db.sql("set vmem_protect_limit_mb = 1")
        try:
            r = db.sql("select w, count(*), sum(v) from big join dw "
                       "on big.fk = dw.k group by w order by w")
            spans = _dispatch_spans()   # a SET would be the last trace
        finally:
            db.sql("set vmem_protect_limit_mb = 12288")
        assert r.stats.get("spill_passes", 0) >= 2, r.stats
        assert len(spans) > r.stats["spill_passes"]   # the passes, the merge
    else:
        db.sql("create table sink (k int, v int) distributed by (k)")
        r = db.sql("insert into sink select k, v from f where v < "
                   "(select max(v) from f)")
        assert r.nrows == 1999
        spans = _dispatch_spans()
        assert len(spans) == 2   # the subquery, the scan
    for s in spans:
        assert isinstance(s["args"]["program"], int) and s["args"]["program"] > 0


# ---------------------------------------------------------------------------
# (e) EXPLAIN ANALYZE and the capture
# ---------------------------------------------------------------------------

def test_explain_analyze_on_the_cpu_keeps_its_line(db):
    text = db.sql("explain analyze " + TWO_JOINS).plan_text
    lines = text.splitlines()
    assert lines[0].startswith("Motion Gather") and "(" not in lines[0][14:]
    assert all("(host-attributed)" in ln and "device ~" in ln
               for ln in lines[1:13]), text
    assert "(measured" not in text and "Device:" not in text
    assert lines[13].startswith(" Plan cache:")


def test_capture_gives_none_when_the_profiler_refuses(monkeypatch):
    import jax

    calls = []

    def refuse(*a, **kw):
        raise RuntimeError("Profile has already been started. Only one "
                           "profile may be run at a time.")
    # a backend with a device plane, and a session of someone else's open
    monkeypatch.setattr(devprofile, "_has_device_plane", lambda: True)
    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    assert devprofile.capture(lambda: calls.append("ran") or 7) == (7, None)
    assert calls == ["ran"]   # once, and their session is not ours to stop
    # the CPU backend: no session is opened at all
    monkeypatch.setattr(devprofile, "_has_device_plane", lambda: False)
    assert devprofile.capture(lambda: 8) == (8, None)
    assert calls == ["ran"]


def test_capture_stops_its_session_when_the_statement_raises(monkeypatch):
    import jax

    calls = []
    monkeypatch.setattr(devprofile, "_has_device_plane", lambda: True)
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    with pytest.raises(ZeroDivisionError):
        devprofile.capture(lambda: 1 / 0)
    assert calls == ["start", "stop"]
    # ... and a profile that cannot be read back costs the figure only
    assert devprofile.capture(lambda: 9) == (9, None)


def _hand_made_capture(monkeypatch, dispatches=1, labels=None):
    """`devprofile.capture` replaced by one that runs the statement and
    returns operations made up from the program's own node map: 2 s in a
    join, 3 s under its second join... by instruction name."""
    made = {}

    def capture(fn):
        res = fn()
        node_map = res.stats["program"].node_map()
        kinds, parts = frozenset(NODE_KINDS), frozenset(PART_NAMES)
        by_label = {}
        for instr, path in sorted(node_map.items()):
            label, part = devprofile._innermost(path, kinds, parts)
            if part is None:   # one instruction of each node's own
                by_label.setdefault(label, instr)
        made["by_label"] = by_label
        ops, t = [], 100.5
        for label, instr in sorted(by_label.items()):
            dur = {"join": 2.0, "agg-sort": 0.5}.get(label.split("#")[0], 0.25)
            ops.append((f"%{instr} = s32[8] fusion(...)", t, dur))
            t += dur
        ops.append(("%made-by-the-compiler = s32[8] copy(...)", t, 0.125))
        made["end"] = t + 0.125
        return res, devprofile.Capture(
            ops, [(100.0, made["end"] + 1.0)] * dispatches)
    monkeypatch.setattr(devprofile, "capture", capture)
    return made


def test_explain_analyze_prints_what_was_measured(db, monkeypatch):
    made = _hand_made_capture(monkeypatch)
    text = db.sql("explain analyze " + TWO_JOINS).plan_text
    lines = text.splitlines()
    assert "(host-attributed)" not in text
    joins = [ln for ln in lines if ln.lstrip().startswith("Join inner")]
    assert len(joins) == 2 and all(
        "device 2,000.0 ms (measured)" in ln for ln in joins), joins
    aggs = [ln for ln in lines if ln.lstrip().startswith("Aggregate")]
    assert len(aggs) == 2 and all(
        "device 500.0 ms (measured)" in ln for ln in aggs), aggs
    # a node that ran no operation says nothing of the device
    scans = [ln for ln in lines if ln.lstrip().startswith("Scan ")
             and "rows=" in ln]
    assert len(scans) == 3 and all(
        "actual rows=" in ln and "device" not in ln for ln in scans), scans
    # the Gather ran the program's last steps
    assert lines[0].startswith("Motion Gather") and \
        "(device 250.0 ms (measured))" in lines[0]
    busy = sum({"join": 2.0, "agg-sort": 0.5}.get(k.split("#")[0], 0.25)
               for k in made["by_label"]) + 0.125
    span = made["end"] + 1.0 - 100.0
    no_node = 0.125 + (0.25 if NO_NODE in made["by_label"] else 0.0)
    device = next(ln for ln in lines if ln.startswith(" Device:"))
    assert device == (
        f" Device: {busy * 1e3:,.1f} ms busy of {span * 1e3:,.1f} ms "
        f"dispatch (head 500.0, tail 1,000.0), {no_node * 1e3:,.1f} ms "
        "under no node")
    # right under the tree's thirteen lines
    assert lines.index(device) == 13 and lines[14].startswith(" Plan cache:")


def test_a_part_is_shown_apart(db, monkeypatch):
    def capture(fn):
        res = fn()
        kinds, parts = frozenset(NODE_KINDS), frozenset(PART_NAMES)
        by = {}
        for instr, path in sorted(res.stats["program"].node_map().items()):
            by.setdefault(devprofile._innermost(path, kinds, parts), instr)
        (label,) = {k[0] for k in by if k[1] == "join-expand"}
        ops = [(f"%{by[label, None]} = s32[8] fusion(...)", 1.0, 0.4289),
               (f"%{by[label, 'join-expand']} = s32[8] fusion()", 2.0, 1.9042)]
        return res, devprofile.Capture(ops, [(0.5, 4.0)])
    monkeypatch.setattr(devprofile, "capture", capture)
    # (run plainly first: a cold EXPLAIN ANALYZE does not yet take the
    # duplicate-key retry a SELECT takes, in the parent either)
    db.sql("select count(u) from dw left join dup on dw.k = dup.k")
    text = db.sql("explain analyze select count(u) from dw left join dup "
                  "on dw.k = dup.k").plan_text
    join = next(ln for ln in text.splitlines() if "Join left" in ln)
    assert "device 2,333.1 ms (measured; join-expand 1,904.2)" in join, text
    assert " Device: 2,333.1 ms busy of 3,500.0 ms dispatch (head 500.0, " \
        "tail 95.8), 0.0 ms under no node" in text


def test_two_dispatches_are_not_a_measurement(db, monkeypatch):
    _hand_made_capture(monkeypatch, dispatches=2)
    text = db.sql("explain analyze " + TWO_JOINS).plan_text
    assert "(host-attributed)" in text and "(measured" not in text
    assert "Device:" not in text
