"""Observability subsystem (docs/OBSERVABILITY.md): statement tracing
spans, per-operator EXPLAIN ANALYZE, the Prometheus metrics exposition,
and the slow-statement log — the gpperfmon-analog PR's acceptance tests.
"""

import json
import os
import re
import socket
import subprocess
import sys

import numpy as np
import pytest

import greengage_tpu
from greengage_tpu.runtime.logger import (counters, histograms,
                                          prometheus_text, read_entries)
from greengage_tpu.runtime import trace as trace_mod
from greengage_tpu.runtime.trace import TRACES, TraceRegistry, to_chrome


@pytest.fixture(scope="module")
def db(devices8):
    d = greengage_tpu.connect(numsegments=4)
    d.sql("create table obs (k int, g int, v int) distributed by (k)")
    n = 5000
    d.load_table("obs", {"k": np.arange(n), "g": np.arange(n) % 7,
                         "v": np.arange(n) % 11})
    d.sql("create table dimt (g int, tag int) distributed by (g)")
    d.sql("insert into dimt values " + ",".join(
        f"({i},{i * 10})" for i in range(7)))
    # spill corpus (mirrors test_spill.py's shape at a smaller scale)
    d.sql("create table sdim (pk int, grp int) distributed by (pk)")
    d.sql("insert into sdim values " + ",".join(
        f"({i},{i % 11})" for i in range(1, 301)))
    d.sql("create table sbig (k int, fk int, v int) distributed by (k)")
    nb = 200_000
    rng = np.random.default_rng(8)
    d.load_table("sbig", {"k": np.arange(nb),
                          "fk": rng.integers(1, 301, nb),
                          "v": rng.integers(0, 100, nb)})
    d.sql("analyze")
    return d


# ---------------------------------------------------------------------------
# span tree
# ---------------------------------------------------------------------------

def test_span_tree_local_statement(db):
    db.sql("select g, count(*) from obs group by g order by g")
    tr = TRACES.last()
    assert tr is not None
    spans = tr.export()
    names = [s["name"] for s in spans]
    for want in ("statement", "parse", "stage", "stage:obs", "dispatch",
                 "fetch", "finalize"):
        assert want in names, names
    by_id = {s["id"]: s for s in spans}
    root = next(s for s in spans if s["name"] == "statement")
    assert root["parent"] is None
    # every other span parents (transitively) under the statement root
    for s in spans:
        if s["id"] == root["id"]:
            continue
        p = s
        hops = 0
        while p["parent"] is not None and hops < 50:
            p = by_id[p["parent"]]
            hops += 1
        assert p["id"] == root["id"], s
    # the per-table staging unit is a child of the stage phase
    st = next(s for s in spans if s["name"] == "stage")
    stt = next(s for s in spans if s["name"] == "stage:obs")
    assert stt["parent"] == st["id"]
    assert stt["args"].get("kind") in ("read", "hit", "dup")
    # durations recorded, non-negative
    assert all(s["dur"] is not None and s["dur"] >= 0 for s in spans)


def test_trace_id_is_statement_id_and_ring_lookup(db):
    db.sql("select count(*) from obs")
    tr = TRACES.last()
    assert tr.trace_id > 0
    assert TRACES.get(tr.trace_id) is tr


def test_chrome_export_shape(db):
    db.sql("select count(*) from obs where v > 3")
    ch = to_chrome(TRACES.last())
    evs = ch["traceEvents"]
    assert isinstance(evs, list) and evs
    xs = [e for e in evs if e.get("ph") == "X"]
    assert xs, evs
    for e in xs:
        assert isinstance(e["ts"], (int, float))
        assert isinstance(e["dur"], (int, float))
        assert "span_id" in e["args"] and "parent" in e["args"]
    # metadata names the threads; the whole thing round-trips JSON
    assert any(e.get("ph") == "M" and e.get("name") == "thread_name"
               for e in evs)
    json.loads(json.dumps(ch))
    assert ch["otherData"]["sql"].startswith("select count(*)")


def test_trace_disabled_records_nothing(db):
    db.sql("set trace_enabled = off")   # the SET itself is still traced
    try:
        last_id = TRACES.last().trace_id
        db.sql("select count(*) from obs")
        # no new ring entry: the statement ran untraced
        assert TRACES.last().trace_id == last_id
    finally:
        db.sql("set trace_enabled = on")


def test_active_span_registry_surface():
    reg = TraceRegistry()
    tr, outer = reg.enter(4242, "select 1", enabled=True)
    assert outer
    sid = tr.begin("stage", cat="stage")
    name, ms = reg.active_span(4242)
    assert name == "stage" and ms >= 0
    tr.end(sid)
    reg.exit(tr)
    assert reg.active_span(4242) is None
    assert reg.get(4242) is tr   # retired to the ring


def test_trace_ring_bounded():
    reg = TraceRegistry(ring_size=3)
    for i in range(10, 16):
        tr, _ = reg.enter(i, f"q{i}")
        reg.exit(tr)
    assert reg.get(10) is None and reg.get(12) is None
    assert reg.get(15) is not None
    assert reg.last().trace_id == 15


# ---------------------------------------------------------------------------
# per-operator EXPLAIN ANALYZE
# ---------------------------------------------------------------------------

def test_explain_analyze_per_node_rows_vs_oracle(db):
    r = db.sql("explain analyze select o.g, count(*), sum(o.v) "
               "from obs o join dimt d on o.g = d.g "
               "group by o.g order by o.g")
    text = r.plan_text
    # the scan of the fact table reports exactly its row count
    scan_line = next(ln for ln in text.splitlines() if "Scan obs" in ln)
    assert "actual rows=5000" in scan_line, scan_line
    # per-node device attribution on every instrumented node
    assert "device ~" in scan_line and "host-attributed" in scan_line
    # a Motion node reports moved bytes
    motion_lines = [ln for ln in text.splitlines()
                    if "Motion" in ln and "actual rows=" in ln]
    assert any("motion ~" in ln and re.search(r"motion ~\d+ B", ln)
               for ln in motion_lines), text
    # the legacy statement-level lines survive (tests + docs rely on them)
    assert "Host data path: staging" in text
    assert "Execution time:" in text


def test_explain_analyze_spill_per_node(db):
    q = ("select grp, count(*), sum(v) from sbig join sdim "
         "on sbig.fk = sdim.pk group by grp order by grp")
    want = db.sql(q).rows()
    db.sql("set vmem_protect_limit_mb = 2")
    try:
        r = db.sql(q)
        assert r.stats.get("spill_passes", 0) >= 2, r.stats
        assert r.rows() == want
        ea = db.sql("explain analyze " + q)
        text = ea.plan_text
        assert "Spill passes:" in text, text
        # per-plan-node rows survive spilling: the fact scan's count sums
        # across passes back to the full table cardinality
        scan_line = next(ln for ln in text.splitlines()
                         if "Scan sbig" in ln)
        assert "actual rows=200000" in scan_line, scan_line
        assert "device ~" in scan_line
        # spill passes leave spans in the trace
        names = [s["name"] for s in TRACES.last().export()]
        assert "spill-pass" in names and "spill-merge" in names, names
    finally:
        db.sql("set vmem_protect_limit_mb = 12288")


def test_explain_analyze_sort_spill_per_node(db):
    q = "select k, v from sbig where v >= 50 order by v desc, k limit 20"
    db.sql("set vmem_protect_limit_mb = 1")
    try:
        ea = db.sql("explain analyze " + q)
        text = ea.plan_text
        assert "Spill passes:" in text, text
        # sorted-run passes share node objects with the original plan, so
        # the scan's count sums across passes to the full cardinality
        scan_line = next(ln for ln in text.splitlines()
                         if "Scan sbig" in ln)
        assert "actual rows=200000" in scan_line, scan_line
    finally:
        db.sql("set vmem_protect_limit_mb = 12288")


# ---------------------------------------------------------------------------
# metrics exposition
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{le="([^"\\]+)"\})? '
    r'(-?[0-9.]+(?:[eE][+-]?[0-9]+)?|\+Inf|NaN)$')
_TYPE_RE = re.compile(r"^# TYPE ([a-zA-Z_:][a-zA-Z0-9_:]*) "
                      r"(counter|gauge|histogram|summary|untyped)$")


def _parse_exposition(text):
    """prometheus_client-style text parser: every line is a sample, a
    # TYPE comment, or blank; TYPE precedes its family's samples;
    histograms are cumulative with le="+Inf" == _count."""
    types, samples = {}, {}
    seen_families = set()
    for ln in text.splitlines():
        if not ln.strip():
            continue
        m = _TYPE_RE.match(ln)
        if m:
            assert m.group(1) not in types, f"duplicate TYPE: {ln}"
            types[m.group(1)] = m.group(2)
            continue
        assert not ln.startswith("#"), f"unknown comment: {ln}"
        m = _SAMPLE_RE.match(ln)
        assert m, f"unparseable sample line: {ln!r}"
        name, _, le, val = m.groups()
        fam = re.sub(r"_(bucket|sum|count)$", "", name) \
            if name.endswith(("_bucket", "_sum", "_count")) else name
        assert fam in types or name in types, \
            f"sample before TYPE: {ln}"
        seen_families.add(fam)
        samples.setdefault(name, []).append(
            (le, float(val.replace("+Inf", "inf"))))
    return types, samples


def test_metrics_exposition_parses(db):
    db.sql("select count(*) from obs")
    text = prometheus_text()
    types, samples = _parse_exposition(text)
    # counter vs gauge typing (satellite: gauge names must not be
    # mislabeled as counters)
    assert types.get("ggtpu_mh_topology_version") == "gauge"
    assert types.get("ggtpu_plan_cache_hit", "counter") == "counter"
    # the statement-latency histogram is present and well-formed
    assert types.get("ggtpu_statement_ms") == "histogram"
    buckets = samples["ggtpu_statement_ms_bucket"]
    vals = [v for _le, v in buckets]
    assert vals == sorted(vals), "buckets must be cumulative"
    inf = [v for le, v in buckets if le == "+Inf"]
    count = samples["ggtpu_statement_ms_count"][0][1]
    assert inf and inf[0] == count
    assert count >= 1
    assert samples["ggtpu_statement_ms_sum"][0][1] >= 0
    # host-data-path phase histograms ride along
    for fam in ("ggtpu_stage_ms", "ggtpu_dispatch_ms", "ggtpu_fetch_ms",
                "ggtpu_queue_wait_ms"):
        assert types.get(fam) == "histogram", sorted(types)


def test_gauge_tagging_on_counters():
    counters.set("mh_topology_version", 7)
    counters.inc("some_test_counter_obs")
    assert "mh_topology_version" in counters.gauges()
    assert counters.kind("mh_topology_version") == "gauge"
    assert counters.kind("some_test_counter_obs") == "counter"


def test_server_metrics_and_trace_ops(db, tmp_path):
    from greengage_tpu.runtime.server import SqlClient, SqlServer

    srv = SqlServer(db, str(tmp_path / "obs.sock"))
    srv.start()
    try:
        c = SqlClient(str(tmp_path / "obs.sock"))
        c.sql("select count(*) from obs")
        m = c.op({"op": "metrics"})
        assert m["ok"] and "# TYPE ggtpu_statement_ms histogram" in m["text"]
        _parse_exposition(m["text"])
        t = c.op({"op": "trace"})
        assert t["ok"], t
        evs = t["trace"]["traceEvents"]
        assert any(e.get("name") == "statement" for e in evs)
        ps = c.op({"op": "ps"})
        assert ps["ok"]
        bad = c.op({"op": "trace", "id": 99999999})
        assert not bad["ok"]
        c.close()
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# slow-statement log
# ---------------------------------------------------------------------------

def test_slow_statement_log_fires_at_threshold(db):
    def slow_entries():
        return [e for e in read_entries(db.path)
                if e["kind"] == "slow_statement"]

    db.sql("set log_min_duration_ms = 0")   # every statement qualifies
    try:
        db.sql("select count(*) from obs")
    finally:
        db.sql("set log_min_duration_ms = -1")
    entries = slow_entries()
    assert entries, "slow log did not fire at threshold 0"
    msg = entries[-1]["message"]
    assert "trace=" in msg and "plan=" in msg, msg
    assert float(entries[-1]["duration_ms"]) >= 0
    # the trace JSON export lands beside the CSV logs
    tid = re.search(r"trace=(\d+)", msg).group(1)
    tpath = os.path.join(db.path, "log", f"trace-{tid}.json")
    assert os.path.exists(tpath), tpath
    with open(tpath) as f:
        assert json.load(f)["traceEvents"]
    # and never fires for statements under the threshold
    n0 = len(slow_entries())
    db.sql("set log_min_duration_ms = 100000000")
    try:
        db.sql("select count(*) from obs")
    finally:
        db.sql("set log_min_duration_ms = -1")
    assert len(slow_entries()) == n0
    assert counters.get("slow_statements") >= 1


# ---------------------------------------------------------------------------
# overhead bound: what a warm statement records, counted (ROADMAP D10: a
# timed ratio beside five busy xdist workers said nothing of the program)
# ---------------------------------------------------------------------------

# the warm path of a cached, staged statement: ten spans, no more
WARM_SPANS = ["statement", "parse", "paramize", "admission", "stage",
              "stage:obs", "put", "dispatch", "fetch", "finalize"]
# those of them that sample the device's memory at both ends (one
# device.memory_stats() is ~3.5 us on a TPU v5e's host, my chip run, PR 28):
# docs/OBSERVABILITY.md, "Watermark sampling"
WARM_SAMPLING = ["stage", "stage:obs", "dispatch", "fetch"]


def test_trace_overhead_bounded_on_warm_statement(db):
    q = "select count(*), sum(v) from obs where v > 3"
    db.sql(q)   # compile + cache
    for _ in range(2):
        db.sql(q)
    spans = TRACES.last().export()
    names = [s["name"] for s in spans]
    assert len(names) <= 32, names   # warm path records a bounded span set
    # no span more than the warm path ever recorded, and the sampling ones
    # are the documented four: everything else is two clock reads and a
    # dict append
    assert names == WARM_SPANS
    assert [n for n in names if trace_mod.samples_memory(n)] == WARM_SAMPLING
    assert not any(trace_mod.samples_memory(n)
                   for n in ("parse", "put", "wait", "assemble", "read:obs",
                             "finalize", "compile"))
    # ISSUE 39 added one argument to a span that was there, nothing else
    args = {s["name"]: sorted(s["args"]) for s in spans}
    assert args["dispatch"] == ["est_bytes", "program", "tier"]
    assert args["fetch"] == ["bytes"] and args["put"] == ["bytes"]


# ---------------------------------------------------------------------------
# multihost: worker spans land in the coordinator's trace
# ---------------------------------------------------------------------------

OBS_COORD_SCRIPT = r"""
import json, os, sys
port, cport, path = sys.argv[1], int(sys.argv[2]), sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.environ["GGTPU_REPO"])
from greengage_tpu.parallel.multihost import init_multihost
mh = init_multihost(f"127.0.0.1:{port}", 2, 0, cport, distributed=False)
import greengage_tpu
db = greengage_tpu.connect(path, multihost=mh)
db.sql("create table f (k bigint, v int) distributed by (k)")
db.sql("insert into f values " + ",".join(
    f"({i}, {i % 7})" for i in range(2000)))
db.sql("analyze")
r = db.sql("select count(*), sum(v) from f")
from greengage_tpu.runtime.trace import TRACES, to_chrome
out = {"rows": [int(x) for x in r.rows()[0]],
       "trace": to_chrome(TRACES.last())}
mh.channel.close()
print("RESULT:" + json.dumps(out), flush=True)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def test_multihost_worker_spans_parent_under_dispatch(tmp_path):
    port, cport = _free_port(), _free_port()
    path = str(tmp_path / "cluster")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "GGTPU_REPO": repo, "PYTHONPATH": repo,
    })
    worker = subprocess.Popen(
        [sys.executable, "-m", "greengage_tpu.mgmt.cli", "worker",
         "-d", path, "--coordinator", f"127.0.0.1:{port}",
         "--control-port", str(cport), "--num-processes", "2",
         "--process-id", "1", "--no-distributed"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    coord = subprocess.Popen(
        [sys.executable, "-c", OBS_COORD_SCRIPT, str(port), str(cport), path],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    try:
        cout, _ = coord.communicate(timeout=420)
        wout, _ = worker.communicate(timeout=60)
    except subprocess.TimeoutExpired:
        coord.kill()
        worker.kill()
        cout = coord.stdout.read() if coord.stdout else ""
        wout = worker.stdout.read() if worker.stdout else ""
        raise AssertionError(
            f"multihost timeout\ncoordinator:\n{cout}\nworker:\n{wout}")
    assert coord.returncode == 0, f"coordinator:\n{cout}\nworker:\n{wout}"
    res = [ln for ln in cout.splitlines() if ln.startswith("RESULT:")]
    assert res, f"coordinator:\n{cout}\nworker:\n{wout}"
    out = json.loads(res[0][len("RESULT:"):])
    assert out["rows"] == [2000, sum(i % 7 for i in range(2000))]

    evs = out["trace"]["traceEvents"]
    xs = [e for e in evs if e.get("ph") == "X"]
    tid_names = {e["tid"]: e["args"]["name"] for e in evs
                 if e.get("ph") == "M" and e.get("name") == "thread_name"}
    # the coordinator recorded the multihost dispatch span
    disp = [e for e in xs if e["name"] == "dispatch"
            and e["cat"] == "multihost"]
    assert disp, [e["name"] for e in xs]
    disp_id = disp[0]["args"]["span_id"]
    # worker-side spans were grafted, tagged with the worker's tid...
    wevs = [e for e in xs
            if str(tid_names.get(e["tid"], "")).startswith("worker-")]
    assert wevs, f"no worker spans in {[e['name'] for e in xs]}"
    wnames = {e["name"] for e in wevs}
    assert "dispatch" in wnames or "stage" in wnames, wnames
    # ...and parent (transitively) under the coordinator's dispatch span
    by_id = {e["args"]["span_id"]: e for e in xs}
    for e in wevs:
        p, hops = e, 0
        while p["args"]["parent"] is not None and hops < 50:
            if p["args"]["parent"] == disp_id:
                break
            p = by_id[p["args"]["parent"]]
            hops += 1
        assert p["args"]["parent"] == disp_id or \
            p["args"]["span_id"] == disp_id, e
