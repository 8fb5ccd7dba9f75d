"""Measured memory accounting (docs/OBSERVABILITY.md "Memory
accounting"): XLA memory_analysis attached to cached executables (zero
re-analysis on warm hits), the per-statement owner tree, OOM
classification + one-shot spill demotion + the mem-<id>.json forensics
dump, graceful CPU fallback for device watermarks, and the metrics /
server surfaces — the memaccounting.c-analog PR's acceptance tests."""

import glob
import json
import os

import numpy as np
import pytest

import greengage_tpu
from greengage_tpu.exec.executor import OutOfDeviceMemory
from greengage_tpu.runtime import memaccount
from greengage_tpu.runtime.faultinject import faults
from greengage_tpu.runtime.logger import counters, prometheus_text
from greengage_tpu.runtime.runaway import TRACKER
from greengage_tpu.runtime.trace import TRACES

N = 20_000
Q = "select g, count(*), sum(v) from mt group by g order by g"


@pytest.fixture(scope="module")
def db(devices8):
    d = greengage_tpu.connect(numsegments=4)
    d.sql("create table mt (k int, g int, v int) distributed by (k)")
    d.load_table("mt", {"k": np.arange(N), "g": np.arange(N) % 7,
                        "v": np.arange(N) % 11})
    d.sql("analyze")
    return d


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    faults.reset()


# ---------------------------------------------------------------------------
# executable measurement: memory_analysis attached once, reused warm
# ---------------------------------------------------------------------------

def test_measured_bytes_attached_and_zero_reanalysis_on_warm_hit(db):
    db.sql(Q)   # compile + first dispatch: analysis attaches here
    r = db.sql(Q)
    mem = (r.stats or {}).get("mem")
    assert mem, r.stats
    meas = mem["measured"]
    assert meas is not None, mem
    # argument/output bytes are real allocations of the all-segment
    # program — never zero for a staged scan
    assert meas["argument_bytes"] > 0 and meas["output_bytes"] > 0, meas
    assert mem["est_bytes"] > 0
    # a warm program-cache hit performs ZERO re-analysis (and zero
    # re-compilation): the analysis rides the cached CompileResult
    c0 = counters.get("mem_analysis_runs")
    j0 = counters.get("program_cache_hit")
    r2 = db.sql(Q)
    assert counters.get("mem_analysis_runs") - c0 == 0
    assert counters.get("program_cache_hit") > j0
    assert (r2.stats["mem"]["measured"] or {}) == (meas or {})


def test_owner_tree_charges_staging_blockcache_device(db):
    # force a cold stage (fresh reads + fresh cache inserts)
    db.executor.stager.stage_cache.clear()
    db.store.blockcache.clear()
    r = db.sql(Q)
    owners = r.stats["mem"]["owners"]
    assert owners.get("staging", 0) > 0, owners
    assert owners.get("blockcache", 0) > 0, owners
    assert owners.get("device", 0) > 0, owners
    # accounts retire into the ring with the full tree
    ring = memaccount.ACCOUNTS.ring()
    assert ring, "completed account did not land in the ring"
    snap = ring[-1]
    assert snap["owners"]["staging"]["items"], snap
    assert snap["total_bytes"] > 0


def test_estimate_error_gauge_and_mem_histogram(db):
    db.sql(Q)
    assert counters.kind("mem_est_error_pct") == "gauge"
    text = prometheus_text()
    assert "# TYPE ggtpu_executable_mem_mb histogram" in text
    assert 'ggtpu_executable_mem_mb_bucket{le="1"}' in text


# ---------------------------------------------------------------------------
# OOM forensics: classification, spill demotion, typed error + dump
# ---------------------------------------------------------------------------

def test_oom_demotes_to_spill_once(db):
    e0 = counters.get("oom_events")
    s0 = counters.get("oom_spill_retries")
    faults.inject("device_oom", "skip", occurrences=1)
    r = db.sql(Q)   # first dispatch fakes RESOURCE_EXHAUSTED
    # ... and the statement completes on the spill path anyway
    assert r.stats.get("oom_demoted") is True, r.stats
    assert r.stats.get("spill_passes", 0) >= 1
    assert counters.get("oom_events") == e0 + 1
    assert counters.get("oom_spill_retries") == s0 + 1
    # correct answer survives the demotion
    rows = {int(g): (int(c), int(s)) for g, c, s in r.rows()}
    g = np.arange(N) % 7
    v = np.arange(N) % 11
    for k in range(7):
        m = g == k
        assert rows[k] == (int(m.sum()), int(v[m].sum()))


def test_oom_typed_error_carries_accounting_and_dumps_json(db):
    db.sql("set oom_spill_retry = off")
    db.executor.stager.stage_cache.clear()   # guarantee a staging owner charge
    faults.inject("device_oom", "skip", occurrences=1)
    try:
        with pytest.raises(OutOfDeviceMemory) as ei:
            db.sql(Q)
    finally:
        db.sql("set oom_spill_retry = on")
    e = ei.value
    assert "out of device memory" in str(e).lower()
    owners = e.snapshot.get("owners") or {}
    assert "device" in owners and "staging" in owners, e.snapshot
    # the dump lands beside the slow-log traces with the full tree
    dumps = sorted(glob.glob(os.path.join(db.path, "log", "mem-*.json")),
                   key=os.path.getmtime)
    assert dumps, "mem-<id>.json forensics dump missing"
    with open(dumps[-1]) as f:
        payload = json.load(f)
    assert payload["error"]
    assert payload["accounting"]["owners"]["device"]["bytes"] > 0
    assert payload["accounting"]["owners"]["staging"]["bytes"] > 0
    assert payload["statement_id"] == e.snapshot.get("statement_id")


def test_oom_classifier_shapes():
    assert memaccount.is_oom_error(
        RuntimeError("RESOURCE_EXHAUSTED: Out of memory while trying to "
                     "allocate 123 bytes"))
    assert memaccount.is_oom_error(RuntimeError("Out of memory"))
    assert not memaccount.is_oom_error(RuntimeError("bloom filter failed"))
    assert not memaccount.is_oom_error(ValueError("shape mismatch"))


# ---------------------------------------------------------------------------
# CPU fallback: memory_stats() is None, everything stays graceful
# ---------------------------------------------------------------------------

def test_cpu_memory_stats_none_is_graceful(db):
    # tier-1 runs JAX_PLATFORMS=cpu: the CPU backend has no allocator
    # stats; the sampler must return None (and self-disable), statements
    # must run untouched, and spans must stay free of hbm args
    stats = memaccount.device_memory_stats()
    if stats is not None:
        pytest.skip("backend reports allocator stats (not the CPU path)")
    assert memaccount.sample_watermark() is None
    assert memaccount.sample_watermark() is None   # repeat: stays None
    db.sql(Q)
    tr = TRACES.last()
    assert all("hbm_bytes" not in s["args"] for s in tr.export())


# ---------------------------------------------------------------------------
# process gauges, runaway ledger, report + server surfaces
# ---------------------------------------------------------------------------

def test_process_gauges_rss_fds_pool_depth(db):
    out = memaccount.update_process_gauges()
    assert out.get("host_rss_bytes", 0) > 0
    assert out.get("host_open_fds", 0) > 0
    assert out.get("staging_pool_queue_depth", -1) >= 0
    text = prometheus_text()
    assert "# TYPE ggtpu_host_rss_bytes gauge" in text
    assert "# TYPE ggtpu_staging_pool_queue_depth gauge" in text


def test_owner_gauges_exported_during_statement(db):
    db.executor.stager.stage_cache.clear()
    db.sql(Q)
    # live totals drain when statements retire; the gauge names must
    # still be present (written at least once during the run above via
    # update_process_gauges) and non-negative
    memaccount.update_process_gauges()
    snap = counters.snapshot()
    for name in ("mem_owner_bytes_staging", "mem_owner_bytes_device"):
        assert snap.get(name, 0) >= 0


def test_runaway_ledger_measured_flag():
    TRACKER.enter()
    try:
        TRACKER.reprice(1 << 20, 0, 0.9, measured=True)
        snap = [e for e in TRACKER.snapshot() if e["bytes"] == 1 << 20]
        assert snap and snap[0]["measured"] is True
        assert "statement_id" in snap[0]
    finally:
        TRACKER.release()


def test_mem_report_and_server_op(db, tmp_path):
    from greengage_tpu.runtime.server import SqlClient, SqlServer

    rep = memaccount.report(db)
    assert "process" in rep and "vmem_tracker" in rep
    assert any(x["measured"] for x in rep["executables"]), \
        rep["executables"]
    srv = SqlServer(db, str(tmp_path / "mem.sock"))
    srv.start()
    try:
        c = SqlClient(str(tmp_path / "mem.sock"))
        c.sql("select count(*) from mt")
        m = c.op({"op": "mem"})
        assert m["ok"], m
        assert "block_cache" in m["mem"]
        assert m["mem"]["device"] is None or "bytes_in_use" in m["mem"]["device"]
        # the metrics op refreshes host gauges at scrape time
        t = c.op({"op": "metrics"})
        assert "ggtpu_host_rss_bytes" in t["text"]
        c.close()
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# EXPLAIN ANALYZE surfaces (acceptance criterion)
# ---------------------------------------------------------------------------

def test_explain_analyze_prints_measured_memory_on_warm_statement(db):
    db.sql(Q)   # warm the statement's plan
    txt = db.sql("explain analyze " + Q).plan_text
    assert "Memory: vmem estimate" in txt, txt
    assert "executable measured: args" in txt, txt
    assert "+ temps" in txt and "+ out" in txt, txt
    # per-node Memory annotation rides the instrumented tree
    assert "memory ~" in txt, txt
