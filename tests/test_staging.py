"""Pipelined host data path: parallel staging reads, the byte-accounted
LRU block cache, manifest-version invalidation, and the deterministic
perf-regression guard (docs/PERF.md).

The guard asserts COUNTER VALUES (files read, bytes decoded, cache hits),
never wall clocks, so it is stable on shared CPU runners."""

import os
import threading

import numpy as np
import pytest

import greengage_tpu
from greengage_tpu.runtime.faultinject import faults
from greengage_tpu.runtime.logger import counters
from greengage_tpu.storage.blockcache import CacheRegistry
from greengage_tpu.storage.corruption import CorruptionError


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture()
def db(devices8, tmp_path):
    d = greengage_tpu.connect(str(tmp_path / "cluster"), numsegments=8)
    d.sql("create table t (k int, v bigint, w bigint) distributed by (k)")
    d.sql("insert into t values "
          + ",".join(f"({i},{i * 10},{i * 100})" for i in range(256)))
    return d


def _data_files(db, table, cols):
    """Manifest-referenced data files read by a scan of ``cols``."""
    snap = db.store.manifest.snapshot()
    n = 0
    for files in snap["tables"][table]["segfiles"].values():
        for rel in files:
            fn = os.path.basename(rel)
            if fn.endswith(".ggb") and not fn.endswith(".valid.ggb") \
                    and fn.split(".")[0] in cols:
                n += 1
    return n


# ---------------------------------------------------------------------------
# the blockcache registry itself
# ---------------------------------------------------------------------------

def test_lru_evicts_recency_not_insertion_order():
    reg = CacheRegistry(limit_mb=1)   # 1 MB budget
    c = reg.cache("x")
    a = np.zeros(300_000, np.uint8)   # ~0.3 MB each
    c.put("k0", a.copy())
    c.put("k1", a.copy())
    c.put("k2", a.copy())
    assert c.get("k0") is not None    # touch the OLDEST -> now MRU
    c.put("k3", a.copy())             # over budget: must evict k1, not k0
    assert "k0" in c
    assert "k1" not in c


def test_byte_budget_spans_caches_and_counts_evictions():
    reg = CacheRegistry(limit_mb=1)
    a = reg.cache("a")
    b = reg.cache("b")
    big = np.zeros(600_000, np.uint8)
    before = counters.get("scan_cache_evict")
    a.put("ka", big.copy())
    b.put("kb", big.copy())           # pushes the registry over 1 MB
    assert "ka" not in a              # global LRU: a's entry went first
    assert "kb" in b
    assert reg.total_bytes <= reg.limit_bytes()
    assert counters.get("scan_cache_evict") > before


def test_version_invalidation_spares_untagged_entries():
    reg = CacheRegistry(limit_mb=64)
    c = reg.cache("x")
    c.put("immutable", 1)                  # no version: committed file
    c.put("v1", 2, version=1)
    c.put("v2", 3, version=2)
    assert reg.invalidate_versions(2) == 1
    assert "immutable" in c and "v2" in c and "v1" not in c


# ---------------------------------------------------------------------------
# deterministic perf-regression guard (counter values, never wall clocks)
# ---------------------------------------------------------------------------

def test_cold_scan_reads_each_file_once_and_repeat_reads_nothing(db):
    expect = _data_files(db, "t", {"v"})
    assert expect > 0
    base = counters.snapshot()
    r = db.sql("select sum(v) from t")
    assert r.rows()[0][0] == sum(i * 10 for i in range(256))
    io = counters.since(base, "scan_")
    assert io.get("scan_files_read") == expect
    assert io.get("scan_bytes_decoded", 0) >= expect  # every file decoded

    # repeat statement: served from the staged-input cache, ZERO file I/O
    base = counters.snapshot()
    db.sql("select sum(v) from t")
    io = counters.since(base, "scan_")
    assert io.get("scan_files_read", 0) == 0
    assert io.get("scan_bytes_decoded", 0) == 0

    # drop only the staged inputs: the scan re-assembles entirely from the
    # BLOCK cache — still zero file reads, and real cache hits
    db.executor.stager.stage_cache.clear()
    base = counters.snapshot()
    r = db.sql("select sum(v) from t")
    assert r.rows()[0][0] == sum(i * 10 for i in range(256))
    io = counters.since(base, "scan_")
    assert io.get("scan_files_read", 0) == 0
    assert io.get("scan_cache_hit", 0) > 0


def test_per_statement_scan_io_stats_and_explain(db):
    db.executor.stager.stage_cache.clear()
    db.store.blockcache.clear()
    r = db.sql("select sum(v), sum(w) from t")
    s = r.stats
    assert s["scan_io"]["scan_files_read"] == _data_files(db, "t", {"v", "w"})
    assert s["stage_ms"] >= 0 and s["compute_ms"] >= 0 and s["fetch_ms"] >= 0
    # one read unit a (segment, column): 8 segments x {v, w}, a file each
    # where the segment holds rows
    assert s["stage_read_units"] == 16 >= s["scan_io"]["scan_files_read"]
    db.executor.stager.stage_cache.clear()
    db.store.blockcache.clear()
    plan = db.sql("explain analyze select sum(v) from t").plan_text
    assert "Host data path: staging" in plan and "(8 read units)" in plan
    assert "Scan I/O:" in plan and "files read" in plan


def test_scan_threads_guc_serial_matches_parallel(db):
    want = sorted((i, i * 10) for i in range(256))
    for n in (1, 2, 0):
        db.sql(f"set scan_threads = {n}")
        db.executor.stager.stage_cache.clear()
        db.store.blockcache.clear()
        r = db.sql("select k, v from t")
        assert sorted(r.rows()) == want
        # the split into (segment, column) units does not follow the pool
        assert r.stats["stage_read_units"] == 16
    assert str(db.settings.show("scan_threads")) == "0"


# ---------------------------------------------------------------------------
# invalidation: manifest bump (DML), index build
# ---------------------------------------------------------------------------

def test_dml_bumps_version_and_scan_sees_new_rows(db):
    assert db.sql("select count(*) from t").rows()[0][0] == 256
    db.sql("insert into t values (9999, 5, 7)")
    r = db.sql("select count(*), sum(v) from t")
    assert r.rows()[0][0] == 257
    assert r.rows()[0][1] == sum(i * 10 for i in range(256)) + 5
    db.sql("delete from t where k = 9999")
    assert db.sql("select count(*) from t").rows()[0][0] == 256


def test_index_build_drops_staged_inputs_so_scans_prune(db, tmp_path):
    d = greengage_tpu.connect(str(tmp_path / "idx"), numsegments=8)
    d.sql("create table u (k int, v bigint) distributed by (k)")
    for lo in range(0, 4096, 1024):   # several blocks per segment file
        d.sql("insert into u values "
              + ",".join(f"({i},{i})" for i in range(lo, lo + 1024)))
    assert d.sql("select sum(v) from u where k = 77").rows()[0][0] == 77
    d.sql("create index u_k on u (k)")
    assert len(d.executor.stager.stage_cache) == 0    # staged inputs dropped
    assert d.sql("select sum(v) from u where k = 77").rows()[0][0] == 77


# ---------------------------------------------------------------------------
# concurrency: parallel readers vs corruption (repair exactly once)
# ---------------------------------------------------------------------------

def _first_data_rel(db, table="t"):
    snap = db.store.manifest.snapshot()
    for seg, rels in sorted(snap["tables"][table]["segfiles"].items(),
                            key=lambda kv: int(kv[0])):
        for rel in rels:
            if rel.endswith(".ggb"):
                return rel
    raise AssertionError("no files")


def _flip_byte(path, offset=40):
    with open(path, "r+b") as f:
        f.seek(offset)
        b = f.read(1)
        f.seek(offset)
        f.write(bytes([b[0] ^ 0xFF]))


@pytest.fixture()
def mdb(devices8, tmp_path):
    d = greengage_tpu.connect(str(tmp_path / "mirrored"), numsegments=8,
                              mirrors=True)
    d.sql("create table t (k int, v bigint) distributed by (k)")
    d.sql("insert into t values "
          + ",".join(f"({i},{i * 10})" for i in range(128)))
    return d


def test_parallel_readers_repair_a_corrupt_file_exactly_once(mdb):
    rel = _first_data_rel(mdb)
    path = os.path.join(mdb.path, "data", "t", rel)
    _flip_byte(path)
    mdb.store.blockcache.clear()
    before = counters.get("storage_repair")
    results, errors = [], []

    def read():
        try:
            results.append(mdb.store.read_file("t", rel))
        except Exception as e:   # pragma: no cover - failure detail
            errors.append(e)

    threads = [threading.Thread(target=read) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert len(results) == 6
    for a in results[1:]:
        assert np.array_equal(a, results[0])
    # exactly ONE repair despite six racing readers
    assert counters.get("storage_repair") == before + 1
    assert not os.path.isdir(os.path.join(mdb.path, ".quarantine"))


def test_parallel_readers_quarantine_exactly_once_without_mirror(
        devices8, tmp_path):
    d = greengage_tpu.connect(str(tmp_path / "bare"), numsegments=8)
    d.sql("create table t (k int, v bigint) distributed by (k)")
    d.sql("insert into t values "
          + ",".join(f"({i},{i * 10})" for i in range(128)))
    rel = _first_data_rel(d)
    _flip_byte(os.path.join(d.path, "data", "t", rel))
    d.store.blockcache.clear()
    before = counters.get("storage_quarantine")
    errors = []

    def read():
        try:
            d.store.read_file("t", rel)
        except (CorruptionError, IOError) as e:
            errors.append(e)

    threads = [threading.Thread(target=read) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(errors) == 6                     # nobody got bad data
    assert counters.get("storage_quarantine") == before + 1


def test_fault_injected_corruption_under_parallel_staging(mdb):
    """storage_corrupt_block fires once mid-statement while the staging
    pool reads concurrently: the hit thread repairs, every other thread
    proceeds, the statement returns exact rows."""
    mdb.sql("set scan_threads = 4")
    mdb.executor.stager.stage_cache.clear()
    mdb.store.blockcache.clear()
    before = counters.get("storage_repair")
    faults.inject("storage_corrupt_block", "skip", occurrences=1)
    rows = sorted(mdb.sql("select k, v from t").rows())
    assert rows == sorted((i, i * 10) for i in range(128))
    assert counters.get("storage_repair") == before + 1


# ---------------------------------------------------------------------------
# cache-budget behavior under the GUC
# ---------------------------------------------------------------------------

def test_scan_cache_limit_mb_bounds_resident_bytes(db):
    db.sql("set scan_cache_limit_mb = 1")
    db.executor.stager.stage_cache.clear()
    db.store.blockcache.clear()
    db.sql("select sum(v), sum(w), sum(k) from t")
    assert db.store.blockcache.total_bytes <= 1 << 20
    db.sql("set scan_cache_limit_mb = 1024")
