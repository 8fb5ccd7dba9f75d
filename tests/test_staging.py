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
from greengage_tpu.runtime.trace import TRACES
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

@pytest.mark.parametrize("where", ["", " where v >= 0"],
                         ids=["plain", "predicate"])
def test_scan_cache_limit_mb_bounds_resident_bytes(db, where):
    # a predicate's views are charged the slots they pin, like a plain scan's
    db.sql("set scan_cache_limit_mb = 1")
    db.executor.stager.stage_cache.clear()
    db.store.blockcache.clear()
    r = db.sql("select sum(v), sum(w), sum(k) from t" + where)
    assert r.stats["stage_units_in_slot"] == r.stats["stage_units"] == 24
    assert db.store.blockcache.total_bytes <= 1 << 20
    db.sql("set scan_cache_limit_mb = 1024")


# ---------------------------------------------------------------------------
# the in-place protocol (Stager._submit): a unit that is offered its slot
# fills it; identities and counts, never a clock
# ---------------------------------------------------------------------------

SEG_ROWS = 150_000   # three blocks of 65,536 rows a (segment, column) file
SLOT_COLS = ("v", "g", "x")
PRUNES = {"prunes_nothing": 10 ** 12, "prunes_some": 1000}


@pytest.fixture(scope="module", params=[1, 4], ids=["1seg", "4seg"])
def sdb(devices8, request):
    """v ascends in load order, so its zone maps tell a segment's blocks
    apart; x is nullable and has a `.valid` file."""
    nseg = request.param
    d = greengage_tpu.connect(numsegments=nseg)
    d.sql("create table sl (k bigint, v bigint, g int, x bigint) "
          "distributed by (k)")
    v = np.arange(SEG_ROWS * nseg, dtype=np.int64)
    d.load_table("sl", {"k": v * 7919, "v": v, "g": (v % 7).astype(np.int32),
                        "x": v * 2}, valids={"x": v % 3 != 0})
    d.sql("analyze")
    yield d
    d.close()


def _slot_query(bound):
    return ("select count(*), sum(v), sum(g), sum(x), count(x) from sl "
            f"where v < {bound}")


def _slot_answer(nseg, bound):
    v = np.arange(SEG_ROWS * nseg, dtype=np.int64)
    m = v < bound
    ok = m & (v % 3 != 0)
    return [(int(m.sum()), int(v[m].sum()), int((v[m] % 7).sum()),
             int((v * 2)[ok].sum()), int(ok.sum()))]


@pytest.mark.parametrize("threads", [1, 0], ids=["inline", "pooled"])
@pytest.mark.parametrize("case", ["prunes_nothing", "prunes_some",
                                  "cache_hit"])
def test_offered_slot_is_filled_by_its_unit(sdb, monkeypatch, case, threads):
    from greengage_tpu.exec.staging import Stager

    nseg = sdb.executor.nseg
    bound = PRUNES.get(case, PRUNES["prunes_some"])
    seen = []   # (column, segment, already a view of the column's buffer)
    fill = Stager._fill_column

    def spy(self, schema, c, cap, per_seg, buffers):
        if not c.startswith("@"):
            seen.extend((c, s, cc[c].base is buffers[c])
                        for s, (cc, _, _) in enumerate(per_seg))
        return fill(self, schema, c, cap, per_seg, buffers)
    monkeypatch.setattr(Stager, "_fill_column", spy)
    sdb.sql(f"set scan_threads = {threads}")
    try:
        sdb.executor.stager.stage_cache.clear()
        sdb.store.blockcache.clear()
        if case == "cache_hit":
            # fill the block cache, then restage from it
            assert sdb.sql(_slot_query(bound)).rows() \
                == _slot_answer(nseg, bound)
            sdb.executor.stager.stage_cache.clear()
            del seen[:]
        base = counters.snapshot()
        r = sdb.sql(_slot_query(bound))
        spans = TRACES.last().export()
    finally:
        sdb.sql("set scan_threads = 0")
    assert r.rows() == _slot_answer(nseg, bound)
    # every column of every segment reached the statement thread as a view
    # of its staging buffer: nothing is left for _fill_column to copy
    assert sorted(seen) == sorted((c, s, True) for c in SLOT_COLS
                                  for s in range(nseg))
    units = nseg * len(SLOT_COLS)
    assert r.stats["stage_units"] == r.stats["stage_units_in_slot"] == units
    assert counters.since(base, "stage_units") == {
        "stage_units": units, "stage_units_in_slot": units}
    table = [s for s in spans if s["name"] == "stage:sl"][0]["args"]
    assert table["read_units"] == table["units_in_slot"] == units
    reads = [s for s in spans if s["name"] == "read:sl"]
    hit = case == "cache_hit"
    assert [s["args"]["in_slot"] for s in reads] \
        == ["copy" if hit else "decode"] * units
    # a hit reads no file: its copy is the unit's work, on the unit's thread
    x_files = 2   # the nullable column's unit reads its `.valid` file too
    for s in reads:
        a, files = s["args"], (x_files if s["args"]["column"] == "x" else 1)
        assert (a["cache_hits"], a["files"]) == ((files, 0) if hit
                                                 else (0, files))
        assert s["tid"].startswith("gg-stage") == (threads == 0)
        assert s["parent"] == [t for t in spans if t["name"] == "stage"][0]["id"]
    kept, total = r.stats["zone_prune"]["sl"]
    assert (kept < total) == (case != "prunes_nothing") and total >= 3 * nseg
    if case == "prunes_some":
        # the kept blocks' rows are the prefix of each slot
        assert table["rows"] == kept * 65_536


@pytest.mark.parametrize("prune", [None, (("v", "<", 10 ** 12),),
                                   (("v", "<", 1000),)],
                         ids=["plain", "prunes_nothing", "prunes_some"])
def test_read_segment_answers_the_same_in_a_slot_and_out_of_it(sdb, prune):
    store, snap = sdb.store, sdb.store.manifest.snapshot()
    cols = list(SLOT_COLS)
    dtypes = {"v": np.int64, "g": np.int32, "x": np.int64}
    cap = 3 * 65_536
    for seg in range(sdb.executor.nseg):
        store.blockcache.clear()
        want_c, want_v, want_n = store.read_segment("sl", seg, cols, snap,
                                                    prune=prune)
        assert all(a.base is None for a in want_c.values())
        for state in ("miss", "hit"):
            if state == "miss":
                store.blockcache.clear()
            dest = {c: np.empty(cap, dtypes[c]) for c in cols}
            got_c, got_v, got_n = store.read_segment("sl", seg, cols, snap,
                                                     prune=prune, dest=dest)
            assert got_n == want_n
            for c in cols:
                assert got_c[c].base is dest[c], (c, state)
                assert np.array_equal(got_c[c], want_c[c])
            assert got_v["v"] is None and np.array_equal(got_v["x"],
                                                         want_v["x"])
        # a slot of another dtype, or too short, is passed over: the rows
        # come back in an array of their own
        for bad in (np.empty(cap, np.int32), np.empty(7, np.int64)):
            got_c, _v, got_n = store.read_segment("sl", seg, ["v"], snap,
                                                  prune=prune,
                                                  dest={"v": bad})
            assert got_c["v"].base is not bad and got_n == want_n
            assert np.array_equal(got_c["v"], want_c["v"])


def _two_files(d):
    d.load_table("c", {"k": np.arange(500, 600), "v": np.arange(500, 600)})
    return "select count(*), sum(v) from c", [(600, sum(range(600)))]


def _deletion_bitmap(d):
    d.sql("delete from c where v < 10")   # rows of both segments
    return "select count(*), sum(v) from c", [(490, sum(range(10, 500)))]


def _partitioned(d):
    d.sql("create table cp (k bigint, v bigint) distributed by (k) "
          "partition by range (v) (partition a start (0) end (250), "
          "default partition rest)")
    d.load_table("cp", {"k": np.arange(500), "v": np.arange(500)})
    return "select count(*), sum(v) from cp", [(500, sum(range(500)))]


def _direct_dispatch(d):
    return "select count(*), sum(v) from c where k = 77", [(1, 77)]


@pytest.fixture()
def cdb(devices8):
    """Two segments, one data file a (segment, column): all in place."""
    d = greengage_tpu.connect(numsegments=2)
    d.sql("create table c (k bigint, v bigint) distributed by (k)")
    d.load_table("c", {"k": np.arange(500), "v": np.arange(500)})
    r = d.sql("select count(*), sum(v) from c")
    assert r.rows() == [(500, sum(range(500)))]
    assert r.stats["stage_units_in_slot"] == r.stats["stage_units"] == 2
    yield d
    d.close()


@pytest.mark.parametrize("make", [_two_files, _deletion_bitmap, _partitioned,
                                  _direct_dispatch])
def test_inputs_that_keep_the_copy_path_answer_the_same(cdb, make):
    q, want = make(cdb)
    r = cdb.sql(q)
    assert r.rows() == want
    assert r.stats["stage_units"] > 0 == r.stats["stage_units_in_slot"]
    reads = [s for s in TRACES.last().export()
             if s["name"].startswith("read:")]
    assert reads and {s["args"]["in_slot"] for s in reads} == {"no"}


def test_a_row_range_keeps_the_copy_path(cdb):
    """A spill pass's slice is cut after the read: no slot is offered."""
    from greengage_tpu.sql.parser import parse

    planned, consts, outs, _ek = cdb._cached_plan(
        parse("select count(*), sum(v) from c")[0])
    snap = cdb.store.manifest.snapshot()
    want_n = want_sum = 0
    for seg in range(2):
        cols, _v, _n = cdb.store.read_segment("c", seg, ["v"], snap)
        want_n += len(cols["v"][10:60])
        want_sum += int(cols["v"][10:60].sum())
    r = cdb.executor.run_single(planned, consts, outs,
                                row_ranges={"c": (10, 60)})
    assert r.rows() == [(want_n, want_sum)] and want_n == 100
    assert r.stats["stage_units"] == 2
    assert r.stats["stage_units_in_slot"] == 0
