"""Benchmark: TPC-H Q1 / Q3 / Q5 through the full engine on the chip.

`python bench.py` runs the measurement in THIS process (one process
holds the chip): generate-or-reuse the cluster, time each query, run the
riders, print a per-query detail block on stderr and ONE JSON headline as
the last line of stdout:

    {"metric": "tpch_q1_rows_per_sec_per_chip", "value": N, "unit": "rows/s",
     "vs_baseline": N, "platform": "tpu", "device_kind": "TPU v5 lite", ...}

The headline names the device it ran on, and a device that is not in
HBM_PEAK_GBS is refused. A failed query or rider makes the exit code
non-zero after the detail block is printed; there is no fallback scale
and no zero-valued headline.

Modes: `--microbench NAME` (host-path microbenches) and `--prewarm`
(populate dataset / cluster / baseline caches) run on the CPU by design
and pin it before jax is imported.

Env: GGTPU_BENCH_SF (default 10), GGTPU_BENCH_RUNS (default 3),
     GGTPU_BENCH_DIR (default /tmp/ggtpu_bench_sf<SF>_1seg; reused when
     already loaded at the right scale), GGTPU_BENCH_QUERIES (default
     q1,q3,q5).
"""

import json
import os
import sys
import time

T0 = time.monotonic()


def log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

SF = float(os.environ.get("GGTPU_BENCH_SF", "10"))
RUNS = int(os.environ.get("GGTPU_BENCH_RUNS", "3"))  # best-of; per-call
QUERIES = os.environ.get("GGTPU_BENCH_QUERIES", "q1,q3,q5").split(",")
# HBM bandwidth peak by jax device_kind, with its source. A device that
# is not listed is refused (the roofline share would be against the
# wrong chip); the CPU entry exists for the harness tests and reports no
# roofline share at all.
HBM_PEAK_GBS = {
    # Google Cloud documentation, "TPU v5e": 16 GB HBM2e, 819 GB/s
    "TPU v5 lite": 819.0,
    "cpu": None,
}
BASELINE_V = 1         # bump when any baseline_qN implementation changes

Q1 = """
select l_returnflag, l_linestatus,
       sum(l_quantity) as sum_qty,
       sum(l_extendedprice) as sum_base_price,
       sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
       sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
       avg(l_quantity) as avg_qty,
       avg(l_extendedprice) as avg_price,
       avg(l_discount) as avg_disc,
       count(*) as count_order
from lineitem
where l_shipdate <= date '1998-12-01' - interval '90' day
group by l_returnflag, l_linestatus
order by l_returnflag, l_linestatus
"""

Q3 = """
select l_orderkey,
       sum(l_extendedprice * (1 - l_discount)) as revenue,
       o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = 'BUILDING'
  and c_custkey = o_custkey and l_orderkey = o_orderkey
  and o_orderdate < date '1995-03-15' and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate limit 10
"""

Q5 = """
select n_name, sum(l_extendedprice * (1 - l_discount)) as revenue
from customer, orders, lineitem, supplier, nation, region
where c_custkey = o_custkey and l_orderkey = o_orderkey
  and l_suppkey = s_suppkey and c_nationkey = s_nationkey
  and s_nationkey = n_nationkey and n_regionkey = r_regionkey
  and r_name = 'ASIA'
  and o_orderdate >= date '1994-01-01'
  and o_orderdate < date '1994-01-01' + interval '1' year
group by n_name
order by revenue desc
"""


# ======================================================================
# microbench: CPU-runnable host-data-path metrics
# ======================================================================

def _pin_cpu_mesh() -> None:
    """The microbench and prewarm modes measure host code and run on an
    8-device virtual CPU mesh by design: pin it before jax is imported."""
    if "jax" in sys.modules:
        raise RuntimeError("_pin_cpu_mesh must run before jax is imported")
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            (flags + " --xla_force_host_platform_device_count=8").strip()


def microbench_staging() -> None:
    """Cold/warm staging-throughput microbench (docs/PERF.md): stages a
    multi-segment, multi-column table through the real executor path and
    reports decoded bytes / staging wall seconds. CPU-only by design — it
    measures the HOST data path (read + CRC/zlib decode + buffer fill +
    transfer). Prints the standard one-line JSON:

        {"metric": "staging_cold_mb_per_sec", "value": N, "unit": "MB/s",
         "vs_baseline": <vs single-threaded staging>, ...}

    Env: GGTPU_MB_ROWS (default 1000000), GGTPU_MB_COLS (6),
         GGTPU_MB_SEGS (4), GGTPU_MB_RUNS (3)."""
    _pin_cpu_mesh()
    import shutil
    import tempfile

    import numpy as np

    import greengage_tpu
    from greengage_tpu.runtime.logger import counters

    rows = int(os.environ.get("GGTPU_MB_ROWS", "1000000"))
    ncols = int(os.environ.get("GGTPU_MB_COLS", "6"))
    nseg = int(os.environ.get("GGTPU_MB_SEGS", "4"))
    runs = int(os.environ.get("GGTPU_MB_RUNS", "3"))
    path = tempfile.mkdtemp(prefix="ggtpu_staging_mb_")
    try:
        db = greengage_tpu.connect(path, numsegments=nseg)
        cols_ddl = ", ".join(f"c{i} bigint" for i in range(ncols))
        db.sql(f"create table t (k int, {cols_ddl}) distributed by (k)")
        rng = np.random.default_rng(7)
        data = {"k": np.arange(rows, dtype=np.int32)}
        for i in range(ncols):
            data[f"c{i}"] = rng.integers(0, 1 << 40, rows, dtype=np.int64)
        t0 = time.monotonic()
        db.load_table("t", data)
        log(f"microbench: loaded {rows} rows x {ncols + 1} cols across "
            f"{nseg} segments in {time.monotonic() - t0:.1f}s")
        q = ("select " + ", ".join(f"sum(c{i})" for i in range(ncols))
             + ", sum(k) from t")
        db.sql(q)   # compile once; measurement runs reuse the program

        def staged_run(clear_blocks: bool) -> tuple[float, dict]:
            db.executor._stage_cache.clear()
            if clear_blocks:
                db.store.blockcache.clear()
            c0 = counters.snapshot()
            r = db.sql(q)
            return r.stats["stage_ms"] / 1e3, counters.since(c0, "scan_")

        # cold: every block read + decoded from disk
        cold_s, cold_io = 1e9, {}
        for _ in range(runs):
            s, io = staged_run(clear_blocks=True)
            if s < cold_s:
                cold_s, cold_io = s, io
        cold_bytes = cold_io.get("scan_bytes_decoded", 0)
        cold_mbs = cold_bytes / max(cold_s, 1e-9) / 1e6
        # warm: stage cache cleared but blocks resident — the block-cache
        # service rate (buffer fill + device put, no disk/decode)
        warm_s, warm_io = 1e9, {}
        for _ in range(runs):
            s, io = staged_run(clear_blocks=False)
            if s < warm_s:
                warm_s, warm_io = s, io
        warm_mbs = cold_bytes / max(warm_s, 1e-9) / 1e6
        # baseline: the same cold staging forced single-threaded — the
        # pre-pipeline serial loop shape
        db.sql("set scan_threads = 1")
        serial_s = 1e9
        for _ in range(runs):
            s, _io = staged_run(clear_blocks=True)
            serial_s = min(serial_s, s)
        db.sql("set scan_threads = 0")
        line = {
            "metric": "staging_cold_mb_per_sec",
            "value": round(cold_mbs, 1),
            "unit": "MB/s",
            "vs_baseline": round(max(serial_s, 1e-9) / max(cold_s, 1e-9), 3),
            "warm_mb_per_sec": round(warm_mbs, 1),
            "cold_stage_ms": round(cold_s * 1e3, 1),
            "warm_stage_ms": round(warm_s * 1e3, 1),
            "serial_stage_ms": round(serial_s * 1e3, 1),
            "bytes_decoded": int(cold_bytes),
            "files_read": cold_io.get("scan_files_read", 0),
            "warm_files_read": warm_io.get("scan_files_read", 0),
            "rows": rows, "segments": nseg,
        }
        print(json.dumps(line), flush=True)
    finally:
        shutil.rmtree(path, ignore_errors=True)


def microbench_plan_cache() -> None:
    """Repeated-shape statement throughput (ISSUE 5, docs/PERF.md "Plan
    cache"): dashboard-style SELECTs that differ only in literal values.
    Cold = every statement re-plans and recompiles (plan_cache_params off,
    caches cleared per statement — the seed behavior); warm = the
    parameterized plan + executable cache serves every value from ONE
    compiled program. CPU-only by design (XLA compile cost dominates on
    every backend). Prints the standard one-line JSON:

        {"metric": "plan_cache_stmts_per_sec", "value": N, "unit":
         "stmts/s", "vs_baseline": <speedup vs cold-compile-every-time>,
         "recompiles_avoided": ..., ...}

    Env: GGTPU_MB_ROWS (default 200000), GGTPU_MB_SEGS (4),
         GGTPU_MB_WARM (30 statements), GGTPU_MB_COLD (3 statements)."""
    _pin_cpu_mesh()
    import shutil
    import tempfile

    import numpy as np

    import greengage_tpu
    from greengage_tpu.runtime.logger import counters

    rows = int(os.environ.get("GGTPU_MB_ROWS", "200000"))
    nseg = int(os.environ.get("GGTPU_MB_SEGS", "4"))
    nwarm = int(os.environ.get("GGTPU_MB_WARM", "30"))
    ncold = int(os.environ.get("GGTPU_MB_COLD", "3"))
    path = tempfile.mkdtemp(prefix="ggtpu_plancache_mb_")
    try:
        # the persistent XLA disk cache would hide recompile cost: turn it
        # off for this process so cold statements pay the real compile
        import jax as _j

        _j.config.update("jax_enable_compilation_cache", False)
        db = greengage_tpu.connect(path, numsegments=nseg)
        db.sql("create table d (k int, grp int, v double precision) "
               "distributed by (k)")
        rng = np.random.default_rng(11)
        db.load_table("d", {
            "k": np.arange(rows, dtype=np.int32),
            "grp": rng.integers(0, 50, rows, dtype=np.int32),
            "v": rng.random(rows)})

        def q(i: int) -> str:
            return (f"select count(*), sum(v), min(grp) from d "
                    f"where grp >= {i % 40} and v < 0.{51 + i % 37}")

        def clear_all() -> None:
            db._select_cache.clear()
            db.executor._plan_cache.clear()
            _j.clear_caches()   # in-memory jit cache, not just ours

        # cold: the seed behavior — every literal change replans+recompiles
        db.sql("set plan_cache_params = off")
        cold_s = 0.0
        for i in range(ncold):
            clear_all()
            t0 = time.monotonic()
            db.sql(q(i))
            cold_s += time.monotonic() - t0
        cold_per = cold_s / max(ncold, 1)

        # warm: parameterized cache — one compile serves every value
        db.sql("set plan_cache_params = on")
        clear_all()
        db.sql(q(0))   # populate
        c0 = counters.snapshot()
        t0 = time.monotonic()
        for i in range(1, nwarm + 1):
            db.sql(q(i))
        warm_s = time.monotonic() - t0
        delta = counters.since(c0)
        warm_per = warm_s / max(nwarm, 1)
        line = {
            "metric": "plan_cache_stmts_per_sec",
            "value": round(1.0 / max(warm_per, 1e-9), 1),
            "unit": "stmts/s",
            "vs_baseline": round(cold_per / max(warm_per, 1e-9), 2),
            "cold_stmt_ms": round(cold_per * 1e3, 1),
            "warm_stmt_ms": round(warm_per * 1e3, 1),
            "recompiles_avoided": nwarm - delta.get("program_cache_miss", 0),
            "plan_cache_hits": delta.get("plan_cache_hit", 0),
            "program_cache_hits": delta.get("program_cache_hit", 0),
            "params_hoisted": delta.get("params_hoisted", 0),
            "rows": rows, "segments": nseg,
        }
        print(json.dumps(line), flush=True)
    finally:
        shutil.rmtree(path, ignore_errors=True)


def _batch_serving_measure(db, make_q, concs=(1, 4, 16),
                           per_thread=16) -> dict:
    """Statements/sec with batched serving on vs off at each concurrency
    (shared by the microbench and the TPU bench's detail rider). Warms
    every pow2 width bucket first so the measurement is steady-state
    serving, not bucket compiles."""
    import threading

    from greengage_tpu.runtime.logger import counters
    from greengage_tpu.sql.parser import parse

    maxw = int(db.settings.batch_max_width)
    db.sql("set batch_serving_enabled = off")
    db.sql(make_q(0))   # warm plan cache + width-0 classic program
    stmt = parse(make_q(0))[0]
    planned, consts, outs, ek = db._cached_plan(stmt)
    pv = consts["@params@"]
    w = 1
    while w <= maxw:
        # the member values are irrelevant for warming — the bucket's
        # program is value-generic; repeating one vector is type-exact
        db.executor.run_batch(planned, consts, outs, ek, [pv] * w)
        w *= 2

    def run_conc(conc: int) -> float:
        def worker(tid):
            for j in range(per_thread):
                db.sql(make_q(tid * per_thread + j))
        ts = [threading.Thread(target=worker, args=(i,))
              for i in range(conc)]
        t0 = time.monotonic()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        return conc * per_thread / (time.monotonic() - t0)

    out = {}
    for conc in concs:
        db.sql("set batch_serving_enabled = off")
        off = run_conc(conc)
        db.sql("set batch_serving_enabled = on")
        c0 = counters.snapshot()
        on = run_conc(conc)
        d = counters.since(c0)
        ndisp = max(d.get("batch_dispatch_total", 0), 1)
        out[f"conc{conc}"] = {
            "off_stmts_per_sec": round(off, 1),
            "on_stmts_per_sec": round(on, 1),
            "speedup": round(on / max(off, 1e-9), 2),
            "avg_width": round(d.get("batch_members_total", 0) / ndisp, 1),
            "dispatches": d.get("batch_dispatch_total", 0),
            "fallbacks": d.get("batch_fallback_total", 0),
        }
    db.sql("set batch_serving_enabled = off")
    return out


def microbench_batch_serving() -> None:
    """Vectorized-serving throughput (ISSUE 11, docs/PERF.md "Vectorized
    serving"): point-query statements/sec at concurrency {1, 4, 16} with
    batched serving on vs off. CPU-runnable by design — the win there is
    amortized per-statement host overhead (the CPU backend executes vmap
    members serially); on TPU the stacked members additionally share the
    device. Prints the standard one-line JSON:

        {"metric": "batch_serving_stmts_per_sec", "value": <conc-16 on>,
         "unit": "stmts/s", "vs_baseline": <on/off at conc 16>, ...}

    Env: GGTPU_MB_ROWS (default 8000), GGTPU_MB_SEGS (4),
         GGTPU_MB_PER_THREAD (16 statements per thread)."""
    _pin_cpu_mesh()
    import shutil
    import tempfile

    import numpy as np

    import greengage_tpu

    rows = int(os.environ.get("GGTPU_MB_ROWS", "8000"))
    nseg = int(os.environ.get("GGTPU_MB_SEGS", "4"))
    per_thread = int(os.environ.get("GGTPU_MB_PER_THREAD", "16"))
    path = tempfile.mkdtemp(prefix="ggtpu_batchserve_mb_")
    try:
        db = greengage_tpu.connect(path, numsegments=nseg)
        db.sql("create table d (k int, a int, v double precision) "
               "distributed by (k)")
        rng = np.random.default_rng(7)
        db.load_table("d", {
            "k": np.arange(rows, dtype=np.int32),
            "a": np.arange(rows, dtype=np.int32),
            "v": rng.random(rows)})

        def q(i: int) -> str:
            return (f"select count(*), sum(v) from d "
                    f"where a > {100 + i % 400}")

        res = _batch_serving_measure(db, q, per_thread=per_thread)
        c16 = res.get("conc16", {})
        line = {
            "metric": "batch_serving_stmts_per_sec",
            "value": c16.get("on_stmts_per_sec", 0),
            "unit": "stmts/s",
            "vs_baseline": c16.get("speedup", 0),
            "rows": rows, "segments": nseg,
            **res,
        }
        print(json.dumps(line), flush=True)
    finally:
        shutil.rmtree(path, ignore_errors=True)


def microbench_scalar_fusion() -> None:
    """Fused device scalar path vs the host-chain fallback (ISSUE 13,
    docs/PERF.md "Scalar data-path fusion") on a dict-encoded AND a raw
    TEXT column: `upper(col) = literal` counted over the table. The raw
    column compares three ways — device byte-window ops
    (scalar_device_enabled=on), the legacy per-row host chain (off), and
    the dictionary column's LUT path. Each measurement clears the staging
    + host-predicate + raw-window caches first, so both paths pay their
    honest per-manifest-version cost (the cost a fresh DML version
    re-incurs — cached repeats are ~free on both paths and measure
    nothing). Prints the standard one-line JSON:

        {"metric": "scalar_fusion_speedup", "value": <host/device on raw>,
         "unit": "x", "vs_baseline": <same>, ...}

    Env: GGTPU_MB_ROWS (default 300000), GGTPU_MB_SEGS (4),
         GGTPU_MB_RUNS (3)."""
    _pin_cpu_mesh()
    import shutil
    import tempfile

    import numpy as np

    import greengage_tpu
    from greengage_tpu.runtime.logger import counters

    rows = int(os.environ.get("GGTPU_MB_ROWS", "300000"))
    nseg = int(os.environ.get("GGTPU_MB_SEGS", "4"))
    runs = int(os.environ.get("GGTPU_MB_RUNS", "3"))
    path = tempfile.mkdtemp(prefix="ggtpu_scalar_mb_")
    try:
        db = greengage_tpu.connect(path, numsegments=nseg)
        db.sql("create table t (k int, cdict text, craw text) "
               "distributed by (k)")
        object.__setattr__(db.catalog.get("t").column("craw"),
                           "encoding", "raw")
        rng = np.random.default_rng(13)
        vocab = [f"  val{i:05d}  " for i in range(2000)]
        codes = rng.integers(0, len(vocab), rows)
        strs = np.array(vocab, dtype=object)[codes]
        db.load_table("t", {"k": np.arange(rows, dtype=np.int32),
                            "cdict": strs, "craw": strs.copy()})

        def timed_stmt(q: str) -> float:
            db.sql(q)   # compile/LUT warm; measurement pays the data path
            best = 1e9
            for _ in range(runs):
                db.executor._stage_cache.clear()
                db.store._hp_cache.clear()
                db.store._rawprefix_cache.clear()
                db.store._raw_cache.clear()
                t0 = time.monotonic()
                db.sql(q)
                best = min(best, time.monotonic() - t0)
            return best

        q_chain = ("select count(*) from t "
                   "where length(trim({c})) > 8 and upper(trim({c})) "
                   "like 'VAL0004%'")
        q_eq = "select count(*) from t where upper(trim({c})) = 'VAL00042'"
        c0 = counters.snapshot()
        dict_s = timed_stmt(q_chain.format(c="cdict"))
        raw_dev_chain = timed_stmt(q_chain.format(c="craw"))
        raw_dev_eq = timed_stmt(q_eq.format(c="craw"))
        db.sql("set scalar_device_enabled = off")
        raw_host_chain = timed_stmt(q_chain.format(c="craw") + " -- host")
        raw_host_eq = timed_stmt(q_eq.format(c="craw") + " -- host")
        db.sql("set scalar_device_enabled = on")
        d = counters.since(c0)
        speedup = raw_host_chain / max(raw_dev_chain, 1e-9)
        line = {
            "metric": "scalar_fusion_speedup",
            "value": round(speedup, 2),
            "unit": "x",
            "vs_baseline": round(speedup, 2),
            "raw_device_ms": round(raw_dev_chain * 1e3, 1),
            "raw_host_ms": round(raw_host_chain * 1e3, 1),
            "raw_eq_device_ms": round(raw_dev_eq * 1e3, 1),
            "raw_eq_host_ms": round(raw_host_eq * 1e3, 1),
            "dict_lut_ms": round(dict_s * 1e3, 1),
            "scalar_device_total": d.get("scalar_device_total", 0),
            "scalar_host_fallback_total":
                d.get("scalar_host_fallback_total", 0),
            "rows": rows, "segments": nseg,
        }
        print(json.dumps(line), flush=True)
    finally:
        shutil.rmtree(path, ignore_errors=True)


def _motion_pipeline_measure(db, q, runs=3) -> dict:
    """Wall time of a bucketed spill merge with the bucket pipeline on
    vs off (identical programs — motion_pipeline only changes whether
    stage k+1 overlaps compute k), plus the realized overlap counter
    (shared by the microbench and the TPU bench's detail rider). The
    caller has already set the vmem budget that forces the spill."""
    from greengage_tpu.runtime.logger import counters

    def best_of(n):
        best, r = 1e9, None
        for _ in range(n):
            t0 = time.monotonic()
            r = db.sql(q)
            best = min(best, time.monotonic() - t0)
        return best, r

    db.sql("set motion_pipeline = on")
    db.sql(q)   # warm: the pass/merge programs compile once
    c0 = counters.snapshot()
    on_s, r = best_of(runs)
    overlap = counters.since(c0).get("motion_overlap_ms", 0)
    db.sql("set motion_pipeline = off")
    off_s, _ = best_of(runs)
    db.sql("set motion_pipeline = on")
    return {
        "on_ms": round(on_s * 1e3, 1),
        "off_ms": round(off_s * 1e3, 1),
        "speedup": round(off_s / max(on_s, 1e-9), 2),
        "overlap_ms_per_run": round(overlap / max(runs, 1), 1),
        "merge_buckets": (r.stats or {}).get("spill_merge_buckets"),
    }


def microbench_motion_pipeline() -> None:
    """Pipelined bucket schedules + the tiered workfile (ISSUE 18,
    docs/PERF.md "Data movement"): a bucketed DISTINCT spill merge with
    the bucket pipeline on vs off — the off path is the strict
    stage/compute alternation, so the headline is the overlap win
    (bounded by min(stage, compute) per bucket pair; >=1.3x once the
    buckets are multi-ms) — plus the disk tier's round-trip cost on a
    full-width sort whose captured passes exceed a 1 MB host tier.
    Prints the standard one-line JSON:

        {"metric": "motion_pipeline_speedup", "value": <off/on>,
         "unit": "x", "vs_baseline": <same>, ...}

    The overlap needs the two legs on DISTINCT execution resources —
    device compute vs host staging on TPU, or >=2 cores on CPU, where
    the XLA dispatch releases the GIL while the stager subsets the next
    bucket. On a single-vCPU container both legs serialize on the same
    core and the ratio honestly reads ~1.0x (the banked
    motion_overlap_ms still proves the schedule overlapped); host_cpus
    rides the JSON so the reader can tell which case they measured.
    Env: GGTPU_MB_ROWS (default 400000), GGTPU_MB_SEGS (4),
         GGTPU_MB_RUNS (3)."""
    _pin_cpu_mesh()
    import shutil
    import tempfile

    import numpy as np

    import greengage_tpu
    from greengage_tpu.runtime.logger import counters

    rows = int(os.environ.get("GGTPU_MB_ROWS", "400000"))
    nseg = int(os.environ.get("GGTPU_MB_SEGS", "4"))
    runs = int(os.environ.get("GGTPU_MB_RUNS", "3"))
    path = tempfile.mkdtemp(prefix="ggtpu_motion_mb_")
    try:
        db = greengage_tpu.connect(path, numsegments=nseg)
        db.sql("create table mp (k int, v int) distributed by (k)")
        rng = np.random.default_rng(18)
        db.load_table("mp", {"k": np.arange(rows, dtype=np.int64),
                             "v": rng.integers(0, 100, rows)})
        db.sql("analyze")
        q = "select count(distinct k) from mp"
        qs = "select k, v from mp order by v, k limit 5"
        db.sql("set vmem_protect_limit_mb = 1")
        mp = _motion_pipeline_measure(db, q, runs=runs)
        # disk tier: the same sort with the host tier at 1 MB vs
        # unbounded — what demote -> segment file -> promote costs when
        # the workfile cannot stay resident
        db.sql(qs)   # warm
        t0 = time.monotonic()
        db.sql(qs)
        ram_s = time.monotonic() - t0
        db.sql(f"set spill_dir to '{os.path.join(path, 'spill-mb')}'")
        db.sql("set spill_host_limit_mb = 1")
        c0 = counters.snapshot()
        t0 = time.monotonic()
        db.sql(qs)
        disk_s = time.monotonic() - t0
        d = counters.since(c0)
        line = {
            "metric": "motion_pipeline_speedup",
            "value": mp["speedup"],
            "unit": "x",
            "vs_baseline": mp["speedup"],
            **mp,
            "spill_ram_ms": round(ram_s * 1e3, 1),
            "spill_disk_tier_ms": round(disk_s * 1e3, 1),
            "disk_tier_overhead": round(disk_s / max(ram_s, 1e-9), 2),
            "demotes": d.get("spill_demote_total", 0),
            "promotes": d.get("spill_promote_total", 0),
            "host_cpus": os.cpu_count(),
            "rows": rows, "segments": nseg,
        }
        print(json.dumps(line), flush=True)
    finally:
        shutil.rmtree(path, ignore_errors=True)


def microbench_feedback() -> None:
    """Closed measurement loop (docs/PERF.md "Self-tuning"): a statement
    whose row estimate is ~3x wrong runs cold (priced off the bad
    estimate), the reconcile pass promotes a calibration, and the SECOND
    execution plans and admits against ground truth. Prints the standard
    one-line JSON:

        {"metric": "feedback_mem_err_pct_warm", "value": N, "unit":
         "pct", "vs_baseline": <cold err / warm err>, ...receipts...}

    Env: GGTPU_MB_ROWS (default 100000), GGTPU_MB_SEGS (4)."""
    _pin_cpu_mesh()
    import shutil
    import tempfile

    import numpy as np

    import greengage_tpu
    from greengage_tpu.runtime.logger import counters

    rows = int(os.environ.get("GGTPU_MB_ROWS", "100000"))
    nseg = int(os.environ.get("GGTPU_MB_SEGS", "4"))
    path = tempfile.mkdtemp(prefix="ggtpu_feedback_mb_")
    try:
        db = greengage_tpu.connect(path, numsegments=nseg)
        db.sql("create table t (k int, b int, v double precision) "
               "distributed by (k)")
        rng = np.random.default_rng(7)
        # b in [0, 7): `where b >= 0` passes EVERYTHING but the default
        # selectivity prices it at ~1/3 — the canonical 3x underestimate
        db.load_table("t", {
            "k": np.arange(rows, dtype=np.int32),
            "b": (np.arange(rows) % 7).astype(np.int32),
            "v": rng.random(rows)})
        q = "select count(*), sum(v) from t where b >= 0"
        c0 = counters.snapshot()
        t0 = time.monotonic()
        db.sql(q)
        cold_ms = (time.monotonic() - t0) * 1e3
        cold_err = abs(int(counters.get("mem_est_error_pct")))
        t0 = time.monotonic()
        db.sql(q)
        warm_ms = (time.monotonic() - t0) * 1e3
        warm_err = abs(int(counters.get("mem_est_error_pct")))
        d = counters.since(c0)
        rep = db.feedback.report()
        line = {
            "metric": "feedback_mem_err_pct_warm",
            "value": warm_err,
            "unit": "pct",
            "vs_baseline": round(cold_err / max(warm_err, 1), 2),
            "cold_mem_err_pct": cold_err,
            "warm_mem_err_pct": warm_err,
            "corrections_applied": d.get("feedback_applied_total", 0),
            "calibration_gen": rep["gen"],
            "pending": rep["pending"],
            "admission_measured": d.get("admission_measured_total", 0),
            "admission_estimated": d.get("admission_estimated_total", 0),
            "cold_stmt_ms": round(cold_ms, 1),
            "warm_stmt_ms": round(warm_ms, 1),
            "rows": rows, "segments": nseg,
        }
        print(json.dumps(line), flush=True)
    finally:
        shutil.rmtree(path, ignore_errors=True)


def microbench_span_cost() -> None:
    """What recording one span (begin + end, runtime/trace.py) costs on THIS
    host and backend, in ns: bare, with the `gg:` profiler mirror and no
    profiler session, and with the device-memory sampler on top (one
    `memory_stats()` PJRT call a sample on a TPU; on the CPU backend the
    sampler latches off and `sampler_live` says so). Pins no platform: run
    it through `chiprun` for the numbers docs/OBSERVABILITY.md quotes."""
    import jax

    from greengage_tpu.runtime import memaccount
    from greengage_tpu.runtime import trace as T

    def per_span(name: str, traces: int = 5, spans: int = 4000) -> float:
        best = float("inf")   # of three rounds; a trace holds MAX_SPANS
        for _ in range(3):
            t0 = time.perf_counter_ns()
            for _ in range(traces):
                tr = T.Trace(0, "probe")
                for _ in range(spans):
                    tr.end(tr.begin(name, cat="exec", n=1))
            best = min(best, (time.perf_counter_ns() - t0) / (traces * spans))
        return round(best, 1)

    dev = jax.devices()[0]
    sampler, live = T.MEM_SAMPLER, memaccount.sample_watermark() is not None
    out = {"metric": "microbench_span_cost", "unit": "ns/span",
           "platform": dev.platform, "device_kind": dev.device_kind,
           "sampler_live": live}
    try:
        T.MEM_SAMPLER, T._ANNOTATION = None, False
        out["bare"] = per_span("dispatch")
        T._ANNOTATION = None   # resolves jax.profiler.TraceAnnotation anew
        out["mirror_idle"] = per_span("dispatch")
        T.MEM_SAMPLER = sampler
        out["mirror_idle_unsampled_name"] = per_span("parse")
        out["mirror_idle_sampled_name"] = per_span("dispatch")
        t0 = time.perf_counter_ns()
        for _ in range(5000):
            sampler()
        out["one_sample"] = round((time.perf_counter_ns() - t0) / 5000, 1)
    finally:
        T.MEM_SAMPLER, T._ANNOTATION = sampler, None
    print(json.dumps(out), flush=True)


def microbench(name: str) -> None:
    fn = globals().get("microbench_" + name)
    if fn is None:
        print(json.dumps({"metric": f"microbench_{name}", "value": 0,
                          "error": f"unknown microbench {name!r}"}),
              flush=True)
        raise SystemExit(2)
    fn()


# ======================================================================
# measurement
# ======================================================================

def _cut(day: str) -> int:
    import numpy as np

    return (np.datetime64(day) - np.datetime64("1970-01-01")).astype(np.int64)


def baseline_q1(data) -> float:
    import numpy as np

    li = data["lineitem"]
    cutoff = _cut("1998-12-01") - 90
    qty, price = li["l_quantity"], li["l_extendedprice"]
    disc, tax, ship = li["l_discount"], li["l_tax"], li["l_shipdate"]
    rf, ls = li["l_returnflag"].codes, li["l_linestatus"].codes

    def run():
        m = ship <= cutoff
        gid = np.where(m, rf * 2 + ls, 6)
        disc_price = price * (100 - disc)
        charge = disc_price * (100 + tax)
        out = []
        for g in range(6):
            mask = gid == g
            cnt = int(mask.sum())
            # all 8 Q1 aggregates, matching what the engine computes
            out.append((np.sum(qty, where=mask), np.sum(price, where=mask),
                        np.sum(disc_price, where=mask), np.sum(charge, where=mask),
                        np.sum(qty, where=mask) / max(cnt, 1),
                        np.sum(price, where=mask) / max(cnt, 1),
                        np.sum(disc, where=mask) / max(cnt, 1), cnt))
        return out

    run()
    best = float("inf")
    for _ in range(2):
        t0 = time.monotonic()
        run()
        best = min(best, time.monotonic() - t0)
    return best


def baseline_q3(data) -> float:
    import pandas as pd

    li, o, c = data["lineitem"], data["orders"], data["customer"]
    cut = _cut("1995-03-15")

    def run():
        lf = pd.DataFrame({
            "l_orderkey": li["l_orderkey"], "rev": li["l_extendedprice"] * (100 - li["l_discount"]),
        })[li["l_shipdate"] > cut]
        of = pd.DataFrame({
            "o_orderkey": o["o_orderkey"], "o_custkey": o["o_custkey"],
            "o_orderdate": o["o_orderdate"],
        })[o["o_orderdate"] < cut]
        cf = pd.DataFrame({"c_custkey": c["c_custkey"]})[c["c_mktsegment"].codes ==
                                                         c["c_mktsegment"].vocab.index("BUILDING")]
        j = lf.merge(of, left_on="l_orderkey", right_on="o_orderkey")
        j = j.merge(cf, left_on="o_custkey", right_on="c_custkey")
        g = j.groupby(["l_orderkey", "o_orderdate"], as_index=False)["rev"].sum()
        return g.nlargest(10, "rev")

    run()   # warm caches: compare steady CPU vs steady device
    best = float("inf")
    for _ in range(2):
        t0 = time.monotonic()
        run()
        best = min(best, time.monotonic() - t0)
    return best


def baseline_q5(data) -> float:
    import pandas as pd

    li, o, c = data["lineitem"], data["orders"], data["customer"]
    s, n, r = data["supplier"], data["nation"], data["region"]
    lo, hi = _cut("1994-01-01"), _cut("1995-01-01")

    def run():
        asia = [i for i, (nm, rk) in enumerate(
            zip(n["n_name"], n["n_regionkey"]))
            if r["r_name"][rk] == "ASIA"]
        sf = pd.DataFrame({"s_suppkey": s["s_suppkey"], "s_nationkey": s["s_nationkey"]})
        sf = sf[sf.s_nationkey.isin(asia)]
        cf = pd.DataFrame({"c_custkey": c["c_custkey"], "c_nationkey": c["c_nationkey"]})
        of = pd.DataFrame({
            "o_orderkey": o["o_orderkey"], "o_custkey": o["o_custkey"],
        })[(o["o_orderdate"] >= lo) & (o["o_orderdate"] < hi)]
        lf = pd.DataFrame({
            "l_orderkey": li["l_orderkey"], "l_suppkey": li["l_suppkey"],
            "rev": li["l_extendedprice"] * (100 - li["l_discount"]),
        })
        j = lf.merge(of, left_on="l_orderkey", right_on="o_orderkey")
        j = j.merge(sf, left_on="l_suppkey", right_on="s_suppkey")
        j = j.merge(cf, left_on="o_custkey", right_on="c_custkey")
        j = j[j.c_nationkey == j.s_nationkey]
        return j.groupby("s_nationkey")["rev"].sum()

    run()   # warm caches: compare steady CPU vs steady device
    best = float("inf")
    for _ in range(2):
        t0 = time.monotonic()
        run()
        best = min(best, time.monotonic() - t0)
    return best


def _meta_path(bench_dir):
    # sidecar NEXT TO the cluster dir, not inside it: the store owns its
    # tree (gpcheckcat walks it) and ensure_loaded may wipe it wholesale
    return bench_dir.rstrip("/") + ".meta.json"


def _load_meta(bench_dir):
    try:
        with open(_meta_path(bench_dir)) as f:
            return json.load(f)
    except Exception:
        return None


def _save_meta(bench_dir, meta):
    tmp = _meta_path(bench_dir) + ".tmp"
    with open(tmp, "w") as f:
        json.dump(meta, f)
    os.replace(tmp, _meta_path(bench_dir))


def _counts_match(db, counts) -> bool:
    for t, want in counts.items():
        try:
            if sum(db.store.segment_rowcounts(t)) != want:
                return False
        except Exception:
            return False
    return True


def ensure_loaded(db, data, counts_want):
    """Reuse the bench dir only when it holds EXACTLY the expected rows; a
    partial/mismatched dir (killed prior run, different SF) is wiped and
    reloaded — load_table is append-only, so loading on top would silently
    inflate every number."""
    import numpy as np  # noqa: F401  (tpch data arrays)

    have = {}
    for t in counts_want:
        try:
            have[t] = sum(db.store.segment_rowcounts(t))
        except Exception:
            have[t] = -1
    if have == counts_want:
        return db
    from greengage_tpu.utils import tpch

    if any(v > 0 for v in have.values()):
        import shutil

        import greengage_tpu

        path = db.path
        log(f"bench dir rowcounts mismatch {have} — wiping and reloading")
        db.close()
        shutil.rmtree(path, ignore_errors=True)
        db = greengage_tpu.connect(path=path, numsegments=1)
    db.sql(tpch.DDL)
    for name, cols in data.items():
        db.load_table(name, cols)
    db._loaded_now = True
    return db


def timed(db, sql, runs):
    t0 = time.monotonic()
    r = db.sql(sql)
    first = time.monotonic() - t0
    log(f"first run {first:.1f}s (tiers={r.stats['tiers_used']})")
    best = float("inf")
    for i in range(runs):
        t0 = time.monotonic()
        r = db.sql(sql)
        best = min(best, time.monotonic() - t0)
    log(f"steady best {best * 1e3:.1f}ms over {runs} runs")
    return best, first, r


def record_trace(db, qname: str) -> str | None:
    """Export the newest statement trace (the last timed run) as Chrome
    trace_event JSON next to the bench cluster, so a run yields a
    per-phase PROFILE (stage vs dispatch vs fetch spans), not just a
    headline number. Best-effort — profiling must never fail the
    measurement."""
    try:
        from greengage_tpu.runtime.trace import TRACES, to_chrome

        tr = TRACES.last()
        if tr is None:
            return None
        path = os.path.join(db.path, f"trace_{qname}.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(to_chrome(tr), f)
        os.replace(tmp, path)
        log(f"trace recorded: {path}")
        return path
    except Exception as e:
        log(f"trace recording failed (non-fatal): {e}")
        return None


class _Setup:
    """Shared by the measurement and --prewarm (cache population):
    connect / validate-or-load the bench cluster, expose sidecar-cached
    CPU baselines."""

    def __init__(self, sf: float):
        from greengage_tpu.utils import tpch

        import greengage_tpu

        self.sf = sf
        self.tpch = tpch
        t_setup = time.monotonic()
        # dir name keyed by segment count (always 1 here), NOT device
        # count: the stored cluster is identical regardless of platform,
        # which is what lets a CPU --prewarm warm the dir a TPU run reads
        self.bench_dir = os.environ.get(
            "GGTPU_BENCH_DIR", f"/tmp/ggtpu_bench_sf{sf:g}_1seg")
        db = greengage_tpu.connect(path=self.bench_dir, numsegments=1)
        # warm path (the time-to-first-number fix): a bench dir already
        # loaded at this SF — validated row-exact against the sidecar —
        # goes straight to measurement; generation (minutes at SF10) and
        # the CPU baselines are skipped or served from the sidecar cache
        meta = _load_meta(self.bench_dir)
        self.data = None
        # baseline_v invalidates sidecar-cached baselines whenever a
        # baseline_qN implementation changes — bump on edit, or stale
        # numbers silently skew vs_baseline across rounds
        if meta and meta.get("baseline_v") != BASELINE_V:
            meta["baselines"] = {}
            meta["baseline_v"] = BASELINE_V
        if meta and meta.get("sf") == sf and _counts_match(db, meta["counts"]):
            counts = meta["counts"]
            loaded = False
            log(f"bench dir warm at SF{sf:g} — skipping generation")
        else:
            log(f"generating SF{sf:g}")
            self.data = tpch.generate_cached(sf)
            counts = {t: len(next(iter(v.values())))
                      for t, v in self.data.items()}
            log("loading")
            db = ensure_loaded(db, self.data, counts)
            loaded = getattr(db, "_loaded_now", False)
            meta = {"sf": sf, "counts": counts, "baselines": {},
                    "baseline_v": BASELINE_V}
            _save_meta(self.bench_dir, meta)
        self.db, self.meta, self.counts, self.loaded = db, meta, counts, loaded
        if loaded or db.catalog.get("lineitem").stats is None:
            log("analyzing")
            db.sql("analyze")   # NDV-accurate capacities avoid recompiles
        self.setup_s = time.monotonic() - t_setup
        log(f"setup done ({self.setup_s:.0f}s, loaded_now={loaded})")

    def get_baseline(self, qname: str) -> float:
        """CPU baseline seconds, from the sidecar when already measured —
        the generated arrays are only materialized if a baseline is
        actually missing."""
        if qname in self.meta.get("baselines", {}):
            return self.meta["baselines"][qname]
        if self.data is None:
            self.data = self.tpch.generate_cached(self.sf)
        s = globals()["baseline_" + qname](self.data)
        self.meta.setdefault("baselines", {})[qname] = s
        _save_meta(self.bench_dir, self.meta)
        return s


def prewarm():
    """Populate every cache the measurement path reads — dataset pickle,
    loaded cluster, stats, baseline sidecar — on the CPU (same dir name
    the real run computes), so a later run on the chip goes straight to
    Q1."""
    _pin_cpu_mesh()
    s = _Setup(SF)
    for q in QUERIES:
        q = q.strip()
        if "baseline_" + q not in globals():
            log(f"prewarm: no baseline for {q!r} — skipped")
            continue
        log(f"prewarm baseline {q}")
        s.get_baseline(q)
    log(f"prewarm complete: {s.bench_dir}")


def run() -> int:
    """The measurement, in this process. -> exit code (non-zero when any
    query or rider failed)."""
    import jax

    dev = jax.devices()[0]
    if dev.device_kind not in HBM_PEAK_GBS:
        raise SystemExit(
            f"bench: device_kind {dev.device_kind!r} (platform "
            f"{dev.platform!r}) is not in HBM_PEAK_GBS "
            f"{sorted(HBM_PEAK_GBS)}; add its peak with a source before "
            "measuring on it")
    hbm_peak = HBM_PEAK_GBS[dev.device_kind]
    device = {"platform": dev.platform, "device_kind": dev.device_kind,
              "device_count": len(jax.devices())}
    log(f"device: {device}")
    s = _Setup(SF)
    db, get_baseline = s.db, s.get_baseline
    n_rows = s.counts["lineitem"]
    loaded, setup_s = s.loaded, s.setup_s
    failed: list[str] = []

    detail = {"sf": SF, "rows": n_rows, **device,
              "loaded_now": loaded, "setup_s": round(setup_s, 1)}
    # the chip's real HBM is the limit for this known workload (the default
    # admission guard is conservative for ad-hoc queries)
    db.sql("set vmem_protect_limit_mb = 15000")
    # Q1 streams 7 lineitem columns: 4×int64 + 3×int32 codes/dates = 44 B/row
    q1_bytes_per_row = 44

    # ONE headline object: the Q1 number is the cross-round metric, and
    # Q3/Q5 ride the same line; printed once, last, after the detail block
    headline = None
    for qname, sql in (("q1", Q1), ("q3", Q3), ("q5", Q5)):
        if qname not in QUERIES:
            continue
        try:
            log(f"=== {qname} ===")
            # release the previous query's staged device arrays: at SF10
            # the three queries' column sets together exceed HBM
            db.executor._stage_cache.clear()
            best, first, r = timed(db, sql, RUNS)
            trace_path = record_trace(db, qname)
            cpu_s = get_baseline(qname)
            value = n_rows / best
            base = n_rows / cpu_s
            detail[qname] = {
                "rows_per_sec_per_chip": round(value),
                "best_ms": round(best * 1e3, 1),
                "first_run_s": round(first, 1),
                "cpu_baseline_ms": round(cpu_s * 1e3, 1),
                "vs_baseline": round(value / base, 3),
                "rows_out": len(r),
                "trace": trace_path,
            }
            # memory profile: the device allocator's live/peak bytes
            # after this query plus the executable's measured
            # memory_analysis (memory_stats is None on CPU — recorded as
            # null)
            dstats = dev.memory_stats() or {}
            detail[qname]["peak_bytes_in_use"] = dstats.get(
                "peak_bytes_in_use")
            detail[qname]["bytes_in_use"] = dstats.get("bytes_in_use")
            mem = (r.stats or {}).get("mem") or {}
            if mem.get("measured"):
                detail[qname]["executable_mem"] = mem["measured"]
            if qname == "q1":
                assert len(r) == 6, f"Q1 expected 6 groups, got {len(r)}"
                if hbm_peak is not None:
                    # share of the device's HBM peak the scan achieved, on
                    # the host clock (44 B/row over best-of-N wall time)
                    gbs = n_rows * q1_bytes_per_row / best / 1e9
                    detail[qname]["gb_per_sec"] = round(gbs, 1)
                    detail[qname]["hbm_peak_frac"] = round(gbs / hbm_peak, 3)
                headline = {
                    "metric": "tpch_q1_rows_per_sec_per_chip",
                    "value": round(value),
                    "unit": "rows/s",
                    "vs_baseline": round(value / base, 3),
                    **device,
                }
            elif headline is not None:
                headline[qname] = {
                    "rows_per_sec_per_chip": round(value),
                    "vs_baseline": round(value / base, 3),
                }
        except Exception as e:  # the remaining queries still run; the
            # failure is reported in the detail block and the exit code
            detail[qname] = {"error": f"{type(e).__name__}: {e}"}
            failed.append(qname)
        print(json.dumps({qname: detail.get(qname)}), file=sys.stderr,
              flush=True)

    # vectorized-serving rider (ISSUE 11): a small point-query table in
    # the same cluster, measured at concurrency 4 with batching on vs off
    try:
        log("=== batch_serving rider ===")
        db.executor._stage_cache.clear()
        import numpy as _np
        db.sql("drop table if exists bserve")   # a reused bench dir
        db.sql("create table bserve (k int, a int, v double precision) "
               "distributed by (k)")
        db.load_table("bserve", {
            "k": _np.arange(50_000, dtype=_np.int32),
            "a": _np.arange(50_000, dtype=_np.int32),
            "v": _np.arange(50_000) * 0.5})
        detail["batch_serving"] = _batch_serving_measure(
            db, lambda i: ("select count(*), sum(v) from bserve "
                           f"where a > {100 + i % 400}"),
            concs=(4,), per_thread=8)
    except Exception as e:
        detail["batch_serving"] = {"error": f"{type(e).__name__}: {e}"}
        failed.append("batch_serving")

    # window-engine rider (ISSUE 12): an ordered-global ntile (the
    # gather-free all-gather rank machinery) and a partitioned running
    # sum over lineitem, warm-timed
    try:
        log("=== window rider ===")
        from greengage_tpu.runtime.logger import counters as _wc

        wq = {
            "ntile_global": ("select max(nt) from (select ntile(8) over "
                             "(order by o_orderkey) nt from orders) t"),
            "partitioned_running_sum": (
                "select max(rs) from (select sum(l_quantity) over "
                "(partition by l_suppkey order by l_extendedprice, "
                "l_orderkey) rs from lineitem) t"),
        }
        wd = {}
        for name, q in wq.items():
            db.sql(q)   # warm: compile once, then measure dispatch
            t0 = time.monotonic()
            r = db.sql(q)
            wd[name] = {"ms": round((time.monotonic() - t0) * 1e3, 1),
                        "compute_ms": r.stats.get("compute_ms")}
        wd["gather_free_total"] = _wc.get("window_gather_free_total")
        wd["funnel_total"] = _wc.get("window_funnel_total")
        detail["window"] = wd
    except Exception as e:
        detail["window"] = {"error": f"{type(e).__name__}: {e}"}
        failed.append("window")

    # TPC-DS / scalar-fusion rider (ISSUE 13): Q42's date-math star join
    # over the dict-encoded dimension, warm-timed with the scalar fusion
    # counters
    try:
        log("=== tpcds scalar rider ===")
        from greengage_tpu.runtime.logger import counters as _sc
        from greengage_tpu.utils import tpcds as _tpcds

        db.executor._stage_cache.clear()
        # only the three tables the rider reads (the TPC-DS schema's
        # `customer` would collide with the TPC-H one in this cluster),
        # dropped first so a reused bench dir does not load them twice
        ds_tables = ["store_sales", "date_dim", "item"]
        for t in ds_tables:
            db.sql(f"drop table if exists {t}")
        _tpcds.load(db, 1.0, tables=ds_tables)
        db.sql("analyze")
        q42 = """select dt.d_year, item.i_category_id, item.i_category,
                        sum(ss_ext_sales_price) rev
                 from date_dim dt, store_sales, item
                 where dt.d_date_sk = store_sales.ss_sold_date_sk
                   and store_sales.ss_item_sk = item.i_item_sk
                   and item.i_manager_id = 1 and dt.d_moy = 11
                   and dt.d_year = 2000
                 group by dt.d_year, item.i_category_id, item.i_category
                 order by rev desc, d_year, i_category_id limit 100"""
        qext = """select extract(year from d_date) y, date_trunc('quarter',
                         d_date) q, sum(ss_ext_sales_price) rev
                  from store_sales, date_dim
                  where ss_sold_date_sk = d_date_sk
                  group by extract(year from d_date),
                           date_trunc('quarter', d_date)
                  order by y, q"""
        ds = {}
        for name, q in (("q42", q42), ("extract_rollup", qext)):
            db.sql(q)   # warm: compile once, then measure dispatch
            t0 = time.monotonic()
            r = db.sql(q)
            ds[name] = {"ms": round((time.monotonic() - t0) * 1e3, 1),
                        "rows": len(r)}
        ds["scalar_device_total"] = _sc.get("scalar_device_total")
        ds["scalar_host_fallback_total"] = \
            _sc.get("scalar_host_fallback_total")
        detail["tpcds"] = ds
    except Exception as e:
        detail["tpcds"] = {"error": f"{type(e).__name__}: {e}"}
        failed.append("tpcds")

    # data-movement rider (ISSUE 18): the bucketed DISTINCT spill merge
    # with the bucket pipeline on vs off, then the same statement through
    # the disk tier
    try:
        log("=== motion pipeline rider ===")
        from greengage_tpu.runtime.logger import counters as _mc

        db.executor._stage_cache.clear()
        qmd = "select count(distinct l_orderkey) from lineitem"
        saved_vmem = int(db.settings.vmem_protect_limit_mb)
        db.sql("set vmem_protect_limit_mb = 64")
        try:
            md = _motion_pipeline_measure(db, qmd, runs=2)
            db.sql("set spill_host_limit_mb = 64")
            c0 = _mc.snapshot()
            t0 = time.monotonic()
            db.sql(qmd)
            md["disk_tier_ms"] = round((time.monotonic() - t0) * 1e3, 1)
            dd = _mc.since(c0)
            md["demotes"] = dd.get("spill_demote_total", 0)
            md["promotes"] = dd.get("spill_promote_total", 0)
        finally:
            db.sql("set spill_host_limit_mb = 512")
            db.sql(f"set vmem_protect_limit_mb = {saved_vmem}")
        detail["motion_pipeline"] = md
    except Exception as e:
        detail["motion_pipeline"] = {"error": f"{type(e).__name__}: {e}"}
        failed.append("motion_pipeline")

    # self-tuning rider (ISSUE 20): the same Q1 shape run twice through
    # the closed loop — on silicon the second execution should admit by
    # MEASURED footprint (live HBM allocator stats), and the est-vs-actual
    # admission error gauge should collapse; receipts land next to the
    # CPU microbench numbers
    try:
        log("=== feedback rider ===")
        from greengage_tpu.runtime.logger import counters as _fc

        qf = ("select l_returnflag, count(*), sum(l_quantity) "
              "from lineitem where l_quantity >= 0 group by l_returnflag")
        c0 = _fc.snapshot()
        db.sql(qf)
        cold_err = abs(int(_fc.get("mem_est_error_pct")))
        t0 = time.monotonic()
        r2 = db.sql(qf)
        fd = _fc.since(c0)
        detail["feedback"] = {
            "warm_stmt_ms": round((time.monotonic() - t0) * 1e3, 1),
            "cold_mem_err_pct": cold_err,
            "warm_mem_err_pct": abs(int(_fc.get("mem_est_error_pct"))),
            "admitted_by": r2.stats.get("mem", {}).get("admitted_by"),
            "corrections_applied": fd.get("feedback_applied_total", 0),
            "admission_measured": fd.get("admission_measured_total", 0),
            "calibration_gen": db.feedback.report()["gen"],
        }
    except Exception as e:
        detail["feedback"] = {"error": f"{type(e).__name__}: {e}"}
        failed.append("feedback")

    detail["failed"] = failed
    print(json.dumps(detail, indent=None), file=sys.stderr, flush=True)
    db.close()
    if headline is not None:
        print(json.dumps(headline), flush=True)
    if failed:
        log(f"FAILED: {', '.join(failed)}")
        return 1
    return 0


if __name__ == "__main__":
    if "--microbench" in sys.argv:
        i = sys.argv.index("--microbench")
        microbench(sys.argv[i + 1] if i + 1 < len(sys.argv) else "staging")
    elif "--prewarm" in sys.argv:
        prewarm()
    else:
        sys.exit(run())
