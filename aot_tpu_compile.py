"""Compile a benchmark statement for the TPU without a chip, at SF10 shapes.

    python aot_tpu_compile.py [--query benchmark/queries/q18.sql] [--sf 10]
                              [--nseg 1] [--lower-only]

Run by hand (on-chip-measurement guide, section 2): libtpu is installed in
the sandbox, so `get_topology_desc("v5e:2x2")` gives compile-only devices.
The statement is planned against a tiny cluster of the benchmark's generator
(SF 0.05, so that c_name is raw TEXT as at SF10; loaded and analyzed) whose row counts and ANALYZE statistics are
then scaled to `--sf`, so the planner and the compiler see the capacities,
bounds and distinct counts of the real scale. What comes out is a compile
time, the executable's memory analysis and an operation count: never a
device time. Not a test: a one-operand sort of 2^20 rows alone compiles in
36 s here.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import tempfile
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(HERE, "benchmark"), HERE]

SMALL_SF = 0.05
COUNTED = ("sort", "scatter", "all-to-all", "all-gather", "while", "gather",
           "fusion")


def scale_stats(db, factor: float) -> None:
    """ANALYZE's numbers at the small scale -> what it would report at the
    large one: row counts, and for key-like columns (distinct count within a
    factor of five of the rows) the distinct count, bounds and histogram."""
    for schema in db.catalog.tables.values():
        ts = schema.stats
        if ts is None or not ts.rows:
            continue
        for cs in ts.columns.values():
            if cs.ndv * 5 < ts.rows:
                continue
            cs.ndv *= factor
            dense = cs.max is not None and cs.min is not None \
                and cs.max - cs.min < 2 * cs.ndv / factor
            if dense:   # surrogate keys 1..n: the domain grows with n
                cs.max = cs.min + (cs.max - cs.min + 1) * factor - 1
                cs.hist = [cs.min + (h - cs.min) * factor for h in cs.hist]
                cs.mcv = []
        ts.rows = int(round(ts.rows * factor))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--query", default=os.path.join(HERE, "benchmark", "queries",
                                                    "q18.sql"))
    ap.add_argument("--sf", type=float, default=10.0)
    ap.add_argument("--nseg", type=int, default=1)
    ap.add_argument("--first-run", action="store_true",
                    help="plan as a process's first run does: forget what the "
                         "small run taught the feedback store")
    ap.add_argument("--lower-only", action="store_true",
                    help="count the lowered program's operations, no compile")
    a = ap.parse_args()
    with open(a.query) as f:
        sql = f.read()

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import greengage_tpu
    import tpch_data
    from greengage_tpu.exec.compile import Compiler
    from greengage_tpu.parallel.mesh import SEG_AXIS
    from greengage_tpu.sql.parser import parse

    root = tempfile.mkdtemp(prefix="aot")
    db = greengage_tpu.connect(root, numsegments=a.nseg)
    data = tpch_data.generate(SMALL_SF, 20260101)
    db.sql(tpch_data.DDL)
    for t in tpch_data.TABLES:
        db.load_table(t, data[t])
    db.sql("analyze")

    # the input dtypes, from one real run at the small scale
    seen = {}
    ex = db.executor
    ensure = ex._ensure_mem_analysis

    def spy(comp, inputs):
        seen["dtypes"] = [x.dtype for x in inputs]
        seen["spec"] = comp.input_spec
        return ensure(comp, inputs)
    ex._ensure_mem_analysis = spy
    db.sql(sql)
    ex._ensure_mem_analysis = ensure
    if a.first_run:
        db.feedback.reset()
    names = [(t, c) for t, cols, *_ in seen["spec"] for c in cols + ["@present"]]
    dtype_of = dict(zip(names, seen["dtypes"]))

    factor = a.sf / SMALL_SF
    scale_stats(db, factor)
    big = tpch_data.table_rows(a.sf)
    counts = db.store.segment_rowcounts

    def scaled_counts(table, snapshot=None):
        out = counts(table, snapshot)
        n = big.get(table)
        return out if n is None else [-(-n // a.nseg)] * len(out)
    db.store.segment_rowcounts = scaled_counts

    stmt = parse(sql)
    stmt = stmt[0] if isinstance(stmt, list) else stmt
    planned, consts, _outs = db._plan(stmt)
    print(db.sql("explain " + sql).plan_text)

    topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    devs = np.array(topo.devices[:a.nseg])
    mesh = Mesh(devs, (SEG_AXIS,))
    comp = Compiler(db.catalog, db.store, mesh, a.nseg, consts,
                    db.settings).compile(planned)
    shard = NamedSharding(mesh, P(SEG_AXIS))
    args = []
    for t, cols, cap, *_ in comp.input_spec:
        for c in cols + ["@present"]:
            args.append(jax.ShapeDtypeStruct((a.nseg * cap,), dtype_of[(t, c)],
                                             sharding=shard))
    out = {"query": os.path.basename(a.query), "sf": a.sf, "nseg": a.nseg,
           "inputs": [[t, len(cols), cap] for t, cols, cap, *_ in comp.input_spec],
           "est_bytes": comp.est_bytes, "flags": comp.flag_names}
    t0 = time.monotonic()
    lowered = comp.device_fn.lower(*args)
    out["lower_s"] = round(time.monotonic() - t0, 1)
    text = lowered.as_text()
    if os.environ.get("AOT_KEEP_STABLEHLO"):
        with open(os.environ["AOT_KEEP_STABLEHLO"], "w") as f:
            f.write(text)
    out["stablehlo_ops"] = {
        k: len(re.findall(r"(?<!#)stablehlo\." + k + r"\b", text))
        for k in ("sort", "scatter", "all_to_all", "all_gather", "while",
                  "gather")}
    if not a.lower_only:
        t0 = time.monotonic()
        exe = lowered.compile()
        out["compile_s"] = round(time.monotonic() - t0, 1)
        ma = exe.memory_analysis()
        out["memory_analysis"] = {
            k: int(getattr(ma, k + "_size_in_bytes", 0))
            for k in ("argument", "output", "temp", "generated_code", "alias")}
        hlo = exe.as_text()
        out["hlo_ops"] = {k: len(re.findall(r"= [^=\n]*? " + k + r"(\.\d+)?\(", hlo))
                          for k in COUNTED}
        out["sort_shapes"] = sorted(set(re.findall(
            r"\[(\d+)\][^\n]*? sort\(", hlo)))
    db.close()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
