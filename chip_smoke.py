"""chip_smoke.py — the quickest proof that the served SQL path runs on the TPU.

One process, no arguments needed, run from a fresh copy of the checkout:

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # one four-chip host, one process

It refuses to start unless JAX reports TPU devices, builds the native
codec (through its loader), generates TPC-H from --seed, loads lineitem / orders / customer into
a fresh cluster through connect() / sql(DDL) / load_table() / analyze, then
answers Q6 and Q1 through Database.sql with default settings (each until
a run hits the program cache) and Q1 once more over a live SqlServer on a
unix socket. `--queries q6,q1,q3` adds Q3, whose cold compile alone takes
minutes on this compiler (see PERF.md). Every answer is compared with a plain
numpy/pandas evaluation of the same query over the generated arrays.

The last line of stdout is one JSON object naming the device, printed only
when every phase held; any failure is a non-zero exit with no such line.
One process holds the chip: the smoke starts no child that imports jax.
"""

import argparse
import importlib.metadata
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback

T0 = time.monotonic()

Q6 = """
select sum(l_extendedprice * l_discount) as revenue
from lineitem
where l_shipdate >= date '1994-01-01'
  and l_shipdate < date '1994-01-01' + interval '1' year
  and l_discount between 0.05 and 0.07 and l_quantity < 24
"""

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

QUERIES = {"q6": Q6, "q1": Q1, "q3": Q3}
TABLES = ("lineitem", "orders", "customer")
# averages divide an exact int64 sum by an exact count in float64 on both
# sides; only the order of the two float divisions may differ
AVG_RTOL = 1e-12
STAT_KEYS = ("compiled", "stage_ms", "compute_ms", "fetch_ms", "tiers_used",
             "spill_kind")


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(f"[smoke +{time.monotonic() - T0:6.1f}s] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ----------------------------------------------------------------------
# the oracle: plain numpy/pandas over the generated arrays. Decimals are
# the generator's scaled int64; a sum is exact integer arithmetic and is
# presented the way the engine presents a DECIMAL (value / 10**scale in
# float64), so equal integers give bit-equal floats.
# ----------------------------------------------------------------------

def _days(day: str) -> int:
    import numpy as np

    return int((np.datetime64(day) - np.datetime64("1970-01-01"))
               .astype(np.int64))


def oracle_q6(data) -> list[tuple]:
    import numpy as np

    li = data["lineitem"]
    ship, disc = li["l_shipdate"], li["l_discount"]
    m = ((ship >= _days("1994-01-01")) & (ship < _days("1995-01-01"))
         & (disc >= 5) & (disc <= 7) & (li["l_quantity"] < 2400))
    rev = int(np.sum(li["l_extendedprice"][m] * disc[m]))
    return [(rev / 10.0 ** 4,)]


def oracle_q1(data) -> list[tuple]:
    import numpy as np

    li = data["lineitem"]
    m = li["l_shipdate"] <= _days("1998-12-01") - 90
    rf, ls = li["l_returnflag"], li["l_linestatus"]
    qty, price = li["l_quantity"][m], li["l_extendedprice"][m]
    disc, tax = li["l_discount"][m], li["l_tax"][m]
    disc_price = price * (100 - disc)
    charge = disc_price * (100 + tax)
    gid = rf.codes[m].astype(np.int64) * len(ls.vocab) + ls.codes[m]
    rows = []
    for g in np.unique(gid):
        k = gid == g
        cnt = int(k.sum())
        s_qty, s_price = int(qty[k].sum()), int(price[k].sum())
        rows.append((
            rf.vocab[g // len(ls.vocab)], ls.vocab[g % len(ls.vocab)],
            s_qty / 10.0 ** 2, s_price / 10.0 ** 2,
            int(disc_price[k].sum()) / 10.0 ** 4,
            int(charge[k].sum()) / 10.0 ** 6,
            s_qty / cnt / 100.0, s_price / cnt / 100.0,
            int(disc[k].sum()) / cnt / 100.0, cnt))
    return sorted(rows)


def oracle_q3(data) -> list[tuple]:
    import numpy as np
    import pandas as pd

    li, o, c = data["lineitem"], data["orders"], data["customer"]
    cut = _days("1995-03-15")
    seg = c["c_mktsegment"]
    cust = c["c_custkey"][seg.codes == seg.vocab.index("BUILDING")]
    om = (o["o_orderdate"] < cut) & np.isin(o["o_custkey"], cust)
    orders = pd.DataFrame({"key": o["o_orderkey"][om],
                           "o_orderdate": o["o_orderdate"][om],
                           "o_shippriority": o["o_shippriority"][om]})
    lm = li["l_shipdate"] > cut
    lm &= np.isin(li["l_orderkey"], orders["key"].to_numpy())
    lines = pd.DataFrame({
        "key": li["l_orderkey"][lm],
        "rev": li["l_extendedprice"][lm] * (100 - li["l_discount"][lm])})
    g = (lines.merge(orders, on="key")
         .groupby(["key", "o_orderdate", "o_shippriority"], as_index=False)
         ["rev"].sum()
         .sort_values(["rev", "o_orderdate"], ascending=[False, True]))
    top = g.head(11)
    keys = list(zip(top["rev"], top["o_orderdate"]))
    check(len(set(keys)) == len(keys),
          "oracle: Q3's first eleven rows tie on (revenue, o_orderdate); "
          "the order of the answer is not defined for this seed")
    epoch = np.datetime64("1970-01-01", "D")
    return [(int(r.key), int(r.rev) / 10.0 ** 4,
             epoch + np.timedelta64(int(r.o_orderdate), "D"),
             int(r.o_shippriority)) for r in top.head(10).itertuples()]


ORACLES = {"q6": oracle_q6, "q1": oracle_q1, "q3": oracle_q3}


def compare(name: str, got: list, want: list) -> None:
    """Sums and counts exact, averages (Q1 columns 6..8) to AVG_RTOL, rows
    in order. Values from the wire arrive as JSON scalars and date
    strings, so both sides are normalized to (str | float | int)."""
    import numpy as np

    def norm(v):
        if isinstance(v, (np.datetime64,)):
            return str(v)
        if isinstance(v, (np.floating, float)):
            return float(v)
        if isinstance(v, (np.integer, int)):
            return int(v)
        return v

    check(len(got) == len(want),
          f"{name}: {len(got)} rows, oracle has {len(want)}")
    for i, (g, w) in enumerate(zip(got, want)):
        g, w = [norm(v) for v in g], [norm(v) for v in w]
        check(len(g) == len(w), f"{name} row {i}: width {len(g)} != {len(w)}")
        for j, (a, b) in enumerate(zip(g, w)):
            if name.startswith("q1") and 6 <= j <= 8:
                ok = abs(a - b) <= AVG_RTOL * abs(b)
            else:
                ok = a == b
            check(ok, f"{name} row {i} col {j}: engine {a!r} != oracle {b!r}")


# ----------------------------------------------------------------------

def device_report(devs) -> list[dict]:
    out = []
    for d in devs:
        ms = d.memory_stats() or {}
        out.append({"id": d.id, "bytes_in_use": ms.get("bytes_in_use"),
                    "peak_bytes_in_use": ms.get("peak_bytes_in_use")})
    return out


def cache_entries(path: str | None) -> int:
    if not path or not os.path.isdir(path):
        return 0
    return sum(len(files) for _r, _d, files in os.walk(path))


def run_query(db, name: str, want: list, devs) -> dict:
    """Through Database.sql until a run reuses the compiled program: twice,
    or three times when cost feedback (planner/feedback.py) re-planned the
    statement from the first run's measured cardinalities — the one
    designed reason for a second compile. Anything else that compiles
    again fails the smoke."""
    runs = []
    gens = [db.feedback.gen]
    for i in (1, 2, 3):
        t = time.monotonic()
        r = db.sql(QUERIES[name])
        wall_s = time.monotonic() - t
        gens.append(db.feedback.gen)
        runs.append({"wall_s": round(wall_s, 3),
                     **{k: r.stats.get(k) for k in STAT_KEYS}})
        log(f"{name} run {i}: {json.dumps(runs[-1])}")
        compare(f"{name} (run {i})", r.rows(), want)
        if i == 1:
            continue
        if r.stats.get("compiled") is False:
            break
        check(i == 2 and gens[1] != gens[0],
              f"{name}: run {i} compiled again and no cost-feedback "
              f"re-plan explains it (feedback generations {gens})")
        log(f"{name}: cost feedback re-planned the statement after run 1 "
            f"(generation {gens[0]} -> {gens[1]}); one more run")
    measured = (r.stats.get("mem") or {}).get("measured")
    if r.stats.get("spill_kind") is None:
        # a one-program statement: its AOT compile + memory analysis must
        # have succeeded (a latched mem_failed would mean the dispatch fell
        # to the jit path after a swallowed lowering error)
        check(measured is not None,
              f"{name}: no measured memory analysis (mem_failed latched)")
    out = {"query": name, "rows": len(r), "correct": True,
           "first_run_s": runs[0]["wall_s"],
           "cached_run_ms": round(runs[-1]["wall_s"] * 1e3, 1),
           "runs": len(runs), "feedback_generations": gens,
           "mem_measured": measured, "devices": device_report(devs)}
    log(json.dumps(out))
    return out


def serve_q1(db, want: list, workdir: str) -> dict:
    """Q1 over a live SqlServer on a unix socket, from a SqlClient thread
    in this same process (the process that holds the chip)."""
    from greengage_tpu.runtime.server import SqlClient, SqlServer

    sock = os.path.join(workdir, "smoke.sock")
    srv = SqlServer(db, sock)
    srv.start()
    box: dict = {}

    def client():
        try:
            c = SqlClient(sock)
            try:
                t = time.monotonic()
                box["resp"] = c.sql(Q1)
                box["ms"] = (time.monotonic() - t) * 1e3
            finally:
                c.close()
        except Exception as e:   # re-raised on the main thread below
            box["error"] = e

    th = threading.Thread(target=client, name="smoke-client")
    try:
        th.start()
        th.join(timeout=600)
        check(not th.is_alive(), "q1 over the socket: no answer in 600 s")
    finally:
        srv.stop()
    if "error" in box:
        raise box["error"]
    compare("q1 (socket)", [tuple(r) for r in box["resp"]["rows"]], want)
    out = {"query": "q1 over SqlServer", "rows": len(box["resp"]["rows"]),
           "ms": round(box["ms"], 1), "correct": True}
    log(json.dumps(out))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1,
                    help="segments = chips of this host (1 or 4)")
    ap.add_argument("--sf", type=float, default=10.0,
                    help="TPC-H scale factor (nothing below 1)")
    ap.add_argument("--seed", type=int, default=19940801)
    ap.add_argument("--queries", default="q6,q1",
                    help="of q6,q1,q3; q3's cold compile takes minutes")
    args = ap.parse_args()
    names = [q.strip() for q in args.queries.split(",") if q.strip()]
    check(all(q in QUERIES for q in names), f"unknown query in {names}")
    check(args.sf >= 1, "the smoke runs nothing below SF1")

    # ---- 1. the device, before anything else; no platform is set here
    import jax
    import jaxlib

    devs = jax.devices()
    try:
        libtpu_v = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu_v = "not installed"
    log(f"platform={devs[0].platform} device_kind={devs[0].device_kind} "
        f"count={len(devs)} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu_v}")
    use = devs[:args.chips]
    if len(use) < args.chips or any(d.platform != "tpu" for d in use):
        print(f"chip_smoke: needs {args.chips} TPU device(s); JAX found "
              f"{len(devs)} device(s) of platform {devs[0].platform!r} "
              f"({devs[0].device_kind!r}). Not running on anything else.",
              file=sys.stderr)
        return 2

    # ---- 2. the native codec: the loader runs `make -C native` (a no-op
    # when current, a rebuild when stale); a numpy fallback is a failure
    import greengage_tpu
    from greengage_tpu.storage import native
    from greengage_tpu.utils import tpch

    check(native.have_native(),
          f"native codec did not load: {native.build_error()}")
    cache_dir = jax.config.jax_compilation_cache_dir
    cache_before = cache_entries(cache_dir)
    log(f"have_native=True compile_cache={cache_dir} "
        f"entries_before={cache_before} "
        f"JAX_COMPILATION_CACHE_DIR={os.environ.get('JAX_COMPILATION_CACHE_DIR')!r}")

    # ---- 3. data from the seed, then the oracle answers
    t = time.monotonic()
    data = tpch.generate(args.sf, seed=args.seed)
    data = {tb: data[tb] for tb in TABLES}
    counts = {tb: len(next(iter(cols.values()))) for tb, cols in data.items()}
    log(f"generated TPC-H SF{args.sf:g} seed={args.seed} in "
        f"{time.monotonic() - t:.1f}s: {counts}")
    t = time.monotonic()
    want = {q: ORACLES[q](data) for q in set(names) | {"q1"}}
    log(f"oracle answers ({', '.join(sorted(want))}) in "
        f"{time.monotonic() - t:.1f}s")

    workdir = tempfile.mkdtemp(prefix="ggtpu_smoke_")
    db = None
    report = []
    try:
        # ---- 4. a fresh cluster through the normal entry points
        db = greengage_tpu.connect(os.path.join(workdir, "cluster"),
                                   numsegments=args.chips)
        mesh_devs = list(db.mesh.devices.flat)
        check(len(mesh_devs) == args.chips
              and all(d.platform == "tpu" for d in mesh_devs),
              f"mesh is not {args.chips} TPU device(s): {mesh_devs}")
        db.sql(tpch.DDL)
        t = time.monotonic()
        for tb in TABLES:
            db.load_table(tb, data[tb])
        log(f"loaded {', '.join(TABLES)} in {time.monotonic() - t:.1f}s")
        del data
        t = time.monotonic()
        db.sql("analyze")
        log(f"analyze in {time.monotonic() - t:.1f}s")
        log("settings: defaults (no SET issued)")

        # ---- 5. the queries, then Q1 over the socket
        for q in names:
            report.append(run_query(db, q, want[q], mesh_devs))
            if args.chips > 1:
                # nothing piled on one device: every chip's allocator peak
                # is within 2x of the largest (staged shards are equal)
                peaks = [d["peak_bytes_in_use"] or 0
                         for d in report[-1]["devices"]]
                check(min(peaks) > 0 and 2 * min(peaks) >= max(peaks),
                      f"{q}: device memory peaks {peaks} are not spread "
                      "evenly over the mesh")
        report.append(serve_q1(db, want["q1"], workdir))
        if args.chips > 1 and "q3" in names:
            plan = db.sql("explain " + Q3).plan_text
            log("Q3 plan:\n" + plan)
            check("Motion Redistribute" in plan or "Motion Broadcast" in plan,
                  "Q3's plan has no Redistribute or Broadcast Motion")
        log(f"compile_cache={cache_dir} entries_before={cache_before} "
            f"entries_after={cache_entries(cache_dir)}")
    finally:
        if db is not None:
            db.close()
        shutil.rmtree(workdir, ignore_errors=True)

    log(f"all phases held: {[r['query'] for r in report]}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
    except Exception:
        traceback.print_exc()
        print("chip_smoke: FAILED with the exception above", file=sys.stderr)
        sys.exit(1)
