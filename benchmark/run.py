"""The benchmark's one command.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

run from the root of a checkout on a machine that holds the TPU chips the
cell asks for. Everything that belongs to one cell is data found by the
names in BENCHMARK.json: `configs/<config>.json`, `traffic/<traffic>.json`,
`queries/<query>.sql` + `.json`, `metrics/<metric>.json`, and the data
module that the configuration's `data` key names. The last line of
stdout is one JSON object; a run that cannot measure prints none and exits
non-zero. One process holds the chips: no child process is started.
"""

from __future__ import annotations

import time

T0 = time.monotonic()   # process start, as near as Python gives it

import argparse   # noqa: E402
import importlib.util   # noqa: E402
import json   # noqa: E402
import os   # noqa: E402
import shutil   # noqa: E402
import sys   # noqa: E402
import tempfile   # noqa: E402
from types import SimpleNamespace   # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]


def log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T0:6.1f}s] {msg}", file=sys.stderr,
          flush=True)


def read_json(*parts: str):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


# what a data module provides (benchmark/README.md, "Data modules")
DATA_INTERFACE = ("GENERATOR_VERSION", "TABLES", "DDL", "table_rows",
                  "generate", "column_types")


def data_module(config: dict):
    """The module that makes a configuration's tables: the file directly
    under benchmark/ that its `data` key names, `tpch_data` without one."""
    name = config.get("data", "tpch_data")
    if not (name.isidentifier() and os.path.isfile(os.path.join(HERE, name + ".py"))):
        raise SystemExit(f"run.py: configuration {config['name']!r} names data "
                         f"module {name!r}, which is no benchmark/{name}.py")
    mod = importlib.import_module(name)
    missing = [k for k in DATA_INTERFACE if not hasattr(mod, k)]
    if missing:
        raise SystemExit(f"run.py: data module {name!r} lacks {missing}")
    return mod


def load_cell(workload: str) -> SimpleNamespace:
    """The cell's entry of BENCHMARK.json and the data files it names."""
    bench = read_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json")
    traffic = read_json(HERE, "traffic", cell["traffic"] + ".json")
    names = traffic.get("round") or [s["query"] for s in traffic["shapes"]]
    queries = {}
    for q in dict.fromkeys(names):
        with open(os.path.join(HERE, "queries", q + ".sql")) as f:
            queries[q] = {"sql": f.read(), **read_json(HERE, "queries", q + ".json")}

    def mine(m):
        return workload in m.get("workloads", [workload])
    config = {"name": cell["config"],
              **read_json(HERE, "configs", cell["config"] + ".json")}
    return SimpleNamespace(
        name=workload, chips=cell["chips"], traffic=traffic, queries=queries,
        config=config, data=data_module(config),
        end_to_end=[m["name"] for m in bench["end_to_end"] if mine(m)],
        per_layer=[m["name"] for m in bench["per_layer"] if mine(m)],
        units={m["name"]: m["unit"]
               for m in bench["end_to_end"] + bench["per_layer"]})


def modules_in(sub: str) -> list:
    """Every Python file of a data directory, imported: how a later PR
    brings code (a metric source kind, a query's oracle) as a new file."""
    out = []
    for fn in sorted(os.listdir(os.path.join(HERE, sub))):
        if fn.endswith(".py"):
            spec = importlib.util.spec_from_file_location(
                f"{sub}_{fn[:-3]}", os.path.join(HERE, sub, fn))
            out.append(importlib.util.module_from_spec(spec))
            spec.loader.exec_module(out[-1])
    return out


def metric_kinds() -> dict:
    """source kind -> reader: every `read_<kind>` of metrics/*.py."""
    return {k[5:]: v for mod in modules_in("metrics")
            for k, v in vars(mod).items()
            if k.startswith("read_") and callable(v)}


def oracles() -> dict:
    """query -> Oracle: oracle.py's, and the ORACLES of any queries/*.py."""
    from oracle import ORACLES

    return {**ORACLES, **{k: v for mod in modules_in("queries")
                          for k, v in getattr(mod, "ORACLES", {}).items()}}


def per_layer_metrics(cell, ctx) -> dict:
    """Each metric's file names a source kind and its arguments; a reader
    that finds nothing to read returns None and the metric is left out."""
    kinds, out = metric_kinds(), {}
    for name in cell.per_layer:
        spec = read_json(HERE, "metrics", name + ".json")
        value = kinds[spec["kind"]](spec, ctx)
        if value is not None:
            out[name] = {"value": value, "unit": cell.units[name]}
    return out


def check_answers(records: list[dict], answers: dict, oracle_of: dict) -> int:
    """Every statement of the window against the stored oracle answers;
    a wrong answer becomes that record's error. -> number failed."""
    from oracle import WrongAnswer, compare

    for r in records:
        if r["error"] is None:
            try:
                compare(r["query"], r["rows"],
                        oracle_of[r["query"]].rows(answers[r["query"]], r["params"]))
            except WrongAnswer as e:
                r["error"] = str(e)
    bad = [r for r in records if r["error"]]
    for r in bad[:5]:
        log(f"FAILED {r['query']} {r['params']}: {r['error']}")
    return len(bad)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             sf: float | None = None, cache_root: str | None = None,
             require_tpu: bool = True, keep_trace: str | None = None
             ) -> dict | None:
    """-> the result object, or None where the devices are not the cell's.
    `sf`, `cache_root` and `require_tpu` are for the CPU rehearsal
    (rehearse.py), which checks control flow and answers, never speed."""
    cell = load_cell(workload)
    import jax

    devs = jax.devices()
    log(f"platform={devs[0].platform} device_kind={devs[0].device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    if len(devs) < cell.chips or (require_tpu and devs[0].platform != "tpu"):
        print(f"run.py: {workload} needs {cell.chips} TPU device(s); JAX found "
              f"{len(devs)} of platform {devs[0].platform!r}. Not running on "
              "anything else.", file=sys.stderr)
        return None

    import greengage_tpu
    from greengage_tpu.runtime.logger import counters, histograms
    from greengage_tpu.storage import native

    import tpch_data
    import devtrace as tracing
    import traffic as tr
    oracle_of = oracles()
    if require_tpu and not native.have_native():
        raise RuntimeError(f"native codec did not load: {native.build_error()}")
    log(f"compile cache: {jax.config.jax_compilation_cache_dir}")

    # ---- set-up: the cached cluster, a working copy, warm-up
    cache_root = cache_root or os.path.join(HERE, ".cache")
    for rebuild in (False, True):
        root, meta, answers = tpch_data.ensure_cluster(
            cell.data, cell.config, seed, list(cell.queries), cache_root,
            oracle_of, log, sf=sf, rebuild=rebuild)
        db = greengage_tpu.connect(tpch_data.working_copy(root),
                                   numsegments=cell.config["numsegments"])
        if tpch_data.counts_match(db, meta["rows"]):
            break
        log("row counts do not match the sidecar: wiping the cached cluster")
        db.close()
    else:
        raise RuntimeError("a freshly built cluster does not hold its rows")
    tmp = tempfile.mkdtemp(prefix="ggb")
    try:
        mesh = list(db.mesh.devices.flat)
        if len(mesh) != cell.chips:
            raise RuntimeError(f"mesh has {len(mesh)} devices, cell asks {cell.chips}")
        rows = meta["rows"]
        stmts = tr.Statements(cell.traffic,
                              {q: v["sql"] for q, v in cell.queries.items()},
                              rows, seed)
        round_rows = sum(rows[t] for q in cell.traffic.get("round", [])
                         for t in cell.queries[q]["reads"])
        if sf is None and round_rows != cell.traffic.get("round_rows", round_rows):
            raise RuntimeError(f"the round reads {round_rows} rows, the traffic "
                               f"file says {cell.traffic['round_rows']}")
        env = SimpleNamespace(db=db, traffic=cell.traffic, stmts=stmts,
                              seconds=seconds, seed=seed, chips=cell.chips,
                              round_rows=round_rows,
                              sock=os.path.join(tmp, "s.sock"))
        warm = tr.warm_up(db, stmts, seed, log)
        if check_answers(warm, answers, oracle_of):
            raise RuntimeError("a warm-up answer differs from the oracle")
        drive = getattr(tr, cell.traffic["kind"])
        tr_dir = os.path.join(tmp, "trace")
        mark = tracing.start(tr_dir) if trace else None
        c0, h0 = counters.snapshot(), histograms.snapshot()
        setup_s = time.monotonic() - T0

        # ---- the window
        records, info = drive(env)
        c1, h1 = counters.since(c0), histograms.snapshot()
        profile = None
        if trace:
            tracing.stop(mark)
            if keep_trace:
                shutil.copytree(tr_dir, keep_trace, dirs_exist_ok=True)
            profile = tracing.load(tr_dir)
        log(f"window: {len(records)} statements, {json.dumps(info)}")

        # ---- answers, then numbers
        failed = check_answers(records, answers, oracle_of)
        metrics = getattr(tr, cell.traffic["kind"] + "_metrics")(records, info, env)
        metrics["setup_s"] = setup_s
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in mesh]
        result = {
            "correct": failed == 0, "attempted": len(records), "failed": failed,
            "metrics": {k: {"value": metrics[k], "unit": cell.units[k]}
                        for k in cell.end_to_end},
            "device": {"platform": devs[0].platform, "kind": devs[0].device_kind,
                       "count": len(devs), "memory_peak_bytes": max(peaks)}}
        if trace:
            log(f"counters over the window: {json.dumps(c1, sort_keys=True)}")
            ctx = SimpleNamespace(
                cell=cell, warmup=warm, window=records, info=info, counters=c1,
                hist=(h0, h1), rows=rows, profile=profile,
                peaks=read_json(HERE, "peaks.json")["device_kinds"],
                device_kind=devs[0].device_kind)
            result["metrics"] = per_layer_metrics(cell, ctx)
            result["device"].update(busy_s=profile.busy_s(), window_s=profile.window_s)
            result["breakdown"] = profile.breakdown()
        return result
    finally:
        db.close()
        shutil.rmtree(tmp, ignore_errors=True)
        shutil.rmtree(os.path.join(root, "work"), ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="with --trace 1: also copy the profiler's files there")
    a = ap.parse_args()
    result = run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                      keep_trace=a.keep_trace)
    if result is None:
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
