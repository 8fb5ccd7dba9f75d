"""CPU rehearsal of the benchmark's control flow (on-chip-measurement §2).

    python benchmark/rehearse.py [workload ...]

Drives run.py's own `run_cell` at SF 0.01 on the CPU backend (four virtual
devices, so the four-chip cell builds its mesh), twice a cell so that the
second run finds the cached cluster. A CPU trace has no device plane, so
the traced run is not rehearsed here (check_trace.py covers its reduction).
Answers are checked; no number is printed under a metric's name, because a
CPU run says nothing about speed. The cache goes to benchmark/.cache/rehearse/.
"""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

import run  # noqa: E402


def main() -> int:
    bench = run.read_json(run.ROOT, "BENCHMARK.json")
    cells = sys.argv[1:] or [w["name"] for w in bench["workloads"]]
    cache = os.path.join(run.HERE, ".cache", "rehearse")
    bad = 0
    for cell in cells:
        for attempt in (1, 2):
            r = run.run_cell(cell, seed=2147483659, seconds=3.0, trace=False,
                             sf=0.01, cache_root=cache, require_tpu=False)
            ok = (r is not None and r["correct"] and r["attempted"] > 0
                  and all(m["value"] > 0 for m in r["metrics"].values()))
            bad += not ok
            print(json.dumps({
                "cell": cell, "run": attempt, "ok": ok,
                "correct": r and r["correct"], "attempted": r and r["attempted"],
                "failed": r and r["failed"],
                "metrics_present": r and sorted(r["metrics"])}), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
