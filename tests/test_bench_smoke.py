"""bench.py harness smoke: the warm path — dataset pickle cache, row-exact
bench-dir reuse, baseline sidecar — must skip generation and still land
the same headline, and the headline must name the device it ran on
(reference analog: the perf harness reuses loaded clusters,
src/test/performance)."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(tmp):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "GGTPU_BENCH_SF": "0.01",
        "GGTPU_BENCH_RUNS": "1",
        "GGTPU_BENCH_QUERIES": "q1",
        "GGTPU_BENCH_DIR": os.path.join(tmp, "cluster"),
        # dataset pickle cache scoped to the test tmpdir, not /tmp
        "GGTPU_TPCH_CACHE_DIR": tmp,
    })
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=420)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


def test_bench_cold_then_warm(tmp_path):
    tmp = str(tmp_path)
    line1, err1 = _run_bench(tmp)
    assert line1["metric"] == "tpch_q1_rows_per_sec_per_chip"
    assert line1["value"] > 0
    assert (line1["platform"], line1["device_kind"]) == ("cpu", "cpu")
    assert "generating" in err1

    # warm run: same dir — generation must be skipped entirely and the
    # baseline must come from the sidecar (no second baseline computation)
    line2, err2 = _run_bench(tmp)
    assert line2["value"] > 0
    assert "skipping generation" in err2
    assert "generating" not in err2
    meta_file = os.path.join(tmp, "cluster.meta.json")
    with open(meta_file) as f:
        meta = json.load(f)
    assert meta["baselines"]["q1"] > 0
    # SF0.01: 15k orders x 1-7 lines (avg 4) — seed-dependent but bounded
    assert 45_000 < meta["counts"]["lineitem"] < 75_000
