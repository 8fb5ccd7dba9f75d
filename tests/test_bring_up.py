"""Start-up contracts of the chip bring-up: chip_smoke.py refuses anything
but a TPU before doing any work, and the persistent compile cache lives
where JAX_COMPILATION_CACHE_DIR says (else <checkout>/.jax_cache) with the
package setting it in one place and deleting nothing."""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    p = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, cwd=REPO, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode not in (0, None), p.stdout[-2000:]
    assert time.monotonic() - t0 < 60
    assert "platform 'cpu'" in p.stderr, p.stderr[-2000:]
    # refused before any data was generated, and no result line
    assert "generated" not in p.stdout
    assert '"ok"' not in p.stdout


_CACHE_CHILD = r"""
import json, os, sys
import numpy as np
import jax
import greengage_tpu

def files(path):
    return sorted(os.path.join(r, f) for r, _d, fs in os.walk(path)
                  for f in fs)

out = {}
db = greengage_tpu.connect(numsegments=2)
if sys.argv[1] == "query":
    db.sql("create table t (k int, g int, v bigint) distributed by (k)")
    db.sql("create table u (k int, w bigint) distributed by (k)")
    n = 4000
    db.load_table("t", {"k": np.arange(n), "g": np.arange(n) % 97,
                        "v": np.arange(n, dtype=np.int64)})
    db.load_table("u", {"k": np.arange(n), "w": np.arange(n, dtype=np.int64)})
    # a join + grouped sort: comfortably above the 0.2 s persist threshold
    db.sql("select g, sum(v + w) s from t, u where t.k = u.k "
           "group by g order by s desc limit 5")
    db.close()
    out["after_query"] = files(jax.config.jax_compilation_cache_dir)
    # a second session must delete nothing in the directory
    db = greengage_tpu.connect(numsegments=2)
db.close()
out["dir"] = jax.config.jax_compilation_cache_dir
out["at_exit"] = files(out["dir"]) if os.path.isdir(out["dir"]) else []
print(json.dumps(out))
"""


def _files(path):
    return {os.path.join(r, f) for r, _d, fs in os.walk(path) for f in fs}


def _cache_child(mode, env):
    env = dict(env, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    p = subprocess.run([sys.executable, "-c", _CACHE_CHILD, mode], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_compile_cache_follows_the_environment(tmp_path):
    placed = str(tmp_path / "jc")
    default = os.path.join(REPO, ".jax_cache")
    before = _files(default)
    got = _cache_child("query", dict(os.environ,
                                     JAX_COMPILATION_CACHE_DIR=placed))
    assert got["dir"] == placed
    assert got["after_query"], "no cache entry landed in the placed directory"
    assert set(got["after_query"]) <= set(got["at_exit"])
    # other xdist workers persist their own programs into .jax_cache while
    # the child runs: what must not appear there is the CHILD's entries
    # (a cache file is named by its program's key)
    mine = {os.path.basename(f) for f in got["at_exit"]}
    leaked = {f for f in _files(default) - before
              if os.path.basename(f) in mine}
    assert not leaked, "entries leaked into .jax_cache"


def test_compile_cache_defaults_to_checkout():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    assert _cache_child("connect", env)["dir"] == os.path.join(REPO,
                                                               ".jax_cache")
