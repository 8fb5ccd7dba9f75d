"""Management-plane tools: logging + gplogfilter, gpstart/gpstop daemon
lifecycle, analyzedb incremental stats, gpload YAML loads, gppkg
packages, gpcheckperf. Reference: gpMgmt/bin counterparts."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import greengage_tpu
from greengage_tpu.mgmt import cli
from greengage_tpu.runtime.logger import filter_entries, read_entries


def run_cli(*argv):
    return cli.main(list(argv))


@pytest.fixture()
def clu(tmp_path, devices8):
    d = str(tmp_path / "clu")
    assert run_cli("init", "-d", d, "-n", "4") == 0
    return d


# ---------------------------------------------------------------------------
# gg scrub (storage verify + repair; the full behavior matrix lives in
# test_scrub.py — this keeps the COMMAND itself wired)
# ---------------------------------------------------------------------------

def test_scrub_smoke_clean_cluster(clu, capsys):
    import json

    db = greengage_tpu.connect(path=clu)
    db.sql("create table st (a int, b int) distributed by (a)")
    db.sql("insert into st values " + ",".join(
        f"({i},{i})" for i in range(32)))
    db.close()
    assert run_cli("scrub", "-d", clu, "--json") == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["files_scanned"] > 0
    assert rep["files_verified"] == rep["files_scanned"]
    assert rep["files_repaired"] == rep["files_quarantined"] == 0
    assert rep["bytes_scanned"] > 0
    # human-readable variant + the scrub event lands in the cluster log
    assert run_cli("scrub", "-d", clu) == 0
    assert "verified" in capsys.readouterr().out
    assert any(e["kind"] == "scrub" for e in read_entries(clu))


def test_scrub_smoke_reports_corruption(clu, capsys):
    db = greengage_tpu.connect(path=clu)
    db.sql("create table st (a int) distributed by (a)")
    db.sql("insert into st values " + ",".join(f"({i})" for i in range(32)))
    snap = db.store.manifest.snapshot()
    rel = next(rels[0] for rels in
               snap["tables"]["st"]["segfiles"].values() if rels)
    db.close()
    path = os.path.join(clu, "data", "st", rel)
    with open(path, "r+b") as f:
        f.seek(40)
        b = f.read(1)
        f.seek(40)
        f.write(bytes([b[0] ^ 0xFF]))
    # no mirrors: the bad file quarantines and the command reports failure
    assert run_cli("scrub", "-d", clu) == 1
    out = capsys.readouterr().out
    assert "quarantined 1" in out


# ---------------------------------------------------------------------------
# logging + logfilter
# ---------------------------------------------------------------------------

def test_statement_logging_and_filter(clu):
    db = greengage_tpu.connect(path=clu)
    db.sql("create table t (a int) distributed by (a)")
    db.sql("insert into t values (1), (2)")
    db.sql("select count(*) from t")
    with pytest.raises(Exception):
        db.sql("select nope from t")
    entries = read_entries(clu)
    kinds = {e["kind"] for e in entries}
    assert "lifecycle" in kinds and "statement" in kinds
    errs = filter_entries(entries, trouble=True)
    assert any("nope" in e["message"] for e in errs)
    assert all(e["severity"] == "ERROR" for e in errs)
    sel = filter_entries(entries, match="count")
    assert sel and all("count" in e["message"] for e in sel)
    # duration floor keeps only real statements
    slow = filter_entries(entries, min_duration_ms=0.0)
    assert all(e["kind"] == "statement" for e in slow if e["duration_ms"])


def test_log_statement_off(clu):
    db = greengage_tpu.connect(path=clu)
    db.sql("set log_statement to off")
    before = len(read_entries(clu))
    db.sql("create table q (a int) distributed by (a)")
    assert len(read_entries(clu)) == before
    db.sql("set log_statement to on")


# ---------------------------------------------------------------------------
# analyzedb incremental
# ---------------------------------------------------------------------------

def test_analyzedb_incremental(clu, capsys):
    db = greengage_tpu.connect(path=clu)
    db.sql("create table s1 (a int, b int) distributed by (a)")
    db.sql("insert into s1 values (1, 10), (2, 20)")
    db.sql("create table s2 (a int) distributed by (a)")
    db.sql("insert into s2 values (5)")
    assert run_cli("analyzedb", "-d", clu) == 0
    out = capsys.readouterr().out
    assert "analyzed s1" in out and "analyzed s2" in out
    # second run: nothing changed -> both skipped
    assert run_cli("analyzedb", "-d", clu) == 0
    out = capsys.readouterr().out
    assert "skipped s1" in out and "skipped s2" in out
    # touch one table -> only it re-analyzes
    db2 = greengage_tpu.connect(path=clu)
    db2.sql("insert into s1 values (3, 30)")
    assert run_cli("analyzedb", "-d", clu) == 0
    out = capsys.readouterr().out
    assert "analyzed s1" in out and "skipped s2" in out


# ---------------------------------------------------------------------------
# gpload
# ---------------------------------------------------------------------------

def test_gpload_yaml(clu, tmp_path, capsys):
    db = greengage_tpu.connect(path=clu)
    db.sql("create table sales (id int, region text, amt decimal(8,2)) "
           "distributed by (id)")
    csv = tmp_path / "sales.csv"
    csv.write_text("id,region,amt\n1,east,10.50\n2,west,20.25\nbad,x,y\n")
    cfg = tmp_path / "load.yml"
    cfg.write_text(textwrap.dedent(f"""
        gpload:
          input:
            source:
              file: [{csv}]
            format: csv
            header: true
            error_limit: 5
          output:
            table: sales
            mode: insert
    """))
    assert run_cli("load", "-d", clu, "-f", str(cfg)) == 0
    assert "now 2 rows" in capsys.readouterr().out
    db2 = greengage_tpu.connect(path=clu)
    assert db2.sql("select count(*) from sales").rows() == [(2,)]
    # truncate mode replaces
    assert run_cli("load", "-d", clu, "-f", str(cfg)) == 0  # insert appends
    db3 = greengage_tpu.connect(path=clu)
    assert db3.sql("select count(*) from sales").rows() == [(4,)]
    cfg.write_text(cfg.read_text().replace("mode: insert", "mode: truncate"))
    assert run_cli("load", "-d", clu, "-f", str(cfg)) == 0
    db4 = greengage_tpu.connect(path=clu)
    assert db4.sql("select count(*) from sales").rows() == [(2,)]


# ---------------------------------------------------------------------------
# gppkg
# ---------------------------------------------------------------------------

def test_pkg_install_and_create_extension(clu, tmp_path, capsys):
    pkg = tmp_path / "triple"
    pkg.mkdir()
    (pkg / "__init__.py").write_text(
        "from greengage_tpu.extensions import register_scalar\n"
        "register_scalar('triple_it', lambda a: a * 3, ('numeric',), "
        "'first')\n")
    assert run_cli("pkg", "install", str(pkg), "-d", clu) == 0
    assert run_cli("pkg", "list", "-d", clu) == 0
    assert "triple" in capsys.readouterr().out
    db = greengage_tpu.connect(path=clu)
    db.sql("create extension triple")
    db.sql("create table n (a int) distributed by (a)")
    db.sql("insert into n values (7)")
    assert db.sql("select triple_it(a) from n").rows() == [(21,)]
    # removal is refused while created
    assert run_cli("pkg", "remove", "triple", "-d", clu) == 1


# ---------------------------------------------------------------------------
# gg ps / gg cancel (pg_stat_activity / pg_cancel_backend analogs; the
# full wait-state cancellation matrix lives in test_interrupt.py)
# ---------------------------------------------------------------------------

def test_ps_and_cancel_smoke(clu, tmp_path, capsys):
    import threading
    import time

    from greengage_tpu.runtime.faultinject import faults
    from greengage_tpu.runtime.interrupt import StatementCancelled
    from greengage_tpu.runtime.server import SqlServer

    db = greengage_tpu.connect(path=clu)
    db.sql("create table pt (a int) distributed by (a)")
    db.sql("insert into pt values " + ",".join(f"({i})" for i in range(64)))
    sock = str(tmp_path / "ps.sock")
    srv = SqlServer(db, sock)
    srv.start()
    faults.inject("cancel_before_dispatch", "sleep", sleep_s=3.0,
                  occurrences=1)
    err = {}

    def victim():
        try:
            db.sql("select count(*) from pt -- ps-victim")
            err["e"] = None
        except Exception as e:
            err["e"] = e

    t = threading.Thread(target=victim)
    t.start()
    try:
        # poll gg ps until the in-flight statement shows
        line = None
        end = time.monotonic() + 5
        while line is None and time.monotonic() < end:
            assert run_cli("ps", "-s", sock) == 0
            out = capsys.readouterr().out
            line = next((ln for ln in out.splitlines()
                         if "ps-victim" in ln), None)
            if line is None:
                time.sleep(0.05)
        assert line is not None, "gg ps never showed the statement"
        # topology surfacing (the reform counters' operator window):
        # `gg ps` leads with the cluster state + topology version, and the
        # status frame carries the mh_*/manifest_* counter family
        assert "cluster: local  topology v" in out
        from greengage_tpu.runtime.server import SqlClient

        c = SqlClient(sock)
        try:
            st = c.op({"op": "status"})
        finally:
            c.close()
        assert st["ok"] and st["cluster"]["state"] == "local"
        assert "mh_topology_version" in st["cluster"]["counters"]
        sid = line.split()[0]
        assert run_cli("cancel", sid, "-s", sock) == 0
        assert f"statement {sid} cancelled" in capsys.readouterr().out
        t.join(timeout=15)
        assert not t.is_alive()
        assert isinstance(err["e"], StatementCancelled), err["e"]
        assert err["e"].cause == "user"
        # cancelling a finished id is a clean error, not a crash
        assert run_cli("cancel", sid, "-s", sock) == 1
    finally:
        faults.reset("cancel_before_dispatch")
        srv.stop()
        t.join(timeout=15)


def test_ps_requires_running_server(tmp_path, capsys):
    assert run_cli("ps", "-d", str(tmp_path / "nowhere")) == 1
    assert "running server" in capsys.readouterr().err


def test_gg_mem_smoke(clu, tmp_path, capsys):
    """`gg mem` (the measured-memory surface, docs/OBSERVABILITY.md):
    summary + --json against a live server."""
    import json as _json

    from greengage_tpu.runtime.server import SqlServer

    db = greengage_tpu.connect(path=clu)
    db.sql("create table memt (a int) distributed by (a)")
    db.sql("insert into memt values " + ",".join(f"({i})" for i in range(64)))
    db.sql("select count(*) from memt")
    sock = str(tmp_path / "mem.sock")
    srv = SqlServer(db, sock)
    srv.start()
    try:
        assert run_cli("mem", "-s", sock) == 0
        out = capsys.readouterr().out
        assert "host: rss" in out and "device:" in out
        assert run_cli("mem", "-s", sock, "--json") == 0
        payload = _json.loads(capsys.readouterr().out)
        assert "process" in payload and "executables" in payload
    finally:
        srv.stop()
    assert run_cli("mem", "-d", str(tmp_path / "nowhere")) == 1
    assert "running server" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# daemon lifecycle (subprocess: fork conflicts with pytest/jax state)
# ---------------------------------------------------------------------------

def test_constant_select(clu):
    db = greengage_tpu.connect(path=clu)
    assert db.sql("select 1").rows() == [(1,)]
    assert db.sql("select 1 + 2 as x, 'a' || 'b' as s").rows() == [(3, "ab")]
    assert db.sql("select null as n").rows() == [(None,)]
    assert db.sql("select upper('q'), abs(-4)").rows() == [("Q", 4)]
    assert db.sql("select 1 limit 0").rows() == []
    assert db.sql("select 1 where 1 = 0").rows() == []
    assert db.sql("select 1 where 2 > 1").rows() == [(1,)]


def test_pkg_missing_argument(clu, capsys):
    assert run_cli("pkg", "install", "-d", clu) == 1
    assert "requires a package" in capsys.readouterr().err


def test_start_stop_lifecycle(clu):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))))
    env.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    r = subprocess.run(
        [sys.executable, "-m", "greengage_tpu.mgmt.cli", "start", "-d", clu],
        env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "server started" in r.stdout
    try:
        sock = os.path.join(clu, ".gg.sock")
        r = subprocess.run(
            [sys.executable, "-m", "greengage_tpu.mgmt.cli", "sql",
             "-s", sock, "select 1 as one"],
            env=env, capture_output=True, text=True, timeout=120)
        assert "1" in r.stdout, r.stdout + r.stderr
    finally:
        r = subprocess.run(
            [sys.executable, "-m", "greengage_tpu.mgmt.cli", "stop",
             "-d", clu], env=env, capture_output=True, text=True, timeout=60)
    assert "server stopped" in r.stdout
    assert not os.path.exists(os.path.join(clu, "server.pid"))


def test_gpconfig_persisted_settings(devices8, tmp_path, capsys):
    """gpconfig analog: persisted cluster GUCs adopted at every connect."""
    import greengage_tpu
    from greengage_tpu.mgmt import cli

    path = str(tmp_path / "c")
    greengage_tpu.connect(path=path, numsegments=2).close()
    rc = cli.main(["config", "-d", path,
                   "-c", "vmem_protect_limit_mb", "-v", "777"])
    assert rc == 0
    rc = cli.main(["config", "-d", path,
                   "-c", "spill_prefetch", "-v", "off"])
    assert rc == 0
    d = greengage_tpu.connect(path=path, numsegments=2)
    assert d.settings.vmem_protect_limit_mb == 777
    assert d.settings.spill_prefetch is False
    assert "777" in str(d.sql("show vmem_protect_limit_mb"))
    # listing marks persisted values
    capsys.readouterr()
    cli.main(["config", "-d", path])
    out = capsys.readouterr().out
    assert "vmem_protect_limit_mb            777 (persisted)" in out
    # unknown names are rejected at write time
    import pytest as _pytest
    with _pytest.raises(ValueError):
        cli.main(["config", "-d", path, "-c", "no_such_guc", "-v", "1"])
    d.close()


def test_settings_adoption_failures_surface(devices8, tmp_path, capsys):
    """A persisted GUC this build can't adopt (operator typo, version skew)
    must surface as a warning in `gg state` and the cluster log — never a
    silent divergence (guc.c validation analog)."""
    import json

    import greengage_tpu
    from greengage_tpu.mgmt import cli

    path = str(tmp_path / "c")
    greengage_tpu.connect(path=path, numsegments=2).close()
    with open(os.path.join(path, "settings.json"), "w") as f:
        json.dump({"vmem_protect_limit_mb": 512, "no_such_guc": 1}, f)
    d = greengage_tpu.connect(path=path, numsegments=2)
    assert d.settings.vmem_protect_limit_mb == 512   # good one adopted
    assert any("no_such_guc" in w for w in d.settings_warnings)
    d.close()
    capsys.readouterr()
    cli.main(["state", "-d", path])
    out = capsys.readouterr().out
    assert "WARNING" in out and "no_such_guc" in out
    # and it reached the cluster log for logfilter forensics
    logdir = os.path.join(path, "log")
    blob = "".join(open(os.path.join(logdir, p)).read()
                   for p in os.listdir(logdir))
    assert "no_such_guc" in blob


# ---------------------------------------------------------------------------
# gg check --list (ISSUE 14: the check catalog with per-check counts — the
# tier-1 log's receipt of what ran; the analyzers' behavior matrix lives in
# test_analysis.py, this keeps the COMMAND itself wired)
# ---------------------------------------------------------------------------

def test_check_list_smoke(capsys):
    assert run_cli("check", "--list") == 0
    out = capsys.readouterr().out
    for name in ("locks", "interrupts", "tracer", "registry", "imports",
                 "threads", "races"):
        assert name in out, out
    assert "finding(s)" in out


# ---------------------------------------------------------------------------
# gg checkperf --feedback (the self-tuning loop's operator surface; the
# calibration behavior matrix lives in test_feedback.py — this keeps the
# COMMAND and the server frame wired)
# ---------------------------------------------------------------------------

def test_checkperf_feedback_report_and_reset(clu, tmp_path, capsys):
    db = greengage_tpu.connect(path=clu)
    db.sql("create table cp (a int, b int) distributed by (a)")
    db.sql("insert into cp values " +
           ",".join(f"({i},{i % 7})" for i in range(500)))
    db.sql("select count(*) from cp where b >= 0")   # 3x-wrong estimate
    db.sql("select count(*) from cp where b >= 0")
    db.close()
    assert run_cli("checkperf", "-d", clu, "--feedback") == 0
    out = capsys.readouterr().out
    assert "self-tuning: calibration generation" in out
    assert "applied row scales" in out               # the promotion shows
    assert "rows err%" in out
    # --apply is a no-op when nothing is pending, but must be wired
    assert run_cli("checkperf", "-d", clu, "--feedback", "--apply") == 0
    assert "applied 0 pending correction(s)" in capsys.readouterr().out
    # --reset clears the store
    assert run_cli("checkperf", "-d", clu, "--reset") == 0
    assert "feedback store cleared" in capsys.readouterr().out
    assert run_cli("checkperf", "-d", clu, "--feedback") == 0
    assert "0 digest(s) tracked" in capsys.readouterr().out


def test_checkperf_server_frame(clu, tmp_path):
    from greengage_tpu.runtime.server import SqlClient, SqlServer

    db = greengage_tpu.connect(path=clu)
    db.sql("create table cp (a int, b int) distributed by (a)")
    db.sql("insert into cp values " +
           ",".join(f"({i},{i % 7})" for i in range(500)))
    db.sql("select count(*) from cp where b >= 0")
    sock = str(tmp_path / "cp.sock")
    srv = SqlServer(db, sock)
    srv.start()
    try:
        c = SqlClient(sock)
        try:
            st = c.op({"op": "checkperf"})
            assert st["ok"]
            assert st["feedback"]["gen"] >= 1
            assert st["feedback"]["shapes"]
            ap = c.op({"op": "checkperf", "apply": True})
            assert ap["ok"] and ap["applied"] == 0
            rs = c.op({"op": "checkperf", "reset": True})
            assert rs["ok"] and rs["reset"] is True
            assert c.op({"op": "checkperf"})["feedback"]["digests"] == 0
        finally:
            c.close()
    finally:
        srv.stop()
