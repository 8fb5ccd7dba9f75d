"""gg — the cluster management CLI (gpMgmt/bin analog).

Subcommands mirror the reference's operator tools:

  gg init     -d DIR -n NSEG      gpinitsystem: create a cluster
  gg state    -d DIR [--probe]    gpstate: topology + table inventory
  gg sql      -d DIR "SELECT..."  psql: run statements, print results
  gg expand   -d DIR -n NEWN      gpexpand: widen + redistribute
  gg recover  -d DIR              gprecoverseg: roll back in-doubt 2PC,
                                  rebalance roles to preferred
  gg checkcat -d DIR              gpcheckcat: catalog/storage consistency
  gg check [--plans] [--json]     static-analysis gate (docs/ANALYSIS.md)

Run as: python -m greengage_tpu.mgmt.cli <cmd> ...
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import signal
import sys
import tarfile
import tempfile
import threading
import time


def _open(path, numsegments=None):
    import greengage_tpu

    return greengage_tpu.connect(path=path, numsegments=numsegments)


def cmd_init(args):
    if os.path.exists(os.path.join(args.dir, "catalog.json")):
        print(f"error: cluster already exists at {args.dir}", file=sys.stderr)
        return 1
    import greengage_tpu

    db = greengage_tpu.connect(path=args.dir, numsegments=args.numsegments,
                               mirrors=getattr(args, "mirrors", False))
    print(f"cluster initialized at {args.dir}: {db.numsegments} segments "
          f"on {len(list(db.mesh.devices.flat))} devices"
          + (" with mirrors" if getattr(args, "mirrors", False) else ""))
    return 0


def cmd_mirrorroots(args):
    """Cross-host mirror placement (gpaddmirrors spread analog): place
    content k's mirror tree under roots[(k+1) % n] — offset so a content
    never mirrors onto its own root when roots are per-host mounts — and
    move any already-replicated trees there."""

    from greengage_tpu.storage.table_store import mirror_root

    db = _open(args.dir)
    if db.replicator is None:
        print("cluster has no mirrors (re-init with --mirrors)",
              file=sys.stderr)
        db.close()
        return 1
    roots = [os.path.abspath(r) for r in args.roots.split(",") if r]
    if not roots:
        raise ValueError("--roots needs at least one directory")
    nseg = db.numsegments
    old = {k: mirror_root(db.path, k) for k in range(nseg)}
    mapping = {str(k): roots[(k + 1) % len(roots)] for k in range(nseg)}
    mp = os.path.join(db.path, "mirror_roots.json")
    with open(mp + ".tmp", "w") as f:
        json.dump(mapping, f, indent=1)
    os.replace(mp + ".tmp", mp)
    for k in range(nseg):
        new = os.path.join(mapping[str(k)], f"content{k}")
        if os.path.abspath(old[k]) != os.path.abspath(new) \
                and os.path.isdir(old[k]):
            os.makedirs(os.path.dirname(new), exist_ok=True)
            if os.path.exists(new):
                shutil.rmtree(new)
            shutil.move(old[k], new)
        print(f"  content {k}: mirror tree at {new}")
    db.replicator.sync()
    db.catalog._save()
    print("mirrors re-synced at the new roots")
    db.close()
    return 0


def cmd_mapreduce(args):
    """gpmapreduce analog: run a YAML MAP/REDUCE job (mgmt/mapreduce.py)."""
    from greengage_tpu.mgmt.mapreduce import run_job

    db = _open(args.dir)
    with open(args.file) as f:
        run_job(db, f.read())
    db.close()
    return 0


def cmd_config(args):
    """gpconfig analog: show or persist cluster-level settings
    (settings.json, adopted by every connect on every process)."""

    sp = os.path.join(args.dir, "settings.json")
    vals = {}
    if os.path.exists(sp):
        with open(sp) as f:
            vals = json.load(f)
    if args.change is None:
        from greengage_tpu.config import Settings

        base = Settings()
        for k, v in vals.items():
            try:
                base.set(k, v)
            except ValueError:
                pass
        for k in sorted(vars(base)):
            if k.startswith("_"):
                continue
            mark = " (persisted)" if k in vals else ""
            print(f"{k:<32} {getattr(base, k)}{mark}")
        return 0
    if args.value is None:   # --remove
        vals.pop(args.change, None)
        what = f"removed {args.change}"
    else:
        from greengage_tpu.config import Settings

        Settings().set(args.change, args.value)   # validate name + coercion
        vals[args.change] = args.value
        what = f"{args.change} = {args.value}"
    tmp = sp + ".tmp"
    with open(tmp, "w") as f:
        json.dump(vals, f, indent=1)
    os.replace(tmp, sp)
    print(f"config: {what} (takes effect at next connect/restart)")
    return 0


def cmd_initstandby(args):
    """gpinitstandby analog: seed a standby coordinator directory and
    register it for continuous post-commit sync."""
    from greengage_tpu.runtime import standby

    marker = standby.init_standby(args.dir, args.standby)
    print(f"standby initialized at {args.standby} "
          f"(synced to manifest v{marker['synced_version']})")
    return 0


def cmd_activatestandby(args):
    """gpactivatestandby analog: promote the standby's metadata copy to a
    servable cluster directory, linked to the surviving data trees."""
    from greengage_tpu.runtime import standby

    st = standby.activate(args.standby, args.data)
    print(f"standby activated (manifest v{st.get('synced_version', '?')}); "
          f"connect to {args.standby}")
    return 0


def cmd_standby(args):
    """Coordinator-failover control plane (docs/ROBUSTNESS.md "Coordinator
    failover"): default prints the standby's sync status and replication
    lag; --watch runs the heartbeat watcher that auto-promotes on primary
    silence; --promote fences the old primary and promotes immediately;
    --unfence clears a fence after a recovered primary has been verified
    (manual escape hatch — never automatic)."""
    from greengage_tpu.runtime import standby

    if args.unfence:
        owner = standby.fenced(args.unfence)
        if owner is None:
            print(f"no fence at {args.unfence}")
            return 0
        standby.clear_fence(args.unfence)
        print(f"fence cleared at {args.unfence} "
              f"(was held by {owner.get('standby', '?')})")
        return 0
    if not args.standby:
        print("error: -s/--standby is required (or --unfence CLUSTER)",
              file=sys.stderr)
        return 1
    if args.promote:
        st = standby.promote(args.standby, args.data, reason="operator")
        promoted = st.get("promoted") or {}
        print(f"standby promoted (manifest v{st.get('synced_version', '?')}, "
              f"topology v{promoted.get('topology_version', '?')}); "
              f"connect to {args.standby}")
        return 0
    if args.watch:
        from greengage_tpu.config import Settings

        s = Settings()
        # cadence GUCs ride the cluster's settings.json (standby copy
        # first, primary's as fallback — they are synced post-commit)
        st0 = standby.status(args.standby)
        for root in (args.standby, st0.get("primary")):
            sp = os.path.join(root, "settings.json") if root else None
            if sp and os.path.exists(sp):
                try:
                    with open(sp) as f:
                        for k, v in json.load(f).items():
                            try:
                                s.set(k, v)
                            except ValueError:
                                pass
                except (OSError, ValueError):
                    pass
                break
        interval = args.interval if args.interval is not None \
            else s.standby_watch_interval_s
        deadline = args.deadline if args.deadline is not None \
            else s.standby_promote_deadline_s
        done = threading.Event()
        w = standby.StandbyWatcher(
            args.standby, interval_s=interval, deadline_s=deadline,
            data_path=args.data, on_promote=lambda st: done.set())
        print(f"watching primary from {args.standby} "
              f"(interval {interval:g}s, promote deadline {deadline:g}s)")
        w.start()
        try:
            while not done.wait(timeout=0.5):
                pass
            print(f"primary silent past {deadline:g}s — standby promoted; "
                  f"connect to {args.standby}")
        except KeyboardInterrupt:
            print("watch stopped")
        finally:
            w.stop()
        return 0
    st = standby.status(args.standby)
    print(f"standby: {args.standby}")
    print(f"  role: {st.get('role', '?')}  synced to manifest "
          f"v{st.get('synced_version', '?')}")
    primary = st.get("primary")
    if primary and st.get("role") == "standby":
        lag = standby.lag(primary)
        age = standby.beat_age(primary)
        beat = "never" if age == float("inf") else f"{age:.1f}s ago"
        print(f"  primary: {primary}  lag: {lag} commit(s)  "
              f"last beat: {beat}")
        owner = standby.fenced(primary)
        if owner is not None:
            print(f"  FENCED by {owner.get('standby', '?')} "
                  f"({owner.get('reason', '?')})")
    return 0


def cmd_replicate(args):
    """gpaddmirrors/manual sync: bring every mirror to the current manifest
    version (normally automatic via the mirror_sync setting)."""
    db = _open(args.dir)
    if db.replicator is None:
        print("cluster has no mirrors (re-init with --mirrors)", file=sys.stderr)
        return 1
    out = db.replicator.sync()
    db.catalog._save()
    for content, v in sorted(out.items()):
        print(f"  content {content}: mirror at version {v}")
    print("replication complete")
    return 0


def cmd_vacuum(args):
    """Compact deletion bitmaps (visimap VACUUM) and reclaim
    unreferenced segment files (rolled-back/stale writers)."""
    db = _open(args.dir)
    compacted = db.vacuum(getattr(args, "table", None))   # reaps GC too
    n = db.store.sweep_orphans(args.grace)
    print(f"vacuum: compacted {len(compacted)} table(s) "
          f"({sum(compacted.values())} live rows), "
          f"removed {n} orphaned files")
    return 0


def cmd_analyze(args):
    """ANALYZE wrapper: refresh planner statistics."""
    db = _open(args.dir)
    db.sql(f"analyze {args.table}" if args.table else "analyze")
    names = [args.table] if args.table else sorted(db.catalog.tables)
    for n in names:
        ts = db.catalog.get(n).stats
        if ts is not None:
            print(f"  {n}: {ts.rows} rows, {len(ts.columns)} columns analyzed")
    return 0


def cmd_analyzedb(args):
    """analyzedb analog: incremental ANALYZE — only tables whose on-disk
    data changed since their last statistics pass (manifest-entry
    fingerprints stand in for analyzedb's mtime/state tracking)."""
    from greengage_tpu.planner.stats import table_fingerprint

    db = _open(args.dir)
    snap = db.store.manifest.snapshot()
    stale, fresh = [], []
    for name in sorted(db.catalog.tables):
        schema = db.catalog.get(name)
        if getattr(schema, "external", None) or \
                db._external_def(schema) is not None:
            continue
        ts = schema.stats
        if (ts is None or not ts.fingerprint
                or ts.fingerprint != table_fingerprint(snap, schema)
                or args.full):
            stale.append(name)
        else:
            fresh.append(name)
    for name in stale:
        db.sql(f"analyze {name}")
        print(f"  analyzed {name}: {db.catalog.get(name).stats.rows} rows")
    for name in fresh:
        print(f"  skipped {name}: statistics are current")
    db.log.info("mgmt", f"analyzedb: {len(stale)} analyzed, "
                f"{len(fresh)} current")
    return 0


def _print_feedback_report(rep: dict) -> None:
    print(f"self-tuning: calibration generation {rep['gen']}, "
          f"{rep['digests']} digest(s) tracked, {rep['pending']} pending")
    if rep.get("scales"):
        print(f"  applied row scales: {rep['scales']}")
    shapes = rep.get("shapes") or []
    if shapes:
        print(f"  {'shape':<18}{'runs':>5} {'rows err%':>10} "
              f"{'bytes err%':>11}  statement")
        for s in sorted(shapes, key=lambda x: -x.get("runs", 0)):
            rerr = s.get("rows_err_pct")
            berr = s.get("bytes_err_pct")
            print(f"  {s['shape']:<18}{s.get('runs', 0):>5} "
                  f"{('%.1f' % rerr) if rerr is not None else '-':>10} "
                  f"{('%.1f' % berr) if berr is not None else '-':>11}  "
                  f"{(s.get('sql') or '')[:60]}")


def cmd_checkperf_feedback(args) -> int:
    """The self-tuning half of `gg checkperf`: per-plan-digest
    est-vs-actual error (rows + bytes), `--apply` commits every pending
    calibration candidate, `--reset` clears the store."""
    db = _open(args.dir)
    try:
        fb = db.feedback
        if getattr(args, "reset", False):
            fb.reset()
            print("feedback store cleared")
            return 0
        if getattr(args, "apply", False) \
                and not getattr(args, "device", False):
            n = fb.apply_pending()
            print(f"applied {n} pending correction(s)")
        _print_feedback_report(fb.report())
        return 0
    finally:
        db.close()


def cmd_checkperf(args):
    """gpcheckperf analog: micro-benchmark the cluster's hardware paths —
    data-dir disk bandwidth, host memory bandwidth, device HBM bandwidth,
    and the mesh collective (ICI) path — plus the self-tuning loop's
    est-vs-actual report (`--feedback` for the report alone)."""

    if getattr(args, "feedback", False) or getattr(args, "reset", False):
        return cmd_checkperf_feedback(args)

    import numpy as np

    mb = args.size_mb
    buf = np.random.default_rng(0).bytes(mb << 20)
    results = {}

    # disk: write + fsync + read in the cluster's data dir
    with tempfile.NamedTemporaryFile(dir=args.dir, suffix=".perf") as f:
        t0 = time.monotonic()
        f.write(buf)
        f.flush()
        os.fsync(f.fileno())
        results["disk_write_MBps"] = mb / (time.monotonic() - t0)
        f.seek(0)
        t0 = time.monotonic()
        while f.read(1 << 22):
            pass
        results["disk_read_MBps"] = mb / (time.monotonic() - t0)

    # host memory bandwidth (memcpy)
    a = np.frombuffer(buf, np.uint8)
    t0 = time.monotonic()
    for _ in range(4):
        b = a.copy()
    results["host_mem_MBps"] = 4 * mb / (time.monotonic() - t0)
    del b

    # device HBM + collective over the mesh
    try:
        import jax
        import jax.numpy as jnp

        x = jnp.asarray(np.frombuffer(buf, np.float32))
        jax.block_until_ready(x)
        t0 = time.monotonic()
        for _ in range(4):
            y = jax.block_until_ready(x * 2.0)
        # read + write per pass
        results["device_hbm_MBps"] = 8 * mb / (time.monotonic() - t0)
        del y
        db = _open(args.dir)
        from jax.sharding import NamedSharding, PartitionSpec

        mesh = db.mesh
        n = mesh.devices.size
        shard = jax.device_put(
            jnp.ones((n, (mb << 18) // n), jnp.float32),
            NamedSharding(mesh, PartitionSpec("seg", None)))
        f2 = jax.jit(jax.shard_map(
            lambda v: jax.lax.psum(v, "seg"), mesh=mesh,
            in_specs=PartitionSpec("seg", None),
            out_specs=PartitionSpec("seg", None)))
        jax.block_until_ready(f2(shard))
        t0 = time.monotonic()
        for _ in range(4):
            jax.block_until_ready(f2(shard))
        results["collective_allreduce_MBps"] = 4 * mb / (time.monotonic() - t0)
    except Exception as e:   # no device available is a report, not a crash
        results["device_error"] = str(e)[:120]

    if getattr(args, "device", False):
        try:
            cal = _measure_device_primitives()
            results.update({f"cal_{k}": v for k, v in cal.items()})
            if getattr(args, "apply", False):
                p = os.path.join(args.dir, "calibration.json")
                with open(p, "w") as f:
                    json.dump(cal, f, indent=1)
                print(f"calibration written to {p}")
        except Exception as e:
            results["calibration_error"] = str(e)[:160]

    print(f"{'path':<28} {'bandwidth':>14}")
    for k, v in results.items():
        if isinstance(v, float):
            if k.startswith("cal_"):
                print(f"{k:<28} {v:>14.6g}")
            else:
                print(f"{k:<28} {v:>11.0f} MB/s")
        else:
            print(f"{k:<28} {v}")
    return 0


def _measure_device_primitives(n: int = 1 << 22) -> dict:
    """Measure the planner cost model's primitives (planner/cost.py
    CALIBRATION_DEFAULTS) on the live backend: random gather, scatter-add,
    two-operand sort, HBM streaming, the device->host fetch, and — with
    more than one device — an all_to_all over every device (on a single
    chip the ICI constant keeps its default)."""

    import numpy as np

    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    idx = jnp.asarray(rng.integers(0, n, n).astype(np.int32))
    val = jnp.asarray(rng.integers(0, 1 << 30, n).astype(np.int32))
    key = jnp.asarray(rng.integers(0, 1 << 30, n).astype(np.int64))

    def best_s(fn, *a, reps=3):
        fn_j = jax.jit(fn)
        jax.block_until_ready(fn_j(*a))   # compile + warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.monotonic()
            jax.block_until_ready(fn_j(*a))
            best = min(best, time.monotonic() - t0)
        return best

    cal = {}
    cal["ns_gather_row"] = best_s(lambda v, i: v[i], val, idx) * 1e9 / n
    cal["ns_scatter_row"] = best_s(
        lambda v, i: jnp.zeros((n,), v.dtype).at[i].add(v), val, idx) \
        * 1e9 / n
    # two operands (key + payload) -> per-operand cost
    from jax import lax

    cal["ns_sort_row"] = best_s(
        lambda k, v: lax.sort((k, v), num_keys=1), key, val) * 1e9 / n / 2
    # one read + one write pass of 8B rows
    cal["ns_stream_byte"] = best_s(lambda k: k * 2, key) * 1e9 / (n * 16)
    # device->host fetch: fixed call floor from a tiny transfer, per-byte
    # from a big one
    small = jnp.ones((8,), jnp.int64)
    t0 = time.monotonic()
    for _ in range(3):
        jax.device_get(small)
    cal["ns_host_call"] = (time.monotonic() - t0) / 3 * 1e9
    t0 = time.monotonic()
    jax.device_get(key)
    big_s = time.monotonic() - t0
    per_byte = (big_s * 1e9 - cal["ns_host_call"]) / (n * 8)
    cal["ns_host_byte"] = max(per_byte, 1e-4)
    devs = jax.devices()
    if len(devs) > 1:
        # redistribute shape: every device sends 1/ndev of its n/ndev
        # int64 rows to each peer; priced per byte a device sends
        from jax.sharding import NamedSharding, PartitionSpec as P

        from greengage_tpu.parallel import make_mesh

        nd = len(devs)
        mesh = make_mesh(nd, devs)
        rows = n // (nd * nd) * nd
        x = jax.device_put(
            jnp.ones((nd * rows,), jnp.int64), NamedSharding(mesh, P("seg")))
        a2a = jax.shard_map(
            lambda v: lax.all_to_all(v.reshape(nd, -1), "seg", 0, 0,
                                     tiled=True).reshape(-1),
            mesh=mesh, in_specs=P("seg"), out_specs=P("seg"))
        cal["ns_ici_byte"] = best_s(a2a, x) * 1e9 / (rows * 8 * (nd - 1) / nd)
    return cal


def cmd_load(args):
    """gpload analog: YAML-driven bulk load. The control file maps onto
    an external table + INSERT SELECT (exactly gpload's own strategy:
    it generates gpfdist external tables under the covers).

    YAML shape (subset of gpload's):
        gpload:
          input:
            source:
              file: [/path/part*.csv]     # or a gpfdist:// URL
            format: csv
            delimiter: ','
            header: true
            error_limit: 50
          output:
            table: sales
            mode: insert | truncate
    """
    import yaml

    with open(args.config) as f:
        doc = yaml.safe_load(f)
    spec = doc.get("gpload", doc)
    inp = spec.get("input", {})
    out = spec.get("output", {})
    if isinstance(inp, list):   # gpload writes sections as 1-elem maps
        inp = {k: v for d in inp for k, v in d.items()}
    if isinstance(out, list):
        out = {k: v for d in out for k, v in d.items()}
    table = out.get("table")
    if not table:
        print("error: output.table is required", file=sys.stderr)
        return 1
    if not re.fullmatch(r"[A-Za-z_][A-Za-z0-9_]*", str(table)):
        print(f"error: output.table {table!r} is not a valid identifier",
              file=sys.stderr)
        return 1
    src = inp.get("source", {})
    if isinstance(src, list):
        src = {k: v for d in src for k, v in d.items()}
    files = src.get("file") or ([src["url"]] if "url" in src else None)
    if isinstance(files, str):
        files = [files]
    if not files:
        print("error: input.source.file (or url) is required", file=sys.stderr)
        return 1

    db = _open(args.dir)
    schema = db.catalog.get(table)
    from greengage_tpu import types as T

    def typ(c):
        k = c.type.kind
        return {T.Kind.INT32: "int", T.Kind.INT64: "bigint",
                T.Kind.FLOAT64: "double precision", T.Kind.BOOL: "bool",
                T.Kind.DATE: "date", T.Kind.TEXT: "text"}.get(
                    k, f"decimal(18,{c.type.scale})")

    def lit(v):
        # YAML-provided values (delimiters, paths) may contain quotes —
        # escape them the SQL way before splicing into a statement
        return "'" + str(v).replace("'", "''") + "'"

    cols = ", ".join(f"{c.name} {typ(c)}" for c in schema.columns)
    ext = f"gpload_ext_{table}"
    urls = ", ".join(
        lit(u if "://" in u else "file://" + os.path.abspath(u))
        for u in files)
    fmt_opts = []
    if inp.get("delimiter"):
        fmt_opts.append(f"delimiter {lit(inp['delimiter'])}")
    if str(inp.get("header", "")).lower() in ("true", "1", "yes"):
        fmt_opts.append("header")
    fmt_name = str(inp.get("format", "csv"))
    if fmt_name not in ("csv", "text"):
        print(f"error: unsupported format {fmt_name!r}", file=sys.stderr)
        return 1
    fmt = f"format '{fmt_name}'"
    if fmt_opts:
        fmt += " (" + " ".join(fmt_opts) + ")"
    reject = ""
    if inp.get("error_limit"):
        reject = f" segment reject limit {int(inp['error_limit'])}"
    db.sql(f"drop table if exists {ext}")
    db.sql(f"create external table {ext} ({cols}) location ({urls}) "
           f"{fmt}{reject}")
    try:
        if out.get("mode", "insert") == "truncate":
            db.sql(f"delete from {table}")
        db.sql(f"insert into {table} select * from {ext}")
        n = db.sql(f"select count(*) from {table}").rows()[0][0]
        print(f"loaded into {table}: now {n} rows")
        db.log.info("mgmt", f"gpload into {table}: {n} rows total")
    finally:
        db.sql(f"drop table if exists {ext}")
    return 0


def cmd_pkg(args):
    """gppkg analog: install/remove/list extension packages for a
    cluster. A package is a directory (or .tar.gz) holding
    ``<name>/__init__.py`` that registers scalar functions via
    greengage_tpu.extensions.register_scalar. Installing copies it under
    <cluster>/extensions/ and makes `CREATE EXTENSION <name>` resolve it
    for THIS cluster only (per-database pg_proc visibility)."""

    ext_root = os.path.join(args.dir, "extensions")
    if args.action in ("install", "remove") and not args.package:
        print(f"error: gg pkg {args.action} requires a package argument",
              file=sys.stderr)
        return 1
    if args.action == "list":
        names = (sorted(os.listdir(ext_root))
                 if os.path.isdir(ext_root) else [])
        db = _open(args.dir)
        created = set(getattr(db.catalog, "extensions", ()))
        for n in names:
            mark = " (created)" if n in created else ""
            print(f"  {n}{mark}")
        print(f"({len(names)} packages)")
        return 0
    if args.action == "remove":
        target = os.path.join(ext_root, args.package)
        if not os.path.isdir(target):
            print(f"error: package {args.package!r} is not installed",
                  file=sys.stderr)
            return 1
        db = _open(args.dir)
        if args.package in getattr(db.catalog, "extensions", ()):
            print(f"error: extension {args.package!r} is still created "
                  "(drop it first)", file=sys.stderr)
            return 1
        shutil.rmtree(target)
        print(f"removed {args.package}")
        return 0
    # install
    src = args.package
    os.makedirs(ext_root, exist_ok=True)
    if src.endswith((".tar.gz", ".tgz", ".tar")):
        with tarfile.open(src) as tf:
            names = [m.name.split("/")[0] for m in tf.getmembers()
                     if m.name and not m.name.startswith((".", "/"))]
            if not names:
                print("error: empty package", file=sys.stderr)
                return 1
            pkg = names[0]
            tf.extractall(ext_root, filter="data")
    else:
        pkg = os.path.basename(src.rstrip("/"))
        dst = os.path.join(ext_root, pkg)
        if os.path.exists(dst):
            shutil.rmtree(dst)
        shutil.copytree(src, dst)
    init = os.path.join(ext_root, pkg, "__init__.py")
    if not os.path.exists(init):
        print(f"error: {pkg}/__init__.py missing — not an extension "
              "package", file=sys.stderr)
        return 1
    print(f"installed {pkg} (enable with: gg sql -d {args.dir} "
          f"\"create extension {pkg}\")")
    return 0


def cmd_state(args):
    from greengage_tpu.runtime.fts import cluster_state, needs_rebalance

    db = _open(args.dir)
    if args.probe:
        results = db.fts.probe_once()
        print("probe:", json.dumps(results))
    print(f"cluster: {args.dir}  width: {db.numsegments}  "
          f"config version: {db.catalog.segments.version}")
    info = _read_pidfile(args.dir)
    if info and _pid_alive(info[0]):
        print(f"server: running (pid {info[0]}, socket {info[1]})")
    else:
        print("server: not running (embedded access only)")
    print(f"{'content':>8} {'role':>5} {'pref':>5} {'status':>7} {'device':>7} {'synced':>7}")
    for row in cluster_state(db.catalog.segments):
        print(f"{row['content']:>8} {row['role']:>5} {row['preferred_role']:>5} "
              f"{row['status']:>7} {str(row['device']):>7} {str(row['synced']):>7}")
    if needs_rebalance(db.catalog.segments):
        print("NOTE: segments are not on their preferred roles (run gg recover)")
    for w in db.settings_warnings:
        print(f"WARNING: {w}")
    print("tables:")
    for name, schema in sorted(db.catalog.tables.items()):
        counts = db.store.segment_rowcounts(name)
        print(f"  {name}: {sum(counts)} rows over {schema.policy.numsegments} segments "
              f"({schema.policy.describe()})")
    return 0


def cmd_worker(args):
    """Multi-host worker process (the segment-host postmaster role): joins
    the distributed device runtime, then follows the coordinator's
    statement channel in lockstep. Requires the cluster directory on a
    shared filesystem. Start workers first, then the coordinator with
    greengage_tpu.connect(..., multihost=init_multihost(...))."""
    from greengage_tpu.parallel.multihost import init_multihost, worker_loop

    mh = init_multihost(args.coordinator, args.num_processes,
                        args.process_id, args.control_port,
                        distributed=not getattr(args, "no_distributed", False))
    import greengage_tpu

    # multihost must flow through connect(): the worker guard skips the
    # startup writes (catalog save / manifest recovery) that would race
    # the coordinator's in-flight transactions
    db = greengage_tpu.connect(path=args.dir, multihost=mh)
    print(f"worker {args.process_id}/{args.num_processes} serving "
          f"{len(__import__('jax').local_devices())} local devices", flush=True)
    worker_loop(db)
    return 0


def cmd_useradd(args):
    """createuser analog: add/update a remote user in gg_hba.json (salted
    sha256 at rest, file mode 0600)."""
    from greengage_tpu.runtime import auth

    auth.add_user(args.dir, args.user, args.password)
    print(f"user {args.user!r} ready for TCP connections")
    return 0


def cmd_server(args):
    """gpstart-style serving mode: listen on a unix socket (and, with
    --host/--port, on TCP with gg_hba.json authentication) until
    killed."""
    from greengage_tpu.runtime.server import SqlServer

    host = getattr(args, "host", None)
    port = getattr(args, "port", None)
    if (host is None) != (port is None):
        print("error: --host and --port must be given together",
              file=sys.stderr)
        return 1
    db = _open(args.dir)
    srv = SqlServer(db, args.socket, host=host, port=port)
    srv.start()
    where = args.socket + (
        f" and {host}:{srv.port}" if srv._tcp_server is not None else "")
    print(f"serving {args.dir} on {where} (ctrl-c to stop)")

    try:
        if hasattr(signal, "pause"):
            signal.pause()
        else:
            # platforms without signal.pause: sleep-wait for the ctrl-c
            # (the old blanket AttributeError handler silently swallowed
            # REAL AttributeError bugs from anywhere in the wait path)
            while True:
                time.sleep(3600)
    except KeyboardInterrupt:
        # flag every in-flight statement before tearing the listener
        # down, so blocked connections die with a typed cause instead of
        # a connection reset
        from greengage_tpu.runtime.interrupt import REGISTRY

        n = REGISTRY.cancel_all("shutdown")
        if n:
            print(f"cancelled {n} in-flight statement(s)")
    finally:
        srv.stop()
    return 0


def _pidfile(dirpath: str) -> str:
    return os.path.join(dirpath, "server.pid")


def _read_pidfile(dirpath: str):
    """-> (pid, socket_path) or None."""
    try:
        with open(_pidfile(dirpath)) as f:
            pid_s, sock = f.read().splitlines()[:2]
        return int(pid_s), sock
    except (OSError, ValueError, IndexError):
        return None


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False
    except PermissionError:
        return True


def cmd_start(args):
    """gpstart analog: daemonize a serving postmaster for the cluster.

    Double-fork detach; the child writes <dir>/server.pid (pid + socket,
    the postmaster.pid analog) and serves until `gg stop`. stdout/stderr
    go to <dir>/log/server.out.
    """
    info = _read_pidfile(args.dir)
    if info and _pid_alive(info[0]):
        print(f"error: server already running (pid {info[0]})",
              file=sys.stderr)
        return 1
    sock = args.socket or os.path.join(args.dir, ".gg.sock")
    pid = os.fork()
    if pid:
        # parent: reap the intermediate child (it exits at once in the
        # double fork), then poll the pidfile until the daemon confirms

        os.waitpid(pid, 0)
        for _ in range(1200):   # jax import + device init can take ~30s
            info = _read_pidfile(args.dir)
            if info and _pid_alive(info[0]):
                print(f"server started (pid {info[0]}, socket {info[1]})")
                return 0
            time.sleep(0.05)
        print("error: server failed to start (see log/server.out)",
              file=sys.stderr)
        return 1
    # child: become the daemon
    os.setsid()
    if os.fork():
        os._exit(0)
    os.makedirs(os.path.join(args.dir, "log"), exist_ok=True)
    out = open(os.path.join(args.dir, "log", "server.out"), "a")
    os.dup2(out.fileno(), 1)
    os.dup2(out.fileno(), 2)
    from greengage_tpu.runtime.server import SqlServer

    db = _open(args.dir)
    srv = SqlServer(db, sock)
    srv.start()
    with open(_pidfile(args.dir), "w") as f:
        f.write(f"{os.getpid()}\n{sock}\n")
    db.log.info("lifecycle", f"server started on {sock}")

    # sigwait avoids the check-then-pause lost-wakeup race: the signal is
    # blocked until we are actually waiting for it
    signal.pthread_sigmask(signal.SIG_BLOCK,
                           {signal.SIGTERM, signal.SIGINT})
    signal.sigwait({signal.SIGTERM, signal.SIGINT})
    db.log.info("lifecycle", "server stopping (signal)")
    srv.stop()
    try:
        os.remove(_pidfile(args.dir))
    except OSError:
        pass
    os._exit(0)


def cmd_stop(args):
    """gpstop analog. -m smart/fast: SIGTERM + wait; -m immediate:
    SIGKILL."""

    info = _read_pidfile(args.dir)
    if not info or not _pid_alive(info[0]):
        print("server is not running")
        try:
            os.remove(_pidfile(args.dir))
        except OSError:
            pass
        return 0
    pid, _sock = info
    os.kill(pid, signal.SIGKILL if args.mode == "immediate"
            else signal.SIGTERM)
    for _ in range(int(args.timeout / 0.05)):
        if not _pid_alive(pid):
            print(f"server stopped (pid {pid})")
            try:
                os.remove(_pidfile(args.dir))
            except OSError:
                pass
            return 0
        time.sleep(0.05)
    print(f"error: server (pid {pid}) did not exit in {args.timeout}s "
          "(try -m immediate)", file=sys.stderr)
    return 1


def cmd_logfilter(args):
    """gplogfilter analog: mine the cluster's CSV logs."""
    from greengage_tpu.runtime.logger import filter_entries, read_entries

    entries = filter_entries(
        read_entries(args.dir), trouble=args.trouble, match=args.match,
        begin=args.begin, end=args.end,
        min_duration_ms=args.min_duration)
    if args.tail:
        entries = entries[-args.tail:]
    for e in entries:
        dur = f" ({e['duration_ms']}ms)" if e["duration_ms"] else ""
        rows = f" rows={e['rows']}" if e["rows"] else ""
        print(f"{e['ts']} {e['severity']:>7} [{e['kind']}]{dur}{rows} "
              f"{e['message']}")
    print(f"({len(entries)} entries)", file=sys.stderr)
    return 0


def cmd_sql(args):
    if not getattr(args, "socket", None) and not args.dir:
        print("error: sql requires -d DIR (embedded) or -s SOCKET (server)",
              file=sys.stderr)
        return 1
    if getattr(args, "socket", None):
        from greengage_tpu.runtime.server import SqlClient

        c = SqlClient(args.socket)
        resp = c.sql(args.query)
        if resp.get("tag") is not None:
            print(resp["tag"])
        elif resp.get("columns") is not None:
            print("\t".join(resp["columns"]))
            for row in resp["rows"]:
                print("\t".join("" if v is None else str(v) for v in row))
            print(f"({len(resp['rows'])} rows)")
        c.close()
        return 0
    db = _open(args.dir)
    out = db.sql(args.query)
    if isinstance(out, str):
        print(out)
        return 0
    if hasattr(out, "columns"):
        print("\t".join(out.columns))
        for row in out.rows():
            print("\t".join("" if v is None else str(v) for v in row))
        print(f"({len(out)} rows)")
    return 0


def _activity_socket(args):
    """Resolve the serving socket for ps/cancel: explicit -s, or the
    running daemon's server.pid in -d DIR (the postmaster.pid analog)."""
    if getattr(args, "socket", None):
        return args.socket
    if getattr(args, "dir", None):
        info = _read_pidfile(args.dir)
        if info and _pid_alive(info[0]):
            return info[1]
    return None


def cmd_ps(args):
    """pg_stat_activity analog: in-flight statements of a running server
    (id, elapsed, cancel state, sql) for `gg cancel` to target."""
    from greengage_tpu.runtime.server import SqlClient

    sock = _activity_socket(args)
    if sock is None:
        print("error: ps needs -s SOCKET or -d DIR with a running server",
              file=sys.stderr)
        return 1
    c = SqlClient(sock)
    try:
        resp = c.op({"op": "ps"})
    finally:
        c.close()
    rows = resp.get("rows") or []
    cl = resp.get("cluster") or {}
    pipe = resp.get("pipeline") or {}
    if cl:
        gang = ""
        if cl.get("expected_workers") is not None:
            gang = (f"  workers: {cl.get('active_workers')}/"
                    f"{cl.get('expected_workers')}")
        # serving-pipeline depths (vectorized serving + staging pool):
        # a persistent backlog here means the device or scan_threads is
        # the bottleneck, not planning
        pq = ""
        if pipe:
            pq = (f"  pipeline: batch-window "
                  f"{pipe.get('batch_admission_depth', 0)}"
                  f" in-flight {pipe.get('batch_inflight', 0)}"
                  f" stage-pool {pipe.get('staging_pool_queue_depth', 0)}")
        print(f"cluster: {cl.get('state', '?')}  "
              f"topology v{cl.get('topology_version', '?')}{gang}{pq}")
        # standby replication health (docs/ROBUSTNESS.md "Coordinator
        # failover"): a growing lag means promotion would lose commits
        sb = cl.get("standby") or {}
        if sb:
            print(f"standby: {sb.get('path', '?')}  "
                  f"lag {sb.get('lag_commits', '?')} commit(s)  "
                  f"sync failures {sb.get('sync_fail_total', 0)}")
    # overload state (docs/ROBUSTNESS.md "Overload protection"): a
    # browned-out engine is serving degraded on purpose — say so before
    # anyone reads the statement list as a performance bug
    ov = resp.get("overload") or {}
    if ov.get("brownout"):
        print(f"overload: BROWNOUT ({ov.get('since_s', 0):.0f}s) — "
              f"{ov.get('reason')}; block-cache x"
              f"{ov.get('cache_factor')}, batch serving disabled")
    # open ingest streams (streaming COPY plane): buffered rows are
    # volatile until the next micro-batch commit; committed_seq is the
    # durable resume watermark
    for s in resp.get("ingest") or []:
        state = "error" if s.get("error") else (
            "closed" if s.get("closed") else "open")
        print(f"stream: {s['stream']} -> {s['table']}  {state}  "
              f"buffered {s['buffered_rows']}  acked {s['acked_seq']}  "
              f"committed {s['committed_seq']}")
    print(f"{'ID':>6} {'ELAPSED_S':>10} {'STATE':>12} {'BATCH':>6} "
          f"{'SPAN':>22} SQL")
    for r in rows:
        state = f"cancel:{r['cancelled']}" if r.get("cancelled") else "active"
        # current execution phase (trace registry): span name + how long
        # the statement has been inside it — stage vs device vs queue at
        # a glance, the pg_stat_activity wait_event analog
        span = "-"
        if r.get("span"):
            span = f"{r['span']} {r.get('span_ms', 0):.0f}ms"
        # member-of-batch id (vectorized serving): statements riding one
        # admission window share a BATCH id — one device dispatch
        batch = str(r["batch"]) if r.get("batch") is not None else "-"
        print(f"{r['id']:>6} {r['elapsed_s']:>10.3f} {state:>12} "
              f"{batch:>6} {span:>22} {r['sql']}")
    print(f"({len(rows)} statements)", file=sys.stderr)
    return 0


def cmd_trace(args):
    """Chrome trace_event export of one statement's trace (the gpperfmon
    query-detail analog): `gg trace <id>` (or the newest trace with no
    id) from a running server's bounded trace ring; load the JSON in
    chrome://tracing or Perfetto."""
    from greengage_tpu.runtime.server import SqlClient

    sock = _activity_socket(args)
    if sock is None:
        print("error: trace needs -s SOCKET or -d DIR with a running "
              "server", file=sys.stderr)
        return 1
    c = SqlClient(sock)
    try:
        req = {"op": "trace"}
        if args.id is not None:
            req["id"] = args.id
        resp = c.op(req)
    finally:
        c.close()
    if not resp.get("ok"):
        print(f"error: {resp.get('error')}", file=sys.stderr)
        return 1
    out = json.dumps(resp["trace"], indent=1)
    if getattr(args, "out", None):
        with open(args.out, "w") as f:
            f.write(out)
        print(f"trace written to {args.out}", file=sys.stderr)
    else:
        print(out)
    return 0


def cmd_metrics(args):
    """Prometheus text exposition of the cluster's counters, gauges and
    latency histograms (the gpperfmon/pg_stat export surface): scrape
    with any Prometheus agent via `gg metrics`, or eyeball directly."""
    from greengage_tpu.runtime.server import SqlClient

    sock = _activity_socket(args)
    if sock is None:
        print("error: metrics needs -s SOCKET or -d DIR with a running "
              "server", file=sys.stderr)
        return 1
    c = SqlClient(sock)
    try:
        resp = c.op({"op": "metrics"})
    finally:
        c.close()
    if not resp.get("ok"):
        print(f"error: {resp.get('error')}", file=sys.stderr)
        return 1
    sys.stdout.write(resp["text"])
    return 0


def cmd_mem(args):
    """Measured memory accounting surface (`gg mem`, the gp_toolkit vmem
    views analog): live device allocator stats, per-statement owner
    trees (in-flight + recent), the runaway ledger, block-cache budget
    state, and each cached executable's measured footprint."""
    from greengage_tpu.runtime.server import SqlClient

    sock = _activity_socket(args)
    if sock is None:
        print("error: mem needs -s SOCKET or -d DIR with a running server",
              file=sys.stderr)
        return 1
    c = SqlClient(sock)
    try:
        resp = c.op({"op": "mem"})
    finally:
        c.close()
    if not resp.get("ok"):
        print(f"error: {resp.get('error')}", file=sys.stderr)
        return 1
    mem = resp.get("mem") or {}
    if getattr(args, "as_json", False):
        print(json.dumps(mem, indent=1))
        return 0
    dev = mem.get("device")
    if dev:
        print(f"device: {dev.get('bytes_in_use', 0) / 1e6:.1f} MB in use, "
              f"peak {dev.get('peak_bytes_in_use', 0) / 1e6:.1f} MB")
    else:
        print("device: no allocator stats (CPU backend)")
    proc = mem.get("process") or {}
    print(f"host: rss {proc.get('host_rss_bytes', 0) / 1e6:.1f} MB, "
          f"{proc.get('host_open_fds', '?')} fds, staging queue depth "
          f"{proc.get('staging_pool_queue_depth', 0)}")
    bc = mem.get("block_cache") or {}
    if bc:
        print(f"block cache: {bc.get('total_bytes', 0) / 1e6:.1f} / "
              f"{bc.get('limit_bytes', 0) / 1e6:.0f} MB")
    for snap in (mem.get("in_flight") or []):
        owners = ", ".join(
            f"{o}={v['bytes'] / 1e6:.1f}MB"
            for o, v in (snap.get("owners") or {}).items())
        print(f"stmt {snap.get('statement_id')}: "
              f"{snap.get('total_bytes', 0) / 1e6:.1f} MB in flight "
              f"[{owners}] {snap.get('sql', '')[:60]}")
    exes = mem.get("executables") or []
    meas = [x for x in exes if x.get("measured")]
    print(f"({len(mem.get('in_flight') or [])} in-flight statements, "
          f"{len(exes)} cached executables, {len(meas)} measured)",
          file=sys.stderr)
    return 0


def cmd_cancel(args):
    """pg_cancel_backend analog: flag one in-flight statement; it dies at
    its next cancellation point with cause 'user'."""
    from greengage_tpu.runtime.server import SqlClient

    sock = _activity_socket(args)
    if sock is None:
        print("error: cancel needs -s SOCKET or -d DIR with a running "
              "server", file=sys.stderr)
        return 1
    c = SqlClient(sock)
    try:
        resp = c.op({"op": "cancel", "id": args.id})
    finally:
        c.close()
    if resp.get("ok"):
        print(f"statement {args.id} cancelled")
        return 0
    print(f"error: {resp.get('error')}", file=sys.stderr)
    return 1


def cmd_expand(args):
    db = _open(args.dir)
    moved = db.expand(args.numsegments)
    for t, n in moved.items():
        print(f"  {t}: {n} rows redistributed")
    print(f"cluster expanded to {args.numsegments} segments")
    return 0


def cmd_recover(args):
    from greengage_tpu.catalog.segments import SegmentRole

    db = _open(args.dir)
    rolled = db.store.manifest.recover()
    if rolled:
        print(f"rolled back in-doubt transactions: versions {rolled}")
    swept = db.store.sweep_orphans()
    if swept:
        print(f"reclaimed {swept} orphaned segment files")
    cfg = db.catalog.segments
    # full recovery (gprecoverseg -F / buildMirrorSegments full rebuild):
    # any content served by a promoted mirror gets its original primary
    # tree rebuilt from the mirror's files before roles swap back
    if db.replicator is not None:
        for content in range(cfg.numsegments):
            acting = cfg.acting_primary(content)
            if acting is not None and acting.preferred_role is SegmentRole.MIRROR:
                copied = db.replicator.rebuild(content)
                print(f"  content {content}: rebuilt primary from mirror "
                      f"({copied} files)")
    # rebalance: put segments back on preferred roles (gprecoverseg -r)
    changed = 0
    for e in cfg.entries:
        if e.role is not e.preferred_role:
            # restore the device binding along with the role
            e.role = e.preferred_role
            changed += 1
    if changed:
        for e in cfg.entries:
            if e.content >= 0:
                if e.role is SegmentRole.PRIMARY:
                    e.device_index = e.content
                    e.status = type(e.status)("u")
                else:
                    e.device_index = None
        cfg.version += 1
        print(f"rebalanced {changed} segments to preferred roles")
    db.catalog._save()
    print("recovery complete")
    return 0


def cmd_archive(args):
    """Continuous-archiving catch-up (archive_command analog): ship the
    current committed version to the archive. Per-commit archiving is a
    session GUC (SET archive_mode TO on; SET archive_dir TO '...')."""
    from greengage_tpu.storage.archive import Archive

    db = _open(args.dir)
    a = Archive(args.archive)
    v = a.archive_now(args.dir, db.store)
    if v is None:
        print(f"version {db.store.manifest.snapshot().get('version', 0)} "
              "already archived")
    else:
        print(f"archived version {v} to {args.archive}")
        db.log.info("archive", f"manual archive of v{v} to {args.archive}")
    vs = a.versions()
    print(f"archive holds {len(vs)} versions "
          f"(v{vs[0][0]}..v{vs[-1][0]})" if vs else "archive is empty")
    return 0


def cmd_restore_pitr(args):
    """PITR: rebuild a cluster directory at an archived version or the
    newest version at/before a timestamp (recovery_target_time)."""
    from greengage_tpu.storage.archive import Archive

    a = Archive(args.archive)
    try:
        v = a.restore(args.dir, version=args.version, time=args.time)
    except (ValueError, OSError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    print(f"restored version {v} into {args.dir}")
    vs = dict(a.versions())
    print(f"recovery target: v{v} (archived {vs.get(v)})")
    return 0


def cmd_backup(args):
    """Full backup (gp_pitr/pg_basebackup analog). The manifest snapshot
    names one committed version's files; DELETE/UPDATE/expand may GC old
    files concurrently, so a vanished file triggers a re-snapshot retry
    until one version copies completely."""

    db = _open(args.dir)
    last_err = None
    for _ in range(5):
        snap = db.store.manifest.snapshot()
        try:
            os.makedirs(args.out, exist_ok=True)
            shutil.copy(os.path.join(args.dir, "catalog.json"),
                        os.path.join(args.out, "catalog.json"))
            copied = 0
            for tname, tmeta in snap["tables"].items():
                src_base = os.path.join(args.dir, "data", tname)
                dst_base = os.path.join(args.out, "data", tname)
                if os.path.isdir(src_base):
                    for fn in os.listdir(src_base):
                        if fn.startswith("dict_"):
                            os.makedirs(dst_base, exist_ok=True)
                            shutil.copy(os.path.join(src_base, fn),
                                        os.path.join(dst_base, fn))
                for files in tmeta["segfiles"].values():
                    for rel in files:
                        dst = os.path.join(dst_base, rel)
                        os.makedirs(os.path.dirname(dst), exist_ok=True)
                        shutil.copy(os.path.join(src_base, rel), dst)
                        copied += 1
            # manifest written LAST: its presence marks a complete image
            with open(os.path.join(args.out, "manifest.json"), "w") as f:
                json.dump(snap, f, indent=1)
            print(f"backup of version {snap['version']} written to {args.out} "
                  f"({copied} segment files)")
            return 0
        except FileNotFoundError as e:
            last_err = e   # concurrent writer GC'd a file: retry fresh
    print(f"error: backup could not converge ({last_err})", file=sys.stderr)
    return 1


def cmd_restore(args):
    if os.path.exists(os.path.join(args.dir, "catalog.json")):
        print(f"error: {args.dir} already contains a cluster", file=sys.stderr)
        return 1
    shutil.copytree(args.backup, args.dir, dirs_exist_ok=True)
    db = _open(args.dir)
    print(f"restored cluster at {args.dir}: width {db.numsegments}, "
          f"{len(db.catalog.tables)} tables, manifest version "
          f"{db.store.manifest.snapshot()['version']}")
    return 0


def cmd_scrub(args):
    """Storage scrub (AO verify_block_checksums + gprecoverseg repair
    analog): verify the footer and every frame checksum of every
    manifest-referenced block file; repair corrupt/missing files from the
    in-sync standby tree or quarantine them (storage/scrub.py)."""
    from greengage_tpu.storage.scrub import Scrubber

    db = _open(args.dir)
    try:
        # (Scrubber.scrub logs the summary through the cluster log)
        rep = Scrubber(db.store, repair=not args.no_repair).scrub(
            tables=[args.table] if args.table else None,
            mirrors=args.mirrors)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    # in-doubt write intents ride the scrub sweep (same grace-GC
    # discipline as stale delta claims). _open's startup recover()
    # already swept crash leftovers, so report the process-wide
    # manifest_intent_swept_total rather than just this late sweep.
    from greengage_tpu.runtime.logger import counters

    db.store.manifest.sweep_intents()
    rep["intents_swept"] = int(counters.get("manifest_intent_swept_total"))
    if args.json:
        print(json.dumps(rep, indent=1))
    else:
        print(f"scanned     {rep['files_scanned']} files "
              f"({rep['bytes_scanned']} bytes)")
        print(f"verified    {rep['files_verified']}")
        print(f"repaired    {rep['files_repaired']}")
        print(f"quarantined {rep['files_quarantined']}")
        if rep["files_corrupt"]:
            print(f"corrupt     {rep['files_corrupt']} (--no-repair)")
        if rep["files_missing"]:
            print(f"missing     {rep['files_missing']}")
        if rep["intents_swept"]:
            print(f"intents     {rep['intents_swept']} in-doubt write "
                  "intents swept")
        if args.mirrors:
            print(f"standby     {rep['standby_verified']} verified, "
                  f"{rep['standby_repaired']} repaired")
        for p in rep["problems"]:
            print(f"  {p.get('status', '?'):<12} {p.get('table')}/"
                  f"{p.get('relpath')} [{p.get('cause', '?')}]")
    bad = (rep["files_quarantined"] + rep["files_missing"]
           + rep["files_corrupt"]
           + sum(1 for p in rep["problems"]
                 if str(p.get("status", "")).startswith(
                     ("standby_corrupt", "standby_refresh"))))
    return 1 if bad else 0


def cmd_check(args):
    """gg check: the static-analysis gate (docs/ANALYSIS.md) — codebase
    lints always; the TPC-H/TPC-DS plan-corpus sweep under --plans;
    --list prints the check catalog with per-check finding counts (the
    tier-1 log's what-ran receipt)."""
    from greengage_tpu.analysis.runner import (CHECKS, DESCRIPTIONS,
                                               run_checks, run_plan_corpus)

    if args.list:
        from greengage_tpu.analysis import astutil
        from greengage_tpu.analysis.report import load_baseline

        names = args.checks or sorted(CHECKS)
        for name in names:
            if name not in CHECKS:
                raise ValueError(f"unknown check {name!r} "
                                 f"(have: {', '.join(sorted(CHECKS))})")
        # one shared parsed view of the package for every row (the
        # run_checks design), not a re-parse per check
        sources = astutil.SourceSet(exclude=("greengage_tpu/analysis/",))
        baseline = (None if args.no_baseline
                    else load_baseline(args.baseline))
        rows = []
        for name in names:
            rep = CHECKS[name](sources)
            if baseline is not None:
                rep = rep.suppressed(baseline)
            rows.append({"check": name,
                         "description": DESCRIPTIONS.get(name, ""),
                         "findings": len(rep.findings),
                         "notes": rep.notes})
        if args.json:
            print(json.dumps({"checks": rows}, indent=1, sort_keys=True))
        else:
            width = max(len(r["check"]) for r in rows)
            for r in rows:
                print(f"{r['check']:<{width}}  {r['findings']:>3} "
                      f"finding(s)  {r['description']}")
        return 1 if any(r["findings"] for r in rows) else 0

    report = run_checks(names=args.checks or None,
                        baseline_file=args.baseline,
                        use_baseline=not args.no_baseline)
    if args.plans:
        report.extend(run_plan_corpus(numsegments=args.nseg))
    if args.json:
        print(report.to_json())
    else:
        print(report.to_text())
    return 1 if report.findings else 0


def cmd_checkcat(args):
    db = _open(args.dir)
    problems = []
    snap = db.store.manifest.snapshot()
    # orphaned manifest entries (table gone from catalog)
    for t in snap["tables"]:
        if t not in db.catalog:
            problems.append(f"manifest table {t} missing from catalog")
    for name, schema in db.catalog.tables.items():
        # partitioned parents audit through their child storage tables
        for sname in schema.storage_tables():
            tmeta = snap["tables"].get(sname)
            if tmeta is None:
                continue
            for seg, files in tmeta["segfiles"].items():
                if int(seg) >= schema.policy.numsegments:
                    problems.append(
                        f"{sname}: segfiles on seg {seg} beyond width")
                for rel in files:
                    # resolves through per-content roots (failover aware)
                    p = db.store.seg_file_path(sname, rel)
                    if not os.path.exists(p):
                        problems.append(f"{sname}: missing file {rel}")
            # row counts readable + placement verified per segment
            try:
                total = sum(db.store.segment_rowcounts(sname))
                declared = sum(int(v) for v in tmeta["nrows"].values())
                if total != declared:
                    problems.append(
                        f"{sname}: rowcount mismatch {total} != {declared}")
            except Exception as e:
                problems.append(f"{sname}: unreadable ({e})")
    if problems:
        for p in problems:
            print("PROBLEM:", p)
        return 1
    print("catalog and storage are consistent")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(prog="gg")
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("init")
    p.add_argument("-d", "--dir", required=True)
    p.add_argument("-n", "--numsegments", type=int, default=None)
    p.add_argument("--mirrors", action="store_true")
    p.set_defaults(fn=cmd_init)

    p = sub.add_parser("config")   # gpconfig analog
    p.add_argument("-d", "--dir", required=True)
    p.add_argument("-c", "--change", default=None)
    p.add_argument("-v", "--value", default=None)
    p.set_defaults(fn=cmd_config)

    p = sub.add_parser("mirrorroots")   # gpaddmirrors spread placement
    p.add_argument("-d", "--dir", required=True)
    p.add_argument("--roots", required=True,
                   help="comma-separated per-host mirror root directories")
    p.set_defaults(fn=cmd_mirrorroots)

    p = sub.add_parser("mapreduce")   # gpmapreduce analog
    p.add_argument("-d", "--dir", required=True)
    p.add_argument("-f", "--file", required=True, help="YAML job spec")
    p.set_defaults(fn=cmd_mapreduce)

    p = sub.add_parser("initstandby")   # gpinitstandby analog
    p.add_argument("-d", "--dir", required=True)
    p.add_argument("-s", "--standby", required=True)
    p.set_defaults(fn=cmd_initstandby)

    p = sub.add_parser("activatestandby")   # gpactivatestandby analog
    p.add_argument("-s", "--standby", required=True)
    p.add_argument("--data", default=None,
                   help="surviving data directory to link (defaults to the "
                        "primary's if still reachable)")
    p.set_defaults(fn=cmd_activatestandby)

    p = sub.add_parser("standby")   # failover control plane
    p.add_argument("-s", "--standby", default=None,
                   help="standby coordinator directory")
    p.add_argument("--watch", action="store_true",
                   help="heartbeat the primary; auto-promote on silence")
    p.add_argument("--promote", action="store_true",
                   help="fence the primary and promote immediately")
    p.add_argument("--interval", type=float, default=None,
                   help="watch poll interval (default: standby_watch_interval_s)")
    p.add_argument("--deadline", type=float, default=None,
                   help="promote after this many seconds of primary "
                        "silence (default: standby_promote_deadline_s)")
    p.add_argument("--data", default=None,
                   help="surviving data directory to link on promotion")
    p.add_argument("--unfence", default=None, metavar="CLUSTER",
                   help="clear a promotion fence on CLUSTER (operator "
                        "escape hatch after verifying the old primary)")
    p.set_defaults(fn=cmd_standby)

    p = sub.add_parser("replicate")
    p.add_argument("-d", "--dir", required=True)
    p.set_defaults(fn=cmd_replicate)

    p = sub.add_parser("vacuum")
    p.add_argument("-d", "--dir", required=True)
    p.add_argument("-t", "--table", default=None)
    p.add_argument("--grace", type=float, default=120.0)
    p.set_defaults(fn=cmd_vacuum)

    p = sub.add_parser("analyze")
    p.add_argument("-d", "--dir", required=True)
    p.add_argument("-t", "--table", default=None)
    p.set_defaults(fn=cmd_analyze)

    p = sub.add_parser("analyzedb")   # incremental stats refresh
    p.add_argument("-d", "--dir", required=True)
    p.add_argument("--full", action="store_true")
    p.set_defaults(fn=cmd_analyzedb)

    p = sub.add_parser("checkperf")   # gpcheckperf analog
    p.add_argument("-d", "--dir", required=True)
    p.add_argument("--size-mb", type=int, default=64)
    p.add_argument("--device", action="store_true",
                   help="measure planner cost-model primitives on the "
                        "live backend")
    p.add_argument("--apply", action="store_true",
                   help="with --device: persist measurements to "
                        "<dir>/calibration.json; with --feedback: commit "
                        "every pending self-tuning correction")
    p.add_argument("--feedback", action="store_true",
                   help="print only the self-tuning est-vs-actual report "
                        "(planner/feedback.py store)")
    p.add_argument("--reset", action="store_true",
                   help="clear the self-tuning feedback store")
    p.set_defaults(fn=cmd_checkperf)

    p = sub.add_parser("load")        # gpload analog
    p.add_argument("-d", "--dir", required=True)
    p.add_argument("-f", "--config", required=True)
    p.set_defaults(fn=cmd_load)

    p = sub.add_parser("pkg")         # gppkg analog
    p.add_argument("action", choices=("install", "remove", "list"))
    p.add_argument("package", nargs="?", default=None)
    p.add_argument("-d", "--dir", required=True)
    p.set_defaults(fn=cmd_pkg)

    p = sub.add_parser("state")
    p.add_argument("-d", "--dir", required=True)
    p.add_argument("--probe", action="store_true")
    p.set_defaults(fn=cmd_state)

    p = sub.add_parser("sql")
    p.add_argument("-d", "--dir", default=None)
    p.add_argument("-s", "--socket", default=None)
    p.add_argument("query")
    p.set_defaults(fn=cmd_sql)

    p = sub.add_parser("ps")      # pg_stat_activity analog
    p.add_argument("-d", "--dir", default=None)
    p.add_argument("-s", "--socket", default=None)
    p.set_defaults(fn=cmd_ps)

    p = sub.add_parser("cancel")  # pg_cancel_backend analog
    p.add_argument("id", type=int)
    p.add_argument("-d", "--dir", default=None)
    p.add_argument("-s", "--socket", default=None)
    p.set_defaults(fn=cmd_cancel)

    p = sub.add_parser("trace")   # Chrome trace_event export (gpperfmon)
    p.add_argument("id", nargs="?", type=int, default=None,
                   help="statement id (default: newest completed trace)")
    p.add_argument("-d", "--dir", default=None)
    p.add_argument("-s", "--socket", default=None)
    p.add_argument("-o", "--out", default=None,
                   help="write the JSON here instead of stdout")
    p.set_defaults(fn=cmd_trace)

    p = sub.add_parser("metrics")  # Prometheus text exposition
    p.add_argument("-d", "--dir", default=None)
    p.add_argument("-s", "--socket", default=None)
    p.set_defaults(fn=cmd_metrics)

    p = sub.add_parser("mem")      # measured memory accounting surface
    p.add_argument("-d", "--dir", default=None)
    p.add_argument("-s", "--socket", default=None)
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="raw JSON report instead of the summary")
    p.set_defaults(fn=cmd_mem)

    p = sub.add_parser("server")
    p.add_argument("-d", "--dir", required=True)
    p.add_argument("-s", "--socket", required=True)
    p.add_argument("--host", default=None,
                   help="also listen on TCP (requires gg_hba.json users)")
    p.add_argument("--port", type=int, default=None)
    p.set_defaults(fn=cmd_server)

    p = sub.add_parser("useradd")   # createuser + pg_hba analog
    p.add_argument("-d", "--dir", required=True)
    p.add_argument("-u", "--user", required=True)
    p.add_argument("-P", "--password", required=True)
    p.set_defaults(fn=cmd_useradd)

    p = sub.add_parser("start")   # gpstart analog
    p.add_argument("-d", "--dir", required=True)
    p.add_argument("-s", "--socket", default=None)
    p.set_defaults(fn=cmd_start)

    p = sub.add_parser("stop")    # gpstop analog
    p.add_argument("-d", "--dir", required=True)
    p.add_argument("-m", "--mode", choices=("smart", "fast", "immediate"),
                   default="smart")
    p.add_argument("--timeout", type=float, default=30.0)
    p.set_defaults(fn=cmd_stop)

    p = sub.add_parser("logfilter")   # gplogfilter analog
    p.add_argument("-d", "--dir", required=True)
    p.add_argument("-t", "--trouble", action="store_true")
    p.add_argument("-m", "--match", default=None)
    p.add_argument("-b", "--begin", default=None)
    p.add_argument("-e", "--end", default=None)
    p.add_argument("--min-duration", type=float, default=None)
    p.add_argument("-n", "--tail", type=int, default=None)
    p.set_defaults(fn=cmd_logfilter)

    p = sub.add_parser("worker")
    p.add_argument("-d", "--dir", required=True)
    p.add_argument("--coordinator", required=True)   # host:port (jax.distributed)
    p.add_argument("--control-port", type=int, required=True)
    p.add_argument("--num-processes", type=int, required=True)
    p.add_argument("--process-id", type=int, required=True)
    # control-plane-only gang: no jax.distributed global mesh; every
    # process runs the lockstep program on its own full local mesh
    # (replicated-device deployments, CPU demo clusters)
    p.add_argument("--no-distributed", action="store_true")
    p.set_defaults(fn=cmd_worker)

    p = sub.add_parser("expand")
    p.add_argument("-d", "--dir", required=True)
    p.add_argument("-n", "--numsegments", type=int, required=True)
    p.set_defaults(fn=cmd_expand)

    p = sub.add_parser("recover")
    p.add_argument("-d", "--dir", required=True)
    p.set_defaults(fn=cmd_recover)

    p = sub.add_parser("check")   # static analysis gate (docs/ANALYSIS.md)
    p.add_argument("checks", nargs="*",
                   help="subset of checks (default: all static lints)")
    p.add_argument("--json", action="store_true")
    p.add_argument("--plans", action="store_true",
                   help="also validate the TPC-H/TPC-DS plan corpus")
    p.add_argument("--nseg", type=int, default=4)
    p.add_argument("--baseline", default=None,
                   help="alternate baseline file (default: checked-in)")
    p.add_argument("--no-baseline", action="store_true",
                   help="show findings the baseline would suppress")
    p.add_argument("--list", action="store_true",
                   help="print the check catalog with per-check finding "
                        "counts instead of the findings themselves")
    p.set_defaults(fn=cmd_check)

    p = sub.add_parser("checkcat")
    p.add_argument("-d", "--dir", required=True)
    p.set_defaults(fn=cmd_checkcat)

    p = sub.add_parser("scrub")     # storage verify + repair-or-quarantine
    p.add_argument("-d", "--dir", required=True)
    p.add_argument("-t", "--table", default=None)
    p.add_argument("--mirrors", action="store_true",
                   help="also verify (and refresh) standby-tree copies")
    p.add_argument("--no-repair", action="store_true",
                   help="report only; do not repair or quarantine")
    p.add_argument("--json", action="store_true",
                   help="machine-readable report")
    p.set_defaults(fn=cmd_scrub)

    p = sub.add_parser("archive")       # WAL-archive analog
    p.add_argument("-d", "--dir", required=True)
    p.add_argument("-a", "--archive", required=True)
    p.set_defaults(fn=cmd_archive)

    p = sub.add_parser("restore-pitr")  # point-in-time recovery
    p.add_argument("-d", "--dir", required=True)
    p.add_argument("-a", "--archive", required=True)
    p.add_argument("-v", "--version", type=int, default=None)
    p.add_argument("-t", "--time", default=None)
    p.set_defaults(fn=cmd_restore_pitr)

    p = sub.add_parser("backup")
    p.add_argument("-d", "--dir", required=True)
    p.add_argument("-o", "--out", required=True)
    p.set_defaults(fn=cmd_backup)

    p = sub.add_parser("restore")
    p.add_argument("-d", "--dir", required=True)
    p.add_argument("-b", "--backup", required=True)
    p.set_defaults(fn=cmd_restore)

    args = ap.parse_args(argv)
    try:
        return args.fn(args)
    except BrokenPipeError:      # e.g. `gg logfilter | head`
        return 0


if __name__ == "__main__":
    sys.exit(main())
