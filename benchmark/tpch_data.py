"""TPC-H data for the benchmark: generate from the seed, load, cache.

The generator is the benchmark's own copy of `greengage_tpu/utils/tpch.py`
(same value ranges, decimal scales, date windows and simplified text
columns), cut to the three tables the cells read and changed in one place:
the lines per order are a seeded permutation of a fixed multiset, so every
seed gives the same row counts in another order.

This module is also the default data module: a configuration without a
`data` key is made by it. A data module (this one, or the file under
benchmark/ that a configuration's `data` names) provides GENERATOR_VERSION,
TABLES, DDL, table_rows(sf), generate(sf, seed) and column_types().

A loaded, analyzed cluster is kept under `benchmark/.cache/<config>-seed<n>/`
(`cluster/` pristine, `answers/` the oracle's stored answers, `meta.json`
the sidecar). A run never opens `cluster/`: it opens `work/`, a throw-away
copy of the metadata with the data files hard-linked, so every run starts
from the same bytes.
"""

from __future__ import annotations

import json
import os
import shutil
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

GENERATOR_VERSION = "b1"
TABLES = ("lineitem", "orders", "customer")
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
STATUSES = ["F", "O", "P"]
SHIPMODES = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
INSTRUCTS = ["COLLECT COD", "DELIVER IN PERSON", "NONE", "TAKE BACK RETURN"]

DDL = """
create table if not exists customer (
  c_custkey bigint, c_name text, c_address text, c_nationkey int,
  c_phone text, c_acctbal decimal(15,2), c_mktsegment text, c_comment text
) distributed by (c_custkey);
create table if not exists orders (
  o_orderkey bigint, o_custkey bigint, o_orderstatus text,
  o_totalprice decimal(15,2), o_orderdate date, o_orderpriority text,
  o_clerk text, o_shippriority int, o_comment text
) distributed by (o_orderkey);
create table if not exists lineitem (
  l_orderkey bigint, l_partkey bigint, l_suppkey bigint, l_linenumber int,
  l_quantity decimal(15,2), l_extendedprice decimal(15,2),
  l_discount decimal(15,2), l_tax decimal(15,2),
  l_returnflag text, l_linestatus text,
  l_shipdate date, l_commitdate date, l_receiptdate date,
  l_shipinstruct text, l_shipmode text, l_comment text
) distributed by (l_orderkey);
"""

# bytes a staged column takes per row on the device: decimals are scaled
# int64, dates int32 days, text a dictionary code (Q1's executable takes
# 45 bytes a padded row for seven such columns and a validity byte; my
# chip run, PR 23)
STAGED_BYTES = {"bigint": 8, "int": 4, "date": 4, "decimal": 8, "text": 4}


def column_types(ddl: str = DDL) -> dict[str, str]:
    """column -> type word, read from a DDL (this module's by default)."""
    out = {}
    for line in ddl.replace("(\n", ",").split(","):
        words = line.split()
        if len(words) >= 2 and words[0][1:2] == "_":
            out[words[0]] = words[1].split("(")[0]
    return out


def table_rows(sf: float) -> dict[str, int]:
    n_orders = max(int(1_500_000 * sf), 10)
    lines = 28 * (n_orders // 7) + sum(range(1, n_orders % 7 + 1))
    return {"lineitem": lines, "orders": n_orders,
            "customer": max(int(150_000 * sf), 5)}


def query_bytes(reads: dict[str, list[str]], rows: dict[str, int],
                types: dict[str, str]) -> int:
    """Bytes a query must read: rows x staged widths of the columns it
    names, unpadded and without validity masks (a floor, so a roofline
    share computed from it errs low, never high). `types` is the cell's
    data module's `column_types()`."""
    return sum(rows[t] * sum(STAGED_BYTES[types[c]] for c in cols)
               for t, cols in reads.items())


def generate(sf: float, seed: int) -> dict[str, dict]:
    """-> {table: {column: array | Coded}}, decimals as scaled int64."""
    from greengage_tpu.types import Coded, date_to_days

    rng = np.random.default_rng(seed)
    rows = table_rows(sf)
    n_orders, n_cust = rows["orders"], rows["customer"]
    n_supp, n_part = max(int(10_000 * sf), 3), max(int(200_000 * sf), 5)

    def dec(n, lo, hi):
        return rng.integers(int(lo * 100), int(hi * 100) + 1, n).astype(np.int64)

    def vocab(n, prefix, k):
        return Coded([f"{prefix}{i}" for i in range(k)],
                     rng.integers(0, k, n).astype(np.int32))

    def choice(n, values):
        return Coded(list(values),
                     rng.integers(0, len(values), n).astype(np.int32))

    customer = {
        "c_custkey": np.arange(1, n_cust + 1, dtype=np.int64),
        "c_name": [f"Customer#{i:09d}" for i in range(1, n_cust + 1)],
        "c_address": vocab(n_cust, "addr ", 1000),
        "c_nationkey": rng.integers(0, 25, n_cust).astype(np.int32),
        "c_phone": vocab(n_cust, "phone ", 1000),
        "c_acctbal": dec(n_cust, -999.99, 9999.99),
        "c_mktsegment": choice(n_cust, SEGMENTS),
        "c_comment": vocab(n_cust, "cust comment ", 300),
    }
    odate = rng.integers(date_to_days("1992-01-01"),
                         date_to_days("1998-08-02") + 1,
                         n_orders).astype(np.int32)
    n_clerk = max(n_orders // 1000, 2) - 1
    orders = {
        "o_orderkey": np.arange(1, n_orders + 1, dtype=np.int64),
        "o_custkey": rng.integers(1, n_cust + 1, n_orders).astype(np.int64),
        "o_orderstatus": choice(n_orders, STATUSES),
        "o_totalprice": dec(n_orders, 800.0, 500000.0),
        "o_orderdate": odate,
        "o_orderpriority": choice(n_orders, PRIORITIES),
        "o_clerk": Coded([f"Clerk#{i:09d}" for i in range(1, n_clerk + 1)],
                         rng.integers(0, n_clerk, n_orders).astype(np.int32)),
        "o_shippriority": np.zeros(n_orders, dtype=np.int32),
        "o_comment": vocab(n_orders, "order comment ", 500),
    }
    # 1..7 lines an order: the same multiset for every seed, shuffled
    lines_per = rng.permutation(np.arange(n_orders) % 7 + 1)
    n_line = int(lines_per.sum())
    if n_line != rows["lineitem"]:
        raise ValueError(f"generated {n_line} lines, table_rows says {rows['lineitem']}")
    l_ship = (np.repeat(odate, lines_per)
              + rng.integers(1, 122, n_line)).astype(np.int32)
    starts = np.repeat(np.cumsum(lines_per) - lines_per, lines_per)
    lineitem = {
        "l_orderkey": np.repeat(orders["o_orderkey"], lines_per),
        "l_partkey": rng.integers(1, n_part + 1, n_line).astype(np.int64),
        "l_suppkey": rng.integers(1, n_supp + 1, n_line).astype(np.int64),
        "l_linenumber": (np.arange(n_line) - starts + 1).astype(np.int32),
        "l_quantity": dec(n_line, 1.0, 50.0),
        "l_extendedprice": dec(n_line, 900.0, 100000.0),
        "l_discount": dec(n_line, 0.0, 0.10),
        "l_tax": dec(n_line, 0.0, 0.08),
        "l_returnflag": choice(n_line, ["A", "N", "R"]),
        "l_linestatus": choice(n_line, ["F", "O"]),
        "l_shipdate": l_ship,
        "l_commitdate": (l_ship + rng.integers(-30, 31, n_line)).astype(np.int32),
        "l_receiptdate": (l_ship + rng.integers(1, 31, n_line)).astype(np.int32),
        "l_shipinstruct": choice(n_line, INSTRUCTS),
        "l_shipmode": choice(n_line, SHIPMODES),
        "l_comment": vocab(n_line, "li comment ", 1000),
    }
    return {"lineitem": lineitem, "orders": orders, "customer": customer}


# ----------------------------------------------------------------------
# the cached cluster

def _read_json(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _write_json(path: str, obj) -> None:
    with open(path + ".tmp", "w") as f:
        json.dump(obj, f)
    os.replace(path + ".tmp", path)


def _save_answer(path: str, answer) -> None:
    """A fixed answer (list of rows) as JSON, a per-parameter table (dict
    of arrays) as .npz; written under a temporary name, then renamed."""
    if isinstance(answer, dict):
        np.savez(path + ".tmp.npz", **answer)
        os.replace(path + ".tmp.npz", path + ".npz")
    else:
        _write_json(path + ".json", answer)


def _load_answer(path: str):
    if os.path.exists(path + ".npz"):
        with np.load(path + ".npz") as z:
            return {k: z[k] for k in z.files}
    return _read_json(path + ".json")


def _make_room(cache_root: str, keep: str, need_bytes: float, log) -> None:
    """A cluster per seed adds up: before building another, drop the
    oldest cached ones while the disk has less room than one needs."""
    if not os.path.isdir(cache_root):
        return
    others = sorted((os.path.join(cache_root, d) for d in os.listdir(cache_root)
                     if d != keep), key=os.path.getmtime)
    while others and shutil.disk_usage(cache_root).free < need_bytes:
        log(f"disk low: dropping cached {others[0]}")
        shutil.rmtree(others.pop(0), ignore_errors=True)


def cache_key(data, config: dict, seed: int, sf: float) -> tuple[str, dict]:
    """-> (the cached cluster's directory name, what its sidecar must
    hold), for a configuration made by data module `data` at scale `sf`."""
    name = f"{config['name']}-seed{seed}" + ("" if sf == config["scale_factor"]
                                             else f"-sf{sf:g}")
    return name, {"generator": data.GENERATOR_VERSION, "seed": seed, "sf": sf,
                  "numsegments": config["numsegments"],
                  "rows": data.table_rows(sf)}


def ensure_cluster(data, config: dict, seed: int, queries: list[str],
                   cache_root: str, oracles: dict, log, sf: float | None = None,
                   rebuild: bool = False) -> tuple[str, dict, dict]:
    """-> (cache dir, meta, {query: stored answer}). `data` is the
    configuration's data module: it makes, declares and counts the tables.
    Builds what is missing: the cluster (generate, load, analyze) when the
    sidecar does not match or the caller found the row counts wrong
    (`rebuild`), and the answer of each query not yet stored (which needs
    the data again, not the load). The pristine cluster is never opened
    here after it is built: opening it would change its bytes."""
    import greengage_tpu

    sf = config["scale_factor"] if sf is None else sf
    nseg = config["numsegments"]
    name, want = cache_key(data, config, seed, sf)
    root = os.path.join(cache_root, name)
    cluster, ans_dir = os.path.join(root, "cluster"), os.path.join(root, "answers")
    have = _read_json(os.path.join(root, "meta.json"))
    ok = (not rebuild and have is not None
          and {k: have.get(k) for k in want} == want)
    phases = {}

    def timed(what, fn):
        t = time.monotonic()
        out = fn()
        phases[what] = round(time.monotonic() - t, 1)
        log(f"{what}: {phases[what]} s")
        return out

    def answer(tables, missing):
        for q in missing:
            answers[q] = timed(f"oracle {q}", lambda q=q: oracles[q].build(tables))
            _save_answer(os.path.join(ans_dir, q), answers[q])

    answers = {q: None if not ok else _load_answer(os.path.join(ans_dir, q))
               for q in queries}
    missing = [q for q, a in answers.items() if a is None]
    if not ok:
        log(f"no usable cluster at {root}: building (SF{sf:g}, seed {seed}, "
            f"{nseg} segment(s))")
        shutil.rmtree(root, ignore_errors=True)
        _make_room(cache_root, name, 8e9 * sf / 10, log)
        os.makedirs(ans_dir)
        tables = timed("generate", lambda: data.generate(sf, seed))
        # the oracle (numpy, pandas) beside the load (the program's codec):
        # both mostly outside the interpreter lock, on a host with cores to spare
        with ThreadPoolExecutor(1) as pool:
            oracle_job = pool.submit(answer, tables, missing)
            db = greengage_tpu.connect(cluster, numsegments=nseg)
            try:
                db.sql(data.DDL)
                timed("load", lambda: [db.load_table(t, tables[t])
                                       for t in data.TABLES])
                timed("analyze", lambda: db.sql("analyze"))
            finally:
                db.close()
            oracle_job.result()
    elif missing:
        answer(timed("generate (for answers)", lambda: data.generate(sf, seed)),
               missing)
    if not ok:
        size = sum(os.path.getsize(os.path.join(r, f))
                   for r, _d, fs in os.walk(root) for f in fs)
        have = {**want, "build_s": phases, "bytes_on_disk": size}
        _write_json(os.path.join(root, "meta.json"), have)
        log(f"cached cluster: {size} bytes on disk at {root}")
    return root, have, answers


def counts_match(db, rows: dict[str, int]) -> bool:
    """The loaded tables hold exactly the sidecar's rows (bench.py's
    `_counts_match`): load_table appends, so a directory left by a killed
    build would inflate every number. A replicated table holds all its rows
    on every segment; any other, each row on one."""
    def holds(t, n):
        counts = db.store.segment_rowcounts(t)
        if db.catalog.get(t).policy.kind.value == "replicated":
            return all(c == n for c in counts)
        return sum(counts) == n

    try:
        return all(holds(t, n) for t, n in rows.items())
    except Exception:   # a damaged directory is a mismatch, whatever it raises
        return False


def working_copy(root: str) -> str:
    """`work/`: the pristine cluster's tree with the files under data/
    hard-linked and everything else (catalog, manifest, logs, feedback)
    copied. The program writes to the copies only; data files are
    immutable once committed."""
    src, dst = os.path.join(root, "cluster"), os.path.join(root, "work")
    shutil.rmtree(dst, ignore_errors=True)
    for d, _dirs, files in os.walk(src):
        rel = os.path.relpath(d, src)
        os.makedirs(os.path.join(dst, rel), exist_ok=True)
        linked = rel == "data" or rel.startswith("data" + os.sep)
        for f in files:
            a, b = os.path.join(d, f), os.path.join(dst, rel, f)
            if linked:
                os.link(a, b)
            else:
                shutil.copy2(a, b)
    return dst
