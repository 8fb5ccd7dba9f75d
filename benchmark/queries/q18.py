"""The plain reference of TPC-H Q18 "Large Volume Customer" (clause 2.4.18).

numpy/pandas over the generated arrays, independent of the engine: the sum
of l_quantity per order by `bincount` over the order keys (checked exact),
the orders whose sum exceeds the threshold, their order and customer rows,
ordered by o_totalprice descending then o_orderdate, the first hundred.
`sum(l_quantity)` is an exact integer presented the way the engine presents
a DECIMAL (value / 10**scale in float64), as `oracle.py` does for Q1.

A command line that names a cell replaying Q18 is refused here, when run.py
imports this file and before any data is made, where the program under test
plans Q18's IN-subquery as a semi-join above the joins it shares its key
with. Such a program joins every lineitem row to orders and customer before
it looks at the few hundred keys the HAVING keeps, and the one this cell was
added over never finished compiling that at one segment (PERF.md section 6,
PR 31): it exits non-zero at once and does not hang.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import tempfile

import numpy as np

import oracle
import tpch_data

HERE = os.path.dirname(os.path.abspath(__file__))

QUANTITY = 300   # clause 2.4.18.3, the validation parameter
LIMIT = 100


def top_orders(data, quantity: int = QUANTITY, limit: int = LIMIT) -> list:
    """-> rows [c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
    sum(l_quantity)]. Raises WrongAnswer where the first `limit` + 1 rows
    tie on both order keys: the answer's order is then not defined."""
    import pandas as pd

    li, o, c = data["lineitem"], data["orders"], data["customer"]
    total = _sum_by_key(li["l_orderkey"], li["l_quantity"])
    big = np.flatnonzero(total > quantity * 100)          # order keys
    pick = np.flatnonzero(np.isin(o["o_orderkey"], big))  # their order rows
    g = pd.DataFrame({
        "o_orderkey": o["o_orderkey"][pick], "o_custkey": o["o_custkey"][pick],
        "o_orderdate": o["o_orderdate"][pick],
        "o_totalprice": o["o_totalprice"][pick]})
    g["qty"] = total[g["o_orderkey"].to_numpy()]
    # customer keys are 1..n in order (the generator's, checked here)
    cust = g["o_custkey"].to_numpy()
    if not np.array_equal(c["c_custkey"][cust - 1], cust):
        raise oracle.WrongAnswer("oracle: c_custkey is not 1..n in order")
    g["c_name"] = [c["c_name"][k - 1] for k in cust]
    g = g.sort_values(["o_totalprice", "o_orderdate"], ascending=[False, True])
    top = g.head(limit + 1)
    keys = list(zip(top["o_totalprice"], top["o_orderdate"]))
    if len(set(keys)) != len(keys):
        raise oracle.WrongAnswer(
            f"oracle: Q18's first {limit + 1} rows tie on (o_totalprice, "
            "o_orderdate); the answer's order is not defined for this seed")
    epoch = np.datetime64("1970-01-01", "D")
    return [[r.c_name, int(r.o_custkey), int(r.o_orderkey),
             str(epoch + np.timedelta64(int(r.o_orderdate), "D")),
             int(r.o_totalprice) / 10.0 ** 2, int(r.qty) / 10.0 ** 2]
            for r in top.head(limit).itertuples()]


def _sum_by_key(key: np.ndarray, value: np.ndarray) -> np.ndarray:
    """Exact int64 sum of `value` per key: bincount adds in float64, which
    is exact while every sum stays below 2**53."""
    s = np.bincount(key, weights=value.astype(np.float64))
    if s.max(initial=0.0) >= 2.0 ** 53:
        raise oracle.WrongAnswer("oracle: a per-order sum leaves float64's integers")
    return s.astype(np.int64)


def replays_q18(workload: str | None) -> bool:
    """Is `workload` a cell of BENCHMARK.json whose round holds q18?"""
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        cells = {w["name"]: w["traffic"] for w in json.load(f)["workloads"]}
    if workload not in cells:
        return False
    with open(os.path.join(HERE, "..", "traffic", cells[workload] + ".json")) as f:
        return "q18" in json.load(f).get("round", [])


def semi_join_above_its_joins() -> bool:
    """EXPLAIN of Q18 over the empty schema on one segment, through the
    program's public surface: does `Join semi` sit above every `Join inner`?"""
    import greengage_tpu

    with open(os.path.join(HERE, "q18.sql")) as f:
        sql = f.read()
    tmp = tempfile.mkdtemp(prefix="ggq18")
    try:
        db = greengage_tpu.connect(tmp, numsegments=1)
        try:
            db.sql(tpch_data.DDL)
            plan = db.sql("explain " + sql).plan_text
        finally:
            db.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    depth = {"semi": [], "inner": []}
    for indent, kind in re.findall(r"^( *)Join (semi|inner)", plan, re.M):
        depth[kind].append(len(indent))
    return bool(depth["semi"] and depth["inner"]
                and min(depth["semi"]) < min(depth["inner"]))


_ap = argparse.ArgumentParser(add_help=False, allow_abbrev=False)
_ap.add_argument("--workload")
if replays_q18(_ap.parse_known_args()[0].workload) and semi_join_above_its_joins():
    raise SystemExit(
        "queries/q18.py: this program plans Q18's semi-join above the joins "
        "on its key; it cannot run a cell that replays Q18 at one segment "
        "(PERF.md section 6, PR 31). Not running it.")

ORACLES = {"q18": oracle.Oracle(top_orders, lambda stored, params: stored)}
