"""The plain reference of TPC-H Q13 "Customer Distribution" (clause 2.4.13).

numpy over the generated arrays, independent of the engine: `o_comment` is
a dictionary column, so `NOT LIKE '%word1%word2%'` is decided once a
dictionary string by Python's `re` and carried to the rows by their codes;
`bincount` of the surviving orders' `o_custkey` gives every customer's
`c_count`, zeros included (the LEFT OUTER JOIN's null-extended rows, which
`count(o_orderkey)` counts as 0); `bincount` of that gives `custdist`. Rows
with `custdist > 0`, by `custdist` descending then `c_count` descending:
`c_count` is unique among the rows, so their order is defined. Integers
throughout, compared bit-equal.

A command line that names a cell replaying Q13 is refused here, when run.py
imports this file and before any data is made, where the program under test
cannot parse the published text: clause 2.4.13.2 names the derived table's
columns in an alias list, `as c_orders (c_custkey, c_count)`, which the
parser took on a CTE only before ISSUE 37. Such a program would build its
cluster for minutes and then fail at the warm-up's first statement.
"""

from __future__ import annotations

import argparse
import json
import os
import re

import numpy as np

import oracle

HERE = os.path.dirname(os.path.abspath(__file__))

WORD1, WORD2 = "special", "requests"   # clause 2.4.13.3, validation values


def comment_matches(vocab: list, word1: str, word2: str) -> np.ndarray:
    """bool per dictionary string: LIKE '%word1%word2%'."""
    pat = re.compile(re.escape(word1) + ".*" + re.escape(word2), re.S)
    return np.array([pat.search(s) is not None for s in vocab], dtype=bool)


def customer_distribution(data, word1: str = WORD1, word2: str = WORD2) -> list:
    """-> rows [c_count, custdist], ordered."""
    o, c = data["orders"], data["customer"]
    n_cust = len(c["c_custkey"])
    # customer keys are 1..n in order (the generator's, checked here)
    if not np.array_equal(c["c_custkey"], np.arange(1, n_cust + 1)):
        raise oracle.WrongAnswer("oracle: c_custkey is not 1..n in order")
    cust = o["o_custkey"]
    if len(cust) and (cust.min() < 1 or cust.max() > n_cust):
        raise oracle.WrongAnswer("oracle: an o_custkey names no customer")
    comment = o["o_comment"]
    kept = ~comment_matches(comment.vocab, word1, word2)[comment.codes]
    c_count = np.bincount(cust[kept], minlength=n_cust + 1)[1:]
    custdist = np.bincount(c_count)
    rows = [(int(custdist[k]), int(k)) for k in np.flatnonzero(custdist)]
    return [[k, n] for n, k in sorted(rows, reverse=True)]


def replays_q13(workload: str | None) -> bool:
    """Is `workload` a cell of BENCHMARK.json whose round holds q13?"""
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        cells = {w["name"]: w["traffic"] for w in json.load(f)["workloads"]}
    if workload not in cells:
        return False
    with open(os.path.join(HERE, "..", "traffic", cells[workload] + ".json")) as f:
        return "q13" in json.load(f).get("round", [])


def parse_error() -> str | None:
    """What the program's own parser says of the published text, if it
    refuses it."""
    from greengage_tpu.sql.parser import parse

    with open(os.path.join(HERE, "q13.sql")) as f:
        sql = f.read()
    try:
        parse(sql)
    except Exception as e:   # whatever the parser raises is a refusal
        return f"{type(e).__name__}: {e}"
    return None


_ap = argparse.ArgumentParser(add_help=False, allow_abbrev=False)
_ap.add_argument("--workload")
if replays_q13(_ap.parse_known_args()[0].workload):
    _err = parse_error()
    if _err is not None:
        raise SystemExit(
            "queries/q13.py: this program's parser refuses TPC-H Q13 as "
            f"published (clause 2.4.13.2): {_err}. Not running it.")

ORACLES = {"q13": oracle.Oracle(customer_distribution,
                                lambda stored, params: stored)}
