"""The plain reference: each query's answer by numpy/pandas over the
generated arrays, independent of the engine, and the comparison that
decides `correct`.

Decimals are the generator's scaled int64; a sum is exact integer
arithmetic and is presented the way the engine presents a DECIMAL
(value / 10**scale in float64), so equal integers give bit-equal floats.
Q6, Q1, Q3 and `compare` are copied from `chip_smoke.py` (PR 23).

An oracle is registered under its query's name with two functions:
`build(data)` computes what is stored beside the cached cluster (a list of
rows for a fixed statement, a dict of arrays for a parameterised one), and
`rows(stored, params)` gives the expected rows of one statement.
"""

from __future__ import annotations

from collections import namedtuple

import numpy as np

from tpch_data import PRIORITIES, SEGMENTS, STATUSES

Oracle = namedtuple("Oracle", "build rows")
ORACLES: dict[str, Oracle] = {}
# averages divide an exact int64 sum by an exact count in float64 on both
# sides; only the order of the two float divisions may differ
AVG_RTOL = 1e-12
# Q1's average columns; every other value of every query is exact
AVG_COLUMNS = {"q1": (6, 7, 8)}


class WrongAnswer(Exception):
    pass


def _days(day: str) -> int:
    return int((np.datetime64(day) - np.datetime64("1970-01-01"))
               .astype(np.int64))


def _fixed(fn):
    return Oracle(fn, lambda stored, params: stored)


def _q6(data) -> list:
    li = data["lineitem"]
    ship, disc = li["l_shipdate"], li["l_discount"]
    m = ((ship >= _days("1994-01-01")) & (ship < _days("1995-01-01"))
         & (disc >= 5) & (disc <= 7) & (li["l_quantity"] < 2400))
    rev = int(np.sum(li["l_extendedprice"][m] * disc[m]))
    return [[rev / 10.0 ** 4]]


def _q1(data) -> list:
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
        rows.append([
            rf.vocab[g // len(ls.vocab)], ls.vocab[g % len(ls.vocab)],
            s_qty / 10.0 ** 2, s_price / 10.0 ** 2,
            int(disc_price[k].sum()) / 10.0 ** 4,
            int(charge[k].sum()) / 10.0 ** 6,
            s_qty / cnt / 100.0, s_price / cnt / 100.0,
            int(disc[k].sum()) / cnt / 100.0, cnt])
    return sorted(rows)


def _q3(data) -> list:
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
    if len(set(keys)) != len(keys):
        raise WrongAnswer("oracle: Q3's first eleven rows tie on (revenue, "
                          "o_orderdate); the answer's order is not defined "
                          "for this seed")
    epoch = np.datetime64("1970-01-01", "D")
    return [[int(r.key), int(r.rev) / 10.0 ** 4,
             str(epoch + np.timedelta64(int(r.o_orderdate), "D")),
             int(r.o_shippriority)] for r in top.head(10).itertuples()]


ORACLES["q6"] = _fixed(_q6)
ORACLES["q1"] = _fixed(_q1)
ORACLES["q3"] = _fixed(_q3)


# ----------------------------------------------------------------------
# the dashboard shapes: a table per shape, one entry per parameter value

def _grouped(key: np.ndarray, group: np.ndarray, n_keys: int, n_groups: int,
             value: np.ndarray | None) -> dict:
    """count (and exact int64 sum of `value`) per (key, group), dense."""
    import pandas as pd

    flat = key.astype(np.int64) * n_groups + group
    out = {"count": np.bincount(flat, minlength=n_keys * n_groups)
           .reshape(n_keys, n_groups)}
    if value is not None:
        s = pd.Series(value).groupby(flat).sum()
        total = np.zeros(n_keys * n_groups, dtype=np.int64)
        total[s.index.to_numpy()] = s.to_numpy()
        out["sum"] = total.reshape(n_keys, n_groups)
    return out


def _group_rows(labels: list[str], count: np.ndarray,
                total: np.ndarray | None) -> list:
    """Rows of a `group by label order by label`: absent groups left out."""
    return [[lab, int(count[i])]
            + ([] if total is None else [int(total[i]) / 10.0 ** 2])
            for i, lab in enumerate(labels) if count[i]]


def _month_index(days: np.ndarray) -> np.ndarray:
    """months since 1970-01 of a days-since-1970 array."""
    return days.astype("datetime64[D]").astype("datetime64[M]").astype(np.int64)


def _month_range(params: dict) -> slice:
    return slice(int(np.datetime64(params["d0"][:7], "M").astype(np.int64)),
                 int(np.datetime64(params["d1"][:7], "M").astype(np.int64)))


def _orders_by_month(group_col: str, value_col: str | None):
    def build(data):
        o = data["orders"]
        g = o[group_col]
        return _grouped(_month_index(o["o_orderdate"]), g.codes, 12 * 30,
                        len(g.vocab), None if value_col is None else o[value_col])
    return build


def _month_rows(labels: list[str]):
    def rows(stored, params):
        r = _month_range(params)
        total = stored["sum"][r].sum(axis=0) if "sum" in stored else None
        return _group_rows(labels, stored["count"][r].sum(axis=0), total)
    return rows


ORACLES["orders_by_quarter"] = Oracle(
    _orders_by_month("o_orderpriority", None), _month_rows(PRIORITIES))
ORACLES["orders_by_year"] = Oracle(
    _orders_by_month("o_orderstatus", "o_totalprice"), _month_rows(STATUSES))


def _by_key(table, key_col, group_col, value_col, labels) -> Oracle:
    def build(data):
        t = data[table]
        return _grouped(t[key_col], t[group_col].codes,
                        int(t[key_col].max()) + 1, len(labels), t[value_col])

    def rows(stored, params):
        k = params["k"]
        return _group_rows(labels, stored["count"][k], stored["sum"][k])
    return Oracle(build, rows)


ORACLES["orders_of_customer"] = _by_key(
    "orders", "o_custkey", "o_orderstatus", "o_totalprice", STATUSES)
ORACLES["cust_by_nation"] = _by_key(
    "customer", "c_nationkey", "c_mktsegment", "c_acctbal", SEGMENTS)


# ----------------------------------------------------------------------

def _norm(v):
    if isinstance(v, np.datetime64):
        return str(v)
    if isinstance(v, (np.floating, float)):
        return float(v)
    if isinstance(v, (np.integer, int)):
        return int(v)
    return v


def compare(query: str, got: list, want: list) -> None:
    """Sums and counts exact, averages to AVG_RTOL, rows in order. Values
    from the wire arrive as JSON scalars and date strings, so both sides
    are normalized to (str | float | int). Raises WrongAnswer."""
    if len(got) != len(want):
        raise WrongAnswer(f"{query}: {len(got)} rows, oracle has {len(want)}")
    avg = AVG_COLUMNS.get(query, ())
    for i, (g, w) in enumerate(zip(got, want)):
        g, w = [_norm(v) for v in g], [_norm(v) for v in w]
        if len(g) != len(w):
            raise WrongAnswer(f"{query} row {i}: width {len(g)} != {len(w)}")
        for j, (a, b) in enumerate(zip(g, w)):
            ok = abs(a - b) <= AVG_RTOL * abs(b) if j in avg else a == b
            if not ok:
                raise WrongAnswer(
                    f"{query} row {i} col {j}: engine {a!r} != oracle {b!r}")
