"""The plain reference of the TPC-H refresh functions around Q1 and Q6
(clauses 2.5-2.7 and 5.3.3), for the statements `rf1_lineitem`,
`rf1_orders`, `q1_live`, `q6_live`, `rf2_lineitem`, `rf2_orders`.

An oracle that follows state. The other oracles answer a read-only window
from one stored answer; here every statement but two writes, so the answer
of a statement depends on every statement before it. `State` below is the
logical content of `orders` and `lineitem`, independent of the engine, and
each oracle's `rows(stored, params)` first applies its own statement to it:
run.py's `check_answers` hands the records over in the order they ran
(the warm-up's first, which also writes), once each, and a statement that
failed is not handed over at all, so the next comparison fails loudly
instead of drifting.

What makes that cheap at SF10 is the shape of the statements. RF1 copies
and RF2 deletes whole ranges of order keys, and a copy keeps its row's
values under a key raised by a constant. So `lineitem` is always a list of
pieces (first key, last key, shift, copies): every key k of a piece stands
for `copies` copies of the generated lines of order k - shift. `orders`
holds each key once and is a list of key intervals. Q1 and Q6 are sums over
rows, so their exact integer aggregates over a piece are a difference of
two entries of a prefix sum taken per generated order. `build` stores the
prefix over the first `STORED_BLOCKS` x `orders_per_refresh` orders and the
whole table's total: the statements only ever cut pieces at low keys, and
`_Prefix.at` raises where a cut falls past what was stored. Nothing here
assumes a scale: at SF 0.01 (rehearse.py, the tier-1 tests) one refresh
function covers the whole table and copies are copied again.

`oracle.compare` treats Q1's average columns by relative tolerance; they
are registered here for `q1_live` without an edit to oracle.py.
"""

from __future__ import annotations

import json
import os

import numpy as np

import oracle
from oracle import _days

HERE = os.path.dirname(os.path.abspath(__file__))
REFRESH = ("rf1_lineitem", "rf1_orders", "rf2_lineitem", "rf2_orders")
# how many refresh functions' worth of the lowest order keys the stored
# prefix sums cover: each RF2 moves the lowest key up by one, the warm-up
# runs a statement up to five times, and a 45 s window holds a few rounds
STORED_BLOCKS = 48

oracle.AVG_COLUMNS["q1_live"] = oracle.AVG_COLUMNS["q1"]


def orders_per_refresh() -> int:
    """SF x 1500 as the statements' text has it: each refresh statement's
    `.json` states it, and its `.sql` must hold the range's last offset."""
    found = set()
    for q in REFRESH:
        with open(os.path.join(HERE, q + ".json")) as f:
            n = json.load(f)["orders_per_refresh"]
        with open(os.path.join(HERE, q + ".sql")) as f:
            if f"min(o_orderkey) + {n - 1} from orders" not in f.read():
                raise ValueError(f"{q}.sql does not end its key range at "
                                 f"min(o_orderkey) + {n - 1}")
        found.add(n)
    if len(found) != 1:
        raise ValueError(f"the refresh statements disagree: {sorted(found)}")
    return found.pop()


BLOCK = orders_per_refresh()


# ----------------------------------------------------------------------
# what is stored beside the cluster: per-order prefix sums

def _order_index(data) -> tuple[np.ndarray, int]:
    """-> (the generated order each lineitem row belongs to, 0-based;
    number of orders). The generator's keys are 1..n in order."""
    okey = data["orders"]["o_orderkey"]
    n = len(okey)
    if okey[0] != 1 or okey[-1] != n:
        raise oracle.WrongAnswer("oracle: o_orderkey is not 1..n in order")
    return data["lineitem"]["l_orderkey"] - 1, n


def _stored_orders(n_orders: int) -> int:
    return min(n_orders, STORED_BLOCKS * BLOCK)


def _prefix(order_of: np.ndarray, n_orders: int, columns: list,
            keep: np.ndarray | None = None, gid: np.ndarray | None = None,
            n_groups: int = 1) -> dict:
    """Exact int64 sums of each of `columns` over the rows `keep` keeps,
    a group of `gid`: over the lines of the generated orders 1..j for j =
    0..stored (`prefix`, [stored + 1, n_groups x len(columns)]) and over
    the whole table (`total`). l_orderkey is sorted, so a prefix over
    orders is a running sum over rows read at each order's last line."""
    stored = _stored_orders(n_orders)
    ends = np.searchsorted(order_of, np.arange(stored + 1))   # rows below j
    rows, k = int(ends[-1]), len(columns)
    prefix = np.zeros((stored + 1, n_groups * k), np.int64)
    total = np.zeros(n_groups * k, np.int64)
    for g in range(n_groups):
        m = np.ones(len(order_of), bool) if keep is None else keep
        if gid is not None:
            m = m & (gid == g)
        total[g * k:(g + 1) * k] = [int(c[m].sum()) for c in columns]
        head = np.stack([c[:rows] for c in columns], axis=1) * m[:rows, None]
        running = np.concatenate([np.zeros((1, k), np.int64),
                                  np.cumsum(head, axis=0, dtype=np.int64)])
        prefix[:, g * k:(g + 1) * k] = running[ends]
    return {"prefix": prefix, "total": total, "n_orders": np.int64(n_orders)}


def _build_lines(data) -> dict:
    order_of, n = _order_index(data)
    return _prefix(order_of, n, [np.ones(len(order_of), np.int64)])


def _build_orders(data) -> dict:
    return {"n_orders": np.int64(_order_index(data)[1])}


Q1_SUMS = 6   # qty, price, disc_price, charge, disc, count


def _build_q1(data) -> dict:
    """Per (l_returnflag, l_linestatus) group, Q1's five exact sums and
    its count, of the rows its WHERE keeps; groups in the answer's order."""
    li = data["lineitem"]
    order_of, n = _order_index(data)
    rf, ls = li["l_returnflag"], li["l_linestatus"]
    if sorted(rf.vocab) != list(rf.vocab) or sorted(ls.vocab) != list(ls.vocab):
        raise oracle.WrongAnswer("oracle: Q1's group labels are not in order")
    price, disc = li["l_extendedprice"], li["l_discount"]
    disc_price = price * (100 - disc)
    return {**_prefix(
        order_of, n,
        [li["l_quantity"], price, disc_price, disc_price * (100 + li["l_tax"]),
         disc, np.ones(len(price), np.int64)],
        keep=li["l_shipdate"] <= _days("1998-12-01") - 90,
        gid=rf.codes.astype(np.int64) * len(ls.vocab) + ls.codes,
        n_groups=len(rf.vocab) * len(ls.vocab)),
        "labels": np.array([[r, s] for r in rf.vocab for s in ls.vocab])}


def _build_q6(data) -> dict:
    li = data["lineitem"]
    order_of, n = _order_index(data)
    ship, disc = li["l_shipdate"], li["l_discount"]
    return _prefix(
        order_of, n, [li["l_extendedprice"] * disc],
        keep=((ship >= _days("1994-01-01")) & (ship < _days("1995-01-01"))
              & (disc >= 5) & (disc <= 7) & (li["l_quantity"] < 2400)))


class _Prefix:
    """A stored prefix sum: `span(a, b)` is the sum over the generated
    orders a..b; a cut past the stored range raises."""

    def __init__(self, stored: dict):
        self.prefix, self.total = stored["prefix"], stored["total"]
        self.n = int(stored["n_orders"])

    def at(self, j: int) -> np.ndarray:
        if j == self.n:
            return self.total
        if not 0 <= j < len(self.prefix):
            raise oracle.WrongAnswer(
                f"oracle: the statements cut at generated order {j}, past the "
                f"{len(self.prefix) - 1} whose prefix sums are stored (raise "
                "STORED_BLOCKS in queries/refresh.py)")
        return self.prefix[j]

    def span(self, a: int, b: int) -> np.ndarray:
        return self.at(b) - self.at(a - 1)


# ----------------------------------------------------------------------
# the logical tables, statement by statement

class State:
    """`orders` as sorted, disjoint key intervals [a, b]; `lineitem` as
    pieces [a, b, shift, copies] (the module's docstring). The statements'
    own arithmetic: the key range [lo, lo + BLOCK - 1] with lo the lowest
    order key, and RF1's new keys raised by the highest order key, both
    read from `orders` as it is when the statement starts."""

    def __init__(self, n_orders: int):
        self.n_orders = n_orders
        self.orders = [[1, n_orders]]
        self.lineitem = [[1, n_orders, 0, 1]]

    def _range(self) -> tuple[int, int, int]:
        if not self.orders:
            raise oracle.WrongAnswer("oracle: orders is empty; min() is NULL")
        lo = self.orders[0][0]
        return lo, lo + BLOCK - 1, self.orders[-1][1]

    def rf1_lineitem(self, lines: _Prefix) -> int:
        lo, hi, top = self._range()
        new = [[max(a, lo) + top, min(b, hi) + top, s + top, m]
               for a, b, s, m in self.lineitem if a <= hi and b >= lo]
        self.lineitem += new
        return self._rows(new, lines)

    def rf1_orders(self) -> int:
        lo, hi, top = self._range()
        new = [[max(a, lo) + top, min(b, hi) + top]
               for a, b in self.orders if a <= hi and b >= lo]
        self.orders += new   # every new key is above `top`: still sorted
        return sum(b - a + 1 for a, b in new)

    def rf2_lineitem(self, lines: _Prefix) -> int:
        lo, hi, _ = self._range()
        gone, kept = [], []
        for a, b, s, m in self.lineitem:
            if a <= hi and b >= lo:
                gone.append([max(a, lo), min(b, hi), s, m])
            kept += [[x, y, s, m] for x, y in ((a, min(b, lo - 1)),
                                               (max(a, hi + 1), b)) if x <= y]
        self.lineitem = kept
        return self._rows(gone, lines)

    def rf2_orders(self) -> int:
        lo, hi, _ = self._range()
        before = sum(b - a + 1 for a, b in self.orders)
        self.orders = [[x, y] for a, b in self.orders
                       for x, y in ((a, min(b, lo - 1)), (max(a, hi + 1), b))
                       if x <= y]
        return before - sum(b - a + 1 for a, b in self.orders)

    def total(self, prefix: _Prefix) -> np.ndarray:
        """Sum over lineitem as it stands of what `prefix` sums."""
        out = np.zeros_like(prefix.total)
        for a, b, s, m in self.lineitem:
            out += m * prefix.span(a - s, b - s)
        return out

    def _rows(self, pieces, lines: _Prefix) -> int:
        return int(sum(m * lines.span(a - s, b - s)[0]
                       for a, b, s, m in pieces))


def _tag(verb: str, n: int) -> list:
    return [[f"{verb} {n}", n]]


def q1_rows(labels, sums: np.ndarray) -> list:
    """Q1's rows from its per-group exact sums, presented as oracle._q1
    presents them; a group with no row is absent."""
    rows = []
    for (rf, ls), (qty, price, disc_price, charge, disc, cnt) in zip(
            labels, sums.reshape(len(labels), Q1_SUMS).tolist()):
        if cnt:
            rows.append([str(rf), str(ls), qty / 10.0 ** 2, price / 10.0 ** 2,
                         disc_price / 10.0 ** 4, charge / 10.0 ** 6,
                         qty / cnt / 100.0, price / cnt / 100.0,
                         disc / cnt / 100.0, cnt])
    return rows


def following() -> dict:
    """The six oracles over ONE State, made from the first stored answer
    they are handed: a new one per import, and run.py imports this file
    anew for every run."""
    held: list[State] = []

    def state(stored: dict) -> State:
        n = int(stored["n_orders"])
        if not held:
            held.append(State(n))
        if held[0].n_orders != n:
            raise oracle.WrongAnswer("oracle: the stored answers are of "
                                     "clusters of different sizes")
        return held[0]

    return {
        "rf1_lineitem": oracle.Oracle(_build_lines, lambda st, _p: _tag(
            "INSERT 0", state(st).rf1_lineitem(_Prefix(st)))),
        "rf1_orders": oracle.Oracle(_build_orders, lambda st, _p: _tag(
            "INSERT 0", state(st).rf1_orders())),
        "q1_live": oracle.Oracle(_build_q1, lambda st, _p: q1_rows(
            st["labels"], state(st).total(_Prefix(st)))),
        "q6_live": oracle.Oracle(_build_q6, lambda st, _p: [[
            int(state(st).total(_Prefix(st))[0]) / 10.0 ** 4]]),
        "rf2_lineitem": oracle.Oracle(_build_lines, lambda st, _p: _tag(
            "DELETE", state(st).rf2_lineitem(_Prefix(st)))),
        "rf2_orders": oracle.Oracle(_build_orders, lambda st, _p: _tag(
            "DELETE", state(st).rf2_orders())),
    }


ORACLES = following()
