"""Aggregates with no GROUP BY (ISSUE 32): one cell, reduced from the masked
rows by a full reduction in every phase (`ops/agg.scalar_aggregate`). Every
function over every type it takes, on one segment and on four (partial ->
broadcast -> final), over inputs that leave rows, leave none, hold only
NULLs, or are mostly padding; against numpy. CPU: answers, never a time."""

import jax.numpy as jnp
import numpy as np
import pytest

import greengage_tpu
from greengage_tpu.ops import agg as agg_ops

N = 1000
SCALE = 2                          # d decimal(12, 2): scaled int64 on the device
COLUMN = {"int": "i", "decimal": "d", "float64": "f", "date": "dt"}
CASES = [("count_star", None)] + [
    (func, typ) for func in ("count", "sum", "avg", "min", "max")
    for typ in COLUMN if not (typ == "date" and func in ("sum", "avg"))]
DDL = ("create table {} (k int, i int, d decimal(12,2), f double precision, "
       "dt date) distributed by (k)")


def _columns(n: int, rng) -> dict:
    return {"k": np.arange(n, dtype=np.int32),
            "i": rng.integers(-1000, 1000, n).astype(np.int32),
            "d": rng.integers(-10**9, 10**9, n),
            "f": rng.normal(size=n) * 1e6,
            "dt": rng.integers(8000, 12000, n).astype(np.int32)}


def _tables() -> dict:
    """name -> (columns, valids). `t`: no NULL. `tn`: every value NULL.
    `tp`: 37 rows in batches of 64 slots or more, so most of a batch is
    padding; strictly positive values (a zero slot that counted would be
    the minimum), every third value NULL."""
    rng = np.random.default_rng(32)
    t, tn = _columns(N, rng), _columns(N, rng)
    tp = {c: np.abs(v) + 1 for c, v in _columns(37, rng).items()}
    tp["k"] = np.arange(37, dtype=np.int32)
    vals = list(COLUMN.values())
    return {"t": (t, {}),
            "tn": (tn, {c: np.zeros(N, bool) for c in vals}),
            "tp": (tp, {c: np.arange(37) % 3 != 0 for c in vals})}


# input -> (table, WHERE clause, the same filter over the table's k)
SOURCE = {"all_live": ("t", "", lambda k: k >= 0),
          "none_kept": ("t", " where k < 0", lambda k: k < 0),
          "all_null": ("tn", "", lambda k: k >= 0),
          "padding": ("tp", " where k <> 5", lambda k: k != 5)}


def _select(inp: str) -> str:
    items = ["count(*)" if typ is None else f"{func}({COLUMN[typ]})"
             for func, typ in CASES]
    table, where, _ = SOURCE[inp]
    return f"select {', '.join(items)} from {table}{where}"


@pytest.fixture(scope="module")
def env(devices8):
    tables = _tables()
    dbs = {}
    for nseg in (1, 4):
        db = greengage_tpu.connect(numsegments=nseg)
        for name, (cols, valids) in tables.items():
            db.sql(DDL.format(name))
            db.load_table(name, cols, valids=valids)
        dbs[nseg] = db
    yield {"tables": tables, "dbs": dbs, "rows": {}}
    for db in dbs.values():
        db.close()


def _answer(env, nseg: int, inp: str) -> tuple:
    """The one statement of (segments, input): every case's column."""
    memo = env["rows"]
    if (nseg, inp) not in memo:
        (row,) = env["dbs"][nseg].sql(_select(inp)).rows()
        memo[nseg, inp] = row
    return memo[nseg, inp]


def _want(func: str, vals, live):
    """numpy's answer over the live rows, in the column's device domain
    (DECIMAL scaled, DATE in days); None is SQL's NULL."""
    if func == "count":
        return int(live.sum())
    if not live.any():
        return None
    v = vals[live]
    if func == "sum":
        return v.sum() if v.dtype.kind == "f" else int(v.astype(np.int64).sum())
    if func == "avg":
        return float(v.astype(np.float64).sum()) / len(v)
    return v.min() if func == "min" else v.max()


@pytest.mark.parametrize("inp", list(SOURCE))
@pytest.mark.parametrize("nseg", [1, 4])
@pytest.mark.parametrize("func,typ", CASES,
                         ids=[f if t is None else f"{f}-{t}" for f, t in CASES])
def test_ungrouped_aggregate_equals_numpy(env, func, typ, nseg, inp):
    got = _answer(env, nseg, inp)[CASES.index((func, typ))]
    table, _, keep = SOURCE[inp]
    cols, valids = env["tables"][table]
    kept = keep(cols["k"])
    if func == "count_star":
        assert got == kept.sum()
        return
    name = COLUMN[typ]
    want = _want(func, cols[name], kept & valids.get(name, np.ones(len(kept), bool)))
    if want is None:
        assert got is None
        return
    assert got is not None
    if func == "count":
        assert got == want
    elif typ == "date":
        assert got == np.datetime64(int(want), "D")
    elif func == "avg":
        if typ == "decimal":
            want /= 10 ** SCALE
        assert got == pytest.approx(want, rel=1e-12)
    elif typ == "decimal":          # sum, min, max: scaled integers, exact
        assert round(float(got) * 10 ** SCALE) == want
    elif typ == "float64" and func == "sum":
        assert got == pytest.approx(want, rel=1e-12)   # SQL fixes no order
    else:
        assert got == want


@pytest.mark.parametrize("dtype", [np.int32, np.int64, np.float64])
@pytest.mark.parametrize("func", ["count_star", "count", "sum", "avg", "min", "max"])
def test_scalar_aggregate_ignores_dead_and_null_slots(func, dtype):
    """The kernel alone: dead slots (padding, filtered rows) and NULL slots
    hold the most misleading values the type has; one cell comes back."""
    rng = np.random.default_rng(7)
    n = 256
    vals = rng.integers(-500, 500, n).astype(dtype)
    sel = np.arange(n) % 4 != 1
    valid = np.arange(n) % 5 != 2
    big = np.finfo(dtype).max / 4 if dtype is np.float64 else np.iinfo(dtype).max
    vals[~sel] = big
    vals[~valid] = -big
    args = (None, None) if func == "count_star" else (jnp.asarray(vals),
                                                      jnp.asarray(valid))
    spec = agg_ops.AggSpec("a", func, *args)
    out, out_valid = agg_ops.scalar_aggregate([spec], jnp.asarray(sel))
    assert out["a"].shape == (1,)
    live = sel & valid
    if func == "count_star":
        assert int(out["a"][0]) == sel.sum()
        return
    assert out_valid["a"] is None or bool(out_valid["a"][0])
    want = _want(func, vals, live)
    assert out["a"][0] == pytest.approx(want, rel=1e-12)
    # ... and with no live row at all: counts 0, everything else NULL
    out, out_valid = agg_ops.scalar_aggregate([spec], jnp.zeros(n, bool))
    if func == "count":
        assert int(out["a"][0]) == 0
    else:
        assert not bool(out_valid["a"][0])
