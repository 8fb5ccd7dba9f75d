"""Device operator tests on the virtual CPU mesh, checked against
numpy/pandas oracles (the pg_regress analog at the operator level)."""

import numpy as np
import pandas as pd
import pytest

import jax
import jax.numpy as jnp

from greengage_tpu import expr as E
from greengage_tpu import types as T
from greengage_tpu.ops import agg as agg_ops
from greengage_tpu.ops import hashing as dev_hash
from greengage_tpu.ops import join as join_ops
from greengage_tpu.ops import sort as sort_ops
from greengage_tpu.ops.batch import Batch
from greengage_tpu.ops.expr_eval import Evaluator
from greengage_tpu.storage import native as host_hash


# ---------------------------------------------------------------------------
# hashing: device must match host spec bit-for-bit
# ---------------------------------------------------------------------------

def test_device_hash_matches_host():
    vals = np.array([0, 1, -1, 2**40, -(2**40), 987654321, 2**63 - 1], dtype=np.int64)
    host = host_hash.hash_i64(vals)
    dev = np.asarray(dev_hash.hash_i64(jnp.asarray(vals)))
    assert np.array_equal(host, dev)
    hc = host_hash.hash_combine(host, host[::-1].copy())
    dc = np.asarray(dev_hash.hash_combine(jnp.asarray(host), jnp.asarray(host[::-1].copy())))
    assert np.array_equal(hc, dc)


def test_device_placement_matches_storage():
    vals = np.random.default_rng(0).integers(-(2**60), 2**60, 5000).astype(np.int64)
    host_seg = host_hash.hash_i64(vals) % np.uint32(8)
    dev_seg = np.asarray(dev_hash.segment_of(dev_hash.hash_i64(jnp.asarray(vals)), 8))
    assert np.array_equal(host_seg.astype(np.int32), dev_seg)


# ---------------------------------------------------------------------------
# expression evaluation
# ---------------------------------------------------------------------------

def _batch(**cols):
    arrs = {}
    valids = {}
    for k, v in cols.items():
        if isinstance(v, tuple):
            arrs[k] = jnp.asarray(v[0])
            valids[k] = jnp.asarray(v[1])
        else:
            arrs[k] = jnp.asarray(v)
    return Batch(arrs, valids)


def test_expr_arith_and_decimal():
    # price decimal(2), disc decimal(2): price * (1 - disc) — the Q1 kernel
    price = np.array([10050, 200], dtype=np.int64)     # 100.50, 2.00
    disc = np.array([10, 50], dtype=np.int64)          # 0.10, 0.50
    b = _batch(p=price, d=disc)
    dec2 = T.decimal(2)
    e = E.BinOp("*", E.ColRef("p", dec2),
                E.BinOp("-", E.Literal(100, dec2), E.ColRef("d", dec2), dec2),
                T.arith_result("*", dec2, dec2))
    v, valid = Evaluator(b).value(e)
    assert e.type.scale == 4
    # 100.50*0.90 = 90.45 -> 904500 at scale 4 ; 2.00*0.50=1.00 -> 10000
    assert list(np.asarray(v)) == [904500, 10000]
    assert valid is None


def test_expr_int_division_truncates():
    b = _batch(x=np.array([7, -7, 7], dtype=np.int32), y=np.array([2, 2, 0], dtype=np.int32))
    e = E.BinOp("/", E.ColRef("x", T.INT32), E.ColRef("y", T.INT32),
                T.arith_result("/", T.INT32, T.INT32))
    v, valid = Evaluator(b).value(e)
    assert list(np.asarray(v)[:2]) == [3, -3]
    assert not bool(np.asarray(valid)[2])  # div by zero -> NULL


def test_expr_3vl():
    x = (np.array([1, 0, 0], dtype=np.int32), np.array([True, True, False]))
    b = _batch(x=x)
    gt = E.Cmp(">", E.ColRef("x", T.INT32), E.Literal(0, T.INT32))
    # x > 0 AND false -> false even for NULL x? (false AND null = false)
    e = E.BoolOp("and", (gt, E.Literal(False, T.BOOL)))
    v, valid = Evaluator(b).value(e)
    res = np.asarray(v)
    assert not res.any()
    assert valid is None or np.asarray(valid).all()
    # NULL OR true = true
    e2 = E.BoolOp("or", (gt, E.Literal(True, T.BOOL)))
    v2, valid2 = Evaluator(b).value(e2)
    assert np.asarray(v2).all()
    assert valid2 is None or np.asarray(valid2).all()
    # IS NULL
    v3, _ = Evaluator(b).value(E.IsNull(E.ColRef("x", T.INT32)))
    assert list(np.asarray(v3)) == [False, False, True]


def test_expr_case_and_inlist():
    b = _batch(x=np.array([1, 2, 3], dtype=np.int32))
    e = E.Case(
        whens=((E.Cmp("=", E.ColRef("x", T.INT32), E.Literal(1, T.INT32)),
                E.Literal(10, T.INT32)),),
        else_=E.Literal(0, T.INT32), type=T.INT32)
    v, _ = Evaluator(b).value(e)
    assert list(np.asarray(v)) == [10, 0, 0]
    v2, _ = Evaluator(b).value(E.InList(E.ColRef("x", T.INT32), (1, 3)))
    assert list(np.asarray(v2)) == [True, False, True]


# ---------------------------------------------------------------------------
# hash aggregation vs pandas
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,groups", [(1000, 7), (5000, 230)])
def test_groupby_matches_pandas(n, groups):
    rng = np.random.default_rng(3)
    k1 = rng.integers(0, groups, n).astype(np.int64)
    k2 = rng.integers(0, 3, n).astype(np.int32)
    v = rng.integers(-100, 100, n).astype(np.int64)
    sel = rng.random(n) < 0.8

    keys = [agg_ops.KeySpec(jnp.asarray(k1), None, T.INT64),
            agg_ops.KeySpec(jnp.asarray(k2), None, T.INT32)]
    perm, boundary, sel_sorted, _ = agg_ops.group_sort(keys, jnp.asarray(sel))
    out_cap = n
    vals, valids, srcpos, total = agg_ops.sorted_group_aggregate(
        boundary, sel_sorted,
        [agg_ops.AggSpec("cnt", "count_star", None, None),
         agg_ops.AggSpec("s", "sum", jnp.asarray(v)[perm], None),
         agg_ops.AggSpec("mn", "min", jnp.asarray(v)[perm], None),
         agg_ops.AggSpec("av", "avg", jnp.asarray(v)[perm], None)],
        out_cap)
    G = int(total)
    rep = np.asarray(perm)[np.asarray(srcpos)[:G]]
    got = pd.DataFrame({
        "k1": k1[rep],
        "k2": k2[rep],
        "cnt": np.asarray(vals["cnt"])[:G],
        "s": np.asarray(vals["s"])[:G],
        "mn": np.asarray(vals["mn"])[:G],
        "av": np.asarray(vals["av"])[:G],
    }).sort_values(["k1", "k2"]).reset_index(drop=True)

    df = pd.DataFrame({"k1": k1[sel], "k2": k2[sel], "v": v[sel]})
    want = df.groupby(["k1", "k2"], as_index=False).agg(
        cnt=("v", "size"), s=("v", "sum"), mn=("v", "min"), av=("v", "mean")
    ).sort_values(["k1", "k2"]).reset_index(drop=True)

    assert len(got) == len(want)
    assert np.array_equal(got["k1"], want["k1"])
    assert np.array_equal(got["cnt"], want["cnt"])
    assert np.array_equal(got["s"], want["s"])
    assert np.array_equal(got["mn"], want["mn"])
    assert np.allclose(got["av"], want["av"])


def test_groupby_null_keys_merge():
    k = np.array([1, 1, 2, 0, 0], dtype=np.int64)
    kv = np.array([True, True, True, False, False])
    sel = np.ones(5, dtype=bool)
    perm, boundary, sel_sorted, _ = agg_ops.group_sort(
        [agg_ops.KeySpec(jnp.asarray(k), jnp.asarray(kv), T.INT64)],
        jnp.asarray(sel))
    assert int(np.asarray(boundary).sum()) == 3  # groups: 1, 2, NULL
    vals, _, srcpos, total = agg_ops.sorted_group_aggregate(
        boundary, sel_sorted,
        [agg_ops.AggSpec("c", "count_star", None, None)], 5)
    cnts = sorted(np.asarray(vals["c"])[:int(total)].tolist())
    assert cnts == [1, 2, 2]


def test_groupby_dead_rows_excluded():
    # dead rows must neither form groups nor leak into neighbors' aggregates
    k = np.array([5, 5, 7, 7, 9], dtype=np.int64)
    sel = np.array([True, False, True, True, False])
    perm, boundary, sel_sorted, _ = agg_ops.group_sort(
        [agg_ops.KeySpec(jnp.asarray(k), None, T.INT64)], jnp.asarray(sel))
    assert int(np.asarray(boundary).sum()) == 2  # groups 5 and 7 only
    v = jnp.asarray(np.array([1, 100, 2, 3, 100], dtype=np.int64))[perm]
    vals, _, srcpos, total = agg_ops.sorted_group_aggregate(
        boundary, sel_sorted, [agg_ops.AggSpec("s", "sum", v, None)], 5)
    got = sorted(np.asarray(vals["s"])[:int(total)].tolist())
    assert got == [1, 5]


def _sorted_rows(case: str):
    """-> (boundary, sel_sorted, values, out_cap) of key-sorted rows as
    `group_sort` leaves them: live rows first, a boundary on each group's
    first row."""
    n = 64
    sel = np.ones(n, bool)
    boundary = np.zeros(n, bool)
    out_cap = 16
    if case == "more_groups_than_slots":
        boundary[::2] = True                      # 32 groups, 16 slots
    elif case == "no_live_row":
        sel[:] = False
    elif case == "one_group":
        boundary[0] = True
    elif case == "every_row_a_group":
        boundary[:] = True
        out_cap = n
    elif case == "dead_rows_after_the_last_group":
        sel[40:] = False
        boundary[[0, 3, 4, 17, 39]] = True
    elif case == "slots_beyond_the_rows":
        boundary[[0, 1, 9, 63]] = True
        out_cap = 2 * n
    else:
        assert case == "last_group_ends_at_the_last_row"
        boundary[[0, 5, 50]] = True               # rows 50..63: no successor
    values = (np.arange(n, dtype=np.int64) * 2654435761) % 1009 - 500
    return boundary & sel, sel, values, out_cap


@pytest.mark.parametrize("form", ["search", "direct"])
@pytest.mark.parametrize("case", [
    "more_groups_than_slots", "no_live_row", "one_group", "every_row_a_group",
    "dead_rows_after_the_last_group", "slots_beyond_the_rows",
    "last_group_ends_at_the_last_row"])
def test_group_starts_forms_equal_a_numpy_reference(monkeypatch, case, form):
    """ISSUE 36: both forms of the group-start step, bit for bit: group g's
    first sorted row, n for an absent group, the first out_cap groups only;
    and the aggregate over them: exact total, sums, counts, srcpos."""
    boundary, sel, values, out_cap = _sorted_rows(case)
    n = len(sel)
    first = np.flatnonzero(boundary)
    want = np.full(out_cap, n, np.int32)
    want[:min(len(first), out_cap)] = first[:out_cap]
    csb = jnp.cumsum(jnp.asarray(boundary).astype(jnp.int32))
    got = agg_ops._starts_search(csb, out_cap) if form == "search" \
        else agg_ops._starts_direct(jnp.asarray(boundary), out_cap)
    assert got.dtype == jnp.int32
    assert np.array_equal(np.asarray(got), want)

    monkeypatch.setattr(agg_ops, "group_starts_direct",
                        lambda out_cap, n: form == "direct")
    vals, _, srcpos, total = agg_ops.sorted_group_aggregate(
        jnp.asarray(boundary), jnp.asarray(sel),
        [agg_ops.AggSpec("c", "count_star", None, None),
         agg_ops.AggSpec("s", "sum", jnp.asarray(values), None),
         agg_ops.AggSpec("mx", "max", jnp.asarray(values), None)], out_cap)
    assert int(total) == len(first)
    kept = min(len(first), out_cap)
    ends = np.append(first[1:], sel.sum())[:kept]
    spans = [values[a:b] for a, b in zip(first[:kept], ends)]
    assert np.array_equal(np.asarray(srcpos)[:kept], first[:kept])
    if len(first) > out_cap:      # the last slot also holds the dropped
        kept -= 1                 # groups' rows: the caller retries anyway
    assert np.asarray(vals["c"])[:kept].tolist() == [
        len(x) for x in spans[:kept]]
    assert np.asarray(vals["s"])[:kept].tolist() == [
        x.sum() for x in spans[:kept]]
    assert np.asarray(vals["mx"])[:kept].tolist() == [
        x.max() for x in spans[:kept]]


def test_group_starts_search_only_under_a_small_group_table():
    """The choice reads out_cap and n alone: a group table a quarter of the
    rows finds its starts in one pass (no `while` in the lowered text), one
    of 1/1024 of them by the search."""
    n = 1 << 14

    def lowered(out_cap):
        return jax.jit(lambda b, s, v: agg_ops.sorted_group_aggregate(
            b, s, [agg_ops.AggSpec("s", "sum", v, None)], out_cap)).lower(
                jax.ShapeDtypeStruct((n,), jnp.bool_),
                jax.ShapeDtypeStruct((n,), jnp.bool_),
                jax.ShapeDtypeStruct((n,), jnp.int64)).as_text()

    assert agg_ops.group_starts_direct(n // 4, n)
    assert not agg_ops.group_starts_direct(n // 1024, n)
    assert "stablehlo.while" not in lowered(n // 4)
    assert "stablehlo.while" in lowered(n // 1024)


# ---------------------------------------------------------------------------
# hash join vs pandas
# ---------------------------------------------------------------------------

def test_hash_join_pk_fk():
    rng = np.random.default_rng(5)
    nb, np_ = 300, 2000
    bkey = rng.permutation(1000)[:nb].astype(np.int64)   # unique build keys
    bval = rng.integers(0, 50, nb).astype(np.int64)
    pkey = rng.integers(0, 1000, np_).astype(np.int64)
    psel = rng.random(np_) < 0.9

    table = join_ops.build(
        [agg_ops.KeySpec(jnp.asarray(bkey), None, T.INT64)],
        jnp.ones(nb, dtype=bool), 1024, 8)
    assert not bool(table.overflow) and not bool(table.dup)
    matched, brow, walk_ov = join_ops.probe(
        table, [agg_ops.KeySpec(jnp.asarray(pkey), None, T.INT64)],
        jnp.asarray(psel), 8)
    assert not bool(walk_ov)

    bcols, bvalids = join_ops.gather_build_columns(
        {"bval": jnp.asarray(bval)}, {}, brow, matched)

    df = pd.merge(
        pd.DataFrame({"pkey": pkey[psel]}),
        pd.DataFrame({"bkey": bkey, "bval": bval}),
        left_on="pkey", right_on="bkey", how="inner")
    m = np.asarray(matched)
    assert m.sum() == len(df)
    got = np.sort(np.asarray(bcols["bval"])[m])
    assert np.array_equal(got, np.sort(df["bval"].to_numpy()))


def test_hash_join_duplicate_build_detected():
    bkey = np.array([1, 2, 2, 3], dtype=np.int64)
    table = join_ops.build(
        [agg_ops.KeySpec(jnp.asarray(bkey), None, T.INT64)],
        jnp.ones(4, dtype=bool), 16, 4)
    assert bool(table.dup)


def test_hash_join_null_keys_never_match():
    bkey = np.array([1, 2], dtype=np.int64)
    table = join_ops.build([agg_ops.KeySpec(jnp.asarray(bkey), None, T.INT64)],
                           jnp.ones(2, dtype=bool), 8, 4)
    pkey = np.array([1, 0], dtype=np.int64)
    pvalid = np.array([True, False])
    matched, _, _ = join_ops.probe(
        table, [agg_ops.KeySpec(jnp.asarray(pkey), jnp.asarray(pvalid), T.INT64)],
        jnp.ones(2, dtype=bool), 4)
    assert list(np.asarray(matched)) == [True, False]


def _multi_case(case):
    """-> (build keys, probe keys, probe sel, out_cap, left_outer): small
    duplicate-key joins whose pair counts a probe row sit at the edges of
    the expansion's one pass."""
    bkey = np.array([7, 3, 7, 5, 3, 7, 9, 5, 7, 3], dtype=np.int64)   # 7 x4, 3 x3, 5 x2, 9 x1
    sel = None
    out_cap, left_outer = 64, False
    if case == "zero_counts_at_the_front":
        pkey = [1, 2, 4, 7, 3, 9]
    elif case == "zero_counts_in_the_middle":
        pkey = [7, 1, 2, 4, 3, 6, 6, 5]
    elif case == "zero_counts_at_the_end":
        pkey = [5, 7, 9, 1, 2, 4, 6]
    elif case == "every_count_zero":
        pkey = [1, 2, 4, 6, 8]
    elif case == "one_probe_row":
        pkey = [7]
    elif case == "total_equals_out_cap":
        pkey, out_cap = [7, 1, 3, 9, 0, 7, 5, 5], 16          # 4+3+1+4+2+2
    elif case == "total_above_out_cap":
        pkey, out_cap = [3, 7, 1, 7, 5, 9, 7, 3], 16          # 22 pairs
    elif case == "left_outer_with_unmatched_selected_rows":
        pkey, left_outer = [1, 7, 2, 2, 3, 4, 9, 6], True
    elif case == "unselected_rows":
        pkey, left_outer = [7, 7, 1, 3, 2, 5, 7, 4], True
        sel = [False, True, False, True, True, False, True, False]
    else:
        assert case == "a_run_longer_than_out_cap"
        bkey = np.full(40, 7, dtype=np.int64)
        pkey, out_cap = [1, 7, 7, 3], 32                      # 0 + 40 + 40 + 0
    pkey = np.asarray(pkey, dtype=np.int64)
    sel = np.ones(len(pkey), bool) if sel is None else np.asarray(sel)
    return bkey, pkey, sel, out_cap, left_outer


@pytest.mark.parametrize("case", [
    "zero_counts_at_the_front", "zero_counts_in_the_middle",
    "zero_counts_at_the_end", "every_count_zero", "one_probe_row",
    "total_equals_out_cap", "total_above_out_cap",
    "left_outer_with_unmatched_selected_rows", "unselected_rows",
    "a_run_longer_than_out_cap"])
def test_pair_expansion_equals_a_loop_over_rows(case):
    """ISSUE 38: the expansion's slot-to-probe-row step (one pass:
    `ops/join.expand_slots`), bit for bit on every present slot, against a
    loop over the probe rows written by hand: the pairs in probe-row order,
    a run's build rows in row order, one unmatched slot for a LEFT join's
    selected row without a match; the exact total and the overflow flag
    whatever fits."""
    bkey, pkey, sel, out_cap, left_outer = _multi_case(case)
    want = []                                 # (probe row, build row, matched)
    for i, k in enumerate(pkey):
        if not sel[i]:
            continue
        hits = [b for b in range(len(bkey)) if bkey[b] == k]
        want += [(i, b, True) for b in hits]
        if not hits and left_outer:
            want.append((i, 0, False))
    kept = min(len(want), out_cap)

    # the step itself, on the pair counts
    count = np.zeros(len(pkey), np.int32)
    for i, _b, _m in want:
        count[i] += 1
    cum = jnp.cumsum(jnp.asarray(count).astype(jnp.int64))
    pr, ordinal = join_ops.expand_slots(cum, jnp.asarray(count), out_cap)
    assert pr.dtype == ordinal.dtype == jnp.int32
    assert np.asarray(pr)[:kept].tolist() == [w[0] for w in want[:kept]]
    starts = np.cumsum(count) - count
    assert np.asarray(ordinal)[:kept].tolist() == [
        j - starts[w[0]] for j, w in enumerate(want[:kept])]
    assert 0 <= int(pr.min()) and int(pr.max()) <= len(pkey) - 1

    # and the join around it
    table = join_ops.build_multi(
        [agg_ops.KeySpec(jnp.asarray(bkey), None, T.INT64)],
        jnp.ones(len(bkey), dtype=bool), 16, 8)
    present, prow, brow, matched, expand_ov, walk_ov, total = join_ops.probe_multi(
        table, [agg_ops.KeySpec(jnp.asarray(pkey), None, T.INT64)],
        jnp.asarray(sel), 8, out_cap, left_outer=left_outer)
    assert not bool(walk_ov)
    assert total.dtype == jnp.int64 and int(total) == len(want)
    assert bool(expand_ov) == (len(want) > out_cap)
    assert np.asarray(present).tolist() == [True] * kept + [False] * (out_cap - kept)
    got = list(zip(np.asarray(prow)[:kept].tolist(), np.asarray(brow)[:kept].tolist(),
                   np.asarray(matched)[:kept].tolist()))
    assert got == want[:kept]
    assert not np.asarray(matched)[kept:].any()
    assert 0 <= int(prow.min()) and int(prow.max()) <= len(pkey) - 1
    assert 0 <= int(brow.min()) and int(brow.max()) <= len(bkey) - 1


# ---------------------------------------------------------------------------
# sort
# ---------------------------------------------------------------------------

def test_sort_multi_key_desc_nulls():
    a = np.array([3, 1, 2, 1, 9], dtype=np.int64)
    av = np.array([True, True, True, True, False])
    bcol = np.array([1.5, -2.0, 0.0, 7.0, 0.0])
    sel = np.array([True, True, True, True, True])
    keys = [
        sort_ops.SortKey(jnp.asarray(a), jnp.asarray(av), T.INT64, desc=False),
        sort_ops.SortKey(jnp.asarray(bcol), None, T.FLOAT64, desc=True),
    ]
    perm, sel_sorted, _ = sort_ops.sort_batch(keys, jnp.asarray(sel), 5)
    order = np.asarray(perm)
    # asc on a (nulls last), desc on b: (1,7.0),(1,-2.0),(2,0.0),(3,1.5),(null)
    assert list(a[order][:4]) == [1, 1, 2, 3]
    assert list(bcol[order][:2]) == [7.0, -2.0]
    assert not av[order][4]


def test_sort_dead_rows_pushed_back_and_limit():
    x = np.array([5, 4, 3, 2, 1], dtype=np.int64)
    sel = np.array([True, False, True, False, True])
    keys = [sort_ops.SortKey(jnp.asarray(x), None, T.INT64)]
    perm, sel_sorted, _ = sort_ops.sort_batch(keys, jnp.asarray(sel), 5)
    assert list(np.asarray(sel_sorted)) == [True, True, True, False, False]
    assert list(x[np.asarray(perm)][:3]) == [1, 3, 5]
    cols, valids, s = sort_ops.limit({"x": jnp.asarray(x)[np.asarray(perm)]}, {}, sel_sorted, 2)
    assert list(np.asarray(cols["x"])) == [1, 3]


# ---------------------------------------------------------------------------
# packed group sort (stats-bounded keys in one uint64 operand)
# ---------------------------------------------------------------------------

def test_packed_group_sort_matches_unpacked():
    import pandas as pd

    rng = np.random.default_rng(9)
    n = 5000
    k1 = rng.integers(-37, 4000, n).astype(np.int64)
    k2 = rng.integers(0, 12, n).astype(np.int32)
    kv2 = rng.random(n) < 0.9          # k2 nullable
    v = rng.integers(-100, 100, n).astype(np.int64)
    sel = rng.random(n) < 0.8
    keys = [agg_ops.KeySpec(jnp.asarray(k1), None, T.INT64),
            agg_ops.KeySpec(jnp.asarray(k2), jnp.asarray(kv2), T.INT32)]
    bounds = [(-37, 3999), (0, 11)]
    assert agg_ops.pack_bits(bounds) is not None

    perm, boundary, sel_sorted, viol = agg_ops.group_sort(
        keys, jnp.asarray(sel), bounds)
    assert viol is not None and not bool(viol)
    vals, _, srcpos, total = agg_ops.sorted_group_aggregate(
        boundary, sel_sorted,
        [agg_ops.AggSpec("c", "count_star", None, None),
         agg_ops.AggSpec("s", "sum", jnp.asarray(v)[perm], None)], n)
    G = int(total)
    rep = np.asarray(perm)[np.asarray(srcpos)[:G]]
    got = pd.DataFrame({
        "k1": k1[rep], "k2": np.where(kv2[rep], k2[rep], -999),
        "c": np.asarray(vals["c"])[:G], "s": np.asarray(vals["s"])[:G],
    }).sort_values(["k1", "k2"]).reset_index(drop=True)
    df = pd.DataFrame({"k1": k1[sel], "k2": np.where(kv2, k2, -999)[sel],
                       "v": v[sel]})
    want = df.groupby(["k1", "k2"], as_index=False).agg(
        c=("v", "size"), s=("v", "sum")).sort_values(
        ["k1", "k2"]).reset_index(drop=True)
    assert len(got) == len(want)
    assert np.array_equal(got["k1"], want["k1"])
    assert np.array_equal(got["k2"], want["k2"])
    assert np.array_equal(got["c"], want["c"])
    assert np.array_equal(got["s"], want["s"])


def test_packed_group_sort_flags_bounds_violation():
    k = np.array([5, 100, 7], dtype=np.int64)   # 100 outside (0, 63)
    keys = [agg_ops.KeySpec(jnp.asarray(k), None, T.INT64)]
    _, _, _, viol = agg_ops.group_sort(
        keys, jnp.asarray(np.ones(3, bool)), [(0, 63)])
    assert bool(viol)
    # dead rows outside bounds do NOT trip the flag
    _, _, _, viol2 = agg_ops.group_sort(
        keys, jnp.asarray(np.array([True, False, True])), [(0, 63)])
    assert not bool(viol2)


def test_pack_bits_budget():
    assert agg_ops.pack_bits([(0, 2**40), (0, 2**30)]) is None  # > 63 bits
    assert agg_ops.pack_bits([(0, 2**40), (0, 2**20)]) is not None
    assert agg_ops.pack_bits([(0, 0)]) == 1
    assert agg_ops.pack_bits([None]) is None
    assert agg_ops.pack_bits([]) is None


def test_packed_join_matches_unpacked():
    rng = np.random.default_rng(21)
    nb, np_ = 500, 3000
    bkey = rng.permutation(5000)[:nb].astype(np.int64) - 250  # unique, offset
    pkey = rng.integers(-400, 5200, np_).astype(np.int64)
    bounds = [(int(bkey.min()), int(bkey.max()))]
    bs = [agg_ops.KeySpec(jnp.asarray(bkey), None, T.INT64)]
    ps = [agg_ops.KeySpec(jnp.asarray(pkey), None, T.INT64)]
    sel_b = jnp.ones(nb, bool)
    sel_p = jnp.ones(np_, bool)
    for kb in (None, bounds):
        table = join_ops.build(bs, sel_b, 2048, 64, kb)
        if kb is not None:
            assert table.bounds is not None and not bool(table.pack_viol)
        matched, brow, ov = join_ops.probe(table, ps, sel_p, 64)
        assert not bool(ov)
        want = np.isin(pkey, bkey)
        assert np.array_equal(np.asarray(matched), want)
        hit = np.asarray(matched)
        assert np.array_equal(bkey[np.asarray(brow)[hit]], pkey[hit])


def test_packed_join_build_violation_flag():
    bkey = np.array([1, 2, 99], dtype=np.int64)   # 99 outside stale (0, 10)
    bs = [agg_ops.KeySpec(jnp.asarray(bkey), None, T.INT64)]
    table = join_ops.build(bs, jnp.ones(3, bool), 64, 16, [(0, 10)])
    assert bool(table.pack_viol)


def test_packed_order_sort_matches_unpacked():
    from greengage_tpu.ops import sort as sort_ops

    rng = np.random.default_rng(33)
    n = 4000
    a = rng.integers(-50, 1000, n).astype(np.int64)
    b = rng.integers(0, 90, n).astype(np.int32)
    bv = rng.random(n) < 0.85
    sel = rng.random(n) < 0.9
    for desc_a, desc_b, nf in ((False, False, None), (True, False, None),
                               (False, True, True), (True, True, False)):
        keys = [sort_ops.SortKey(jnp.asarray(a), None, T.INT64, desc=desc_a),
                sort_ops.SortKey(jnp.asarray(b), jnp.asarray(bv), T.INT32,
                                 desc=desc_b, nulls_first=nf)]
        bounds = [(-50, 999), (0, 89)]
        p1, s1, viol = sort_ops.sort_batch(keys, jnp.asarray(sel), n, bounds)
        assert viol is not None and not bool(viol)
        p2, s2, v2 = sort_ops.sort_batch(keys, jnp.asarray(sel), n)
        assert v2 is None
        # same live set, identical key order (perm may differ only where
        # rows tie on every key INCLUDING null state -> compare key tuples)
        assert np.array_equal(np.asarray(s1), np.asarray(s2))
        k1a, k1b = a[np.asarray(p1)], b[np.asarray(p1)]
        k2a, k2b = a[np.asarray(p2)], b[np.asarray(p2)]
        v1b, v2b = bv[np.asarray(p1)], bv[np.asarray(p2)]
        live = np.asarray(s1)
        assert np.array_equal(k1a[live], k2a[live])
        assert np.array_equal(v1b[live], v2b[live])
        assert np.array_equal(k1b[live & v1b], k2b[live & v2b])
