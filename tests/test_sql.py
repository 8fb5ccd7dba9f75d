"""End-to-end SQL regression tests on the 8-segment virtual cluster —
the pg_regress greengage_schedule analog, with pandas as oracle."""

import numpy as np
import pandas as pd
import pytest

import greengage_tpu
from greengage_tpu.exec.executor import QueryError
from greengage_tpu.utils import tpch


@pytest.fixture(scope="module")
def db(devices8):
    d = greengage_tpu.connect(numsegments=8)
    tpch.load(d, sf=0.002)
    return d


@pytest.fixture(scope="module")
def oracle():
    return tpch.to_pandas(tpch.generate(0.002))


# ---------------------------------------------------------------------------
# basics
# ---------------------------------------------------------------------------

def test_basic_select_where(db, oracle):
    r = db.sql("select l_orderkey, l_quantity from lineitem "
               "where l_quantity > 45 order by l_orderkey, l_quantity")
    li = oracle["lineitem"]
    want = li[li.l_quantity > 45].sort_values(["l_orderkey", "l_quantity"])
    assert len(r) == len(want)
    got = r.to_pandas()
    assert np.array_equal(got["l_orderkey"], want["l_orderkey"])
    assert np.allclose(got["l_quantity"], want["l_quantity"])


def test_projection_arithmetic(db, oracle):
    r = db.sql("select l_orderkey, l_extendedprice * (1 - l_discount) as rev "
               "from lineitem where l_orderkey <= 20 order by 1, 2")
    li = oracle["lineitem"]
    want = li[li.l_orderkey <= 20].copy()
    want["rev"] = want.l_extendedprice * (1 - want.l_discount)
    want = want.sort_values(["l_orderkey", "rev"])
    got = r.to_pandas()
    assert len(got) == len(want)
    assert np.allclose(got["rev"], want["rev"], atol=1e-6)


def test_limit_offset(db, oracle):
    r = db.sql("select o_orderkey from orders order by o_orderkey limit 5 offset 3")
    assert [row[0] for row in r.rows()] == [4, 5, 6, 7, 8]


def test_distinct(db, oracle):
    r = db.sql("select distinct l_returnflag from lineitem order by l_returnflag")
    assert [row[0] for row in r.rows()] == ["A", "N", "R"]


def test_in_between_like(db, oracle):
    r = db.sql("select count(*) from lineitem where l_shipmode in ('AIR', 'RAIL')")
    li = oracle["lineitem"]
    assert r.rows()[0][0] == int(li.l_shipmode.isin(["AIR", "RAIL"]).sum())
    r = db.sql("select count(*) from orders where o_orderpriority like '1%'")
    o = oracle["orders"]
    assert r.rows()[0][0] == int(o.o_orderpriority.str.startswith("1").sum())
    r = db.sql("select count(*) from lineitem where l_quantity between 10 and 20")
    assert r.rows()[0][0] == int(li.l_quantity.between(10, 20).sum())


def test_case_expr(db, oracle):
    r = db.sql(
        "select sum(case when l_returnflag = 'A' then 1 else 0 end) from lineitem")
    li = oracle["lineitem"]
    assert r.rows()[0][0] == int((li.l_returnflag == "A").sum())


def test_extract_year(db, oracle):
    r = db.sql("select extract(year from o_orderdate) y, count(*) c "
               "from orders group by 1 order by 1")
    o = oracle["orders"]
    want = o.groupby(pd.to_datetime(o.o_orderdate, unit="D").dt.year).size()
    got = r.to_pandas()
    assert list(got["y"]) == list(want.index)
    assert list(got["c"]) == list(want.values)


# ---------------------------------------------------------------------------
# TPC-H queries
# ---------------------------------------------------------------------------

def test_q1_pricing_summary(db, oracle):
    r = db.sql("""
      select l_returnflag, l_linestatus,
             sum(l_quantity) as sum_qty,
             sum(l_extendedprice) as sum_base_price,
             sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
             sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
             avg(l_quantity) as avg_qty,
             avg(l_extendedprice) as avg_price,
             avg(l_discount) as avg_disc,
             count(*) as count_order
      from lineitem
      where l_shipdate <= date '1998-12-01' - interval '90' day
      group by l_returnflag, l_linestatus
      order by l_returnflag, l_linestatus
    """)
    li = oracle["lineitem"]
    cutoff = (np.datetime64("1998-12-01") - np.timedelta64(90, "D")
              - np.datetime64("1970-01-01")).astype(int)
    f = li[li.l_shipdate <= cutoff]
    want = f.groupby(["l_returnflag", "l_linestatus"]).agg(
        sum_qty=("l_quantity", "sum"),
        sum_base_price=("l_extendedprice", "sum"),
        avg_qty=("l_quantity", "mean"),
        avg_disc=("l_discount", "mean"),
        count_order=("l_quantity", "size"),
    ).reset_index().sort_values(["l_returnflag", "l_linestatus"])
    got = r.to_pandas()
    assert len(got) == len(want)
    assert list(got.l_returnflag) == list(want.l_returnflag)
    assert np.allclose(got.sum_qty, want.sum_qty)
    assert np.allclose(got.sum_base_price, want.sum_base_price)
    assert np.allclose(got.avg_qty, want.avg_qty, atol=1e-9)
    assert np.allclose(got.avg_disc, want.avg_disc, atol=1e-9)
    assert np.array_equal(got.count_order, want.count_order)
    disc = f.l_extendedprice * (1 - f.l_discount)
    want_disc = disc.groupby([f.l_returnflag, f.l_linestatus]).sum().reset_index(drop=True)
    assert np.allclose(np.sort(got.sum_disc_price), np.sort(want_disc), rtol=1e-12)


def test_q6_forecast_revenue(db, oracle):
    r = db.sql("""
      select sum(l_extendedprice * l_discount) as revenue
      from lineitem
      where l_shipdate >= date '1994-01-01'
        and l_shipdate < date '1994-01-01' + interval '1' year
        and l_discount between 0.05 and 0.07
        and l_quantity < 24
    """)
    li = oracle["lineitem"]
    lo = (np.datetime64("1994-01-01") - np.datetime64("1970-01-01")).astype(int)
    hi = (np.datetime64("1995-01-01") - np.datetime64("1970-01-01")).astype(int)
    f = li[(li.l_shipdate >= lo) & (li.l_shipdate < hi)
           & (li.l_discount >= 0.05) & (li.l_discount <= 0.07) & (li.l_quantity < 24)]
    want = (f.l_extendedprice * f.l_discount).sum()
    got = r.rows()[0][0]
    assert got == pytest.approx(want, rel=1e-12)


def test_q3_shipping_priority(db, oracle):
    r = db.sql("""
      select l_orderkey,
             sum(l_extendedprice * (1 - l_discount)) as revenue,
             o_orderdate, o_shippriority
      from customer, orders, lineitem
      where c_mktsegment = 'BUILDING'
        and c_custkey = o_custkey and l_orderkey = o_orderkey
        and o_orderdate < date '1995-03-15' and l_shipdate > date '1995-03-15'
      group by l_orderkey, o_orderdate, o_shippriority
      order by revenue desc, o_orderdate limit 10
    """)
    c, o, li = oracle["customer"], oracle["orders"], oracle["lineitem"]
    cut = (np.datetime64("1995-03-15") - np.datetime64("1970-01-01")).astype(int)
    j = li[li.l_shipdate > cut].merge(
        o[(o.o_orderdate < cut)], left_on="l_orderkey", right_on="o_orderkey"
    ).merge(c[c.c_mktsegment == "BUILDING"], left_on="o_custkey", right_on="c_custkey")
    j["revenue"] = j.l_extendedprice * (1 - j.l_discount)
    want = j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"], as_index=False) \
        .agg(revenue=("revenue", "sum")) \
        .sort_values(["revenue", "o_orderdate"], ascending=[False, True]).head(10)
    got = r.to_pandas()
    assert len(got) == len(want)
    assert np.allclose(got.revenue, want.revenue, rtol=1e-12)
    assert list(got.l_orderkey) == list(want.l_orderkey)


def test_q5_local_supplier_volume(db, oracle):
    r = db.sql("""
      select n_name, sum(l_extendedprice * (1 - l_discount)) as revenue
      from customer, orders, lineitem, supplier, nation, region
      where c_custkey = o_custkey and l_orderkey = o_orderkey
        and l_suppkey = s_suppkey and c_nationkey = s_nationkey
        and s_nationkey = n_nationkey and n_regionkey = r_regionkey
        and r_name = 'ASIA'
        and o_orderdate >= date '1994-01-01'
        and o_orderdate < date '1994-01-01' + interval '1' year
      group by n_name
      order by revenue desc
    """)
    c, o, li = oracle["customer"], oracle["orders"], oracle["lineitem"]
    s, n, reg = oracle["supplier"], oracle["nation"], oracle["region"]
    lo = (np.datetime64("1994-01-01") - np.datetime64("1970-01-01")).astype(int)
    hi = (np.datetime64("1995-01-01") - np.datetime64("1970-01-01")).astype(int)
    j = (o[(o.o_orderdate >= lo) & (o.o_orderdate < hi)]
         .merge(c, left_on="o_custkey", right_on="c_custkey")
         .merge(li, left_on="o_orderkey", right_on="l_orderkey")
         .merge(s, left_on=["l_suppkey", "c_nationkey"],
                right_on=["s_suppkey", "s_nationkey"])
         .merge(n, left_on="s_nationkey", right_on="n_nationkey")
         .merge(reg[reg.r_name == "ASIA"], left_on="n_regionkey",
                right_on="r_regionkey"))
    j["revenue"] = j.l_extendedprice * (1 - j.l_discount)
    want = j.groupby("n_name", as_index=False).agg(revenue=("revenue", "sum")) \
        .sort_values("revenue", ascending=False)
    got = r.to_pandas()
    assert len(got) == len(want)
    assert list(got.n_name) == list(want.n_name)
    assert np.allclose(got.revenue, want.revenue, rtol=1e-12)


# ---------------------------------------------------------------------------
# joins + NULL semantics + errors
# ---------------------------------------------------------------------------

def test_explicit_join_syntax(db, oracle):
    r = db.sql("""
      select o_orderkey, c_name from orders
      join customer on c_custkey = o_custkey
      where o_orderkey <= 5 order by o_orderkey
    """)
    o, c = oracle["orders"], oracle["customer"]
    want = o[o.o_orderkey <= 5].merge(c, left_on="o_custkey", right_on="c_custkey") \
        .sort_values("o_orderkey")
    got = r.to_pandas()
    assert list(got.c_name) == list(want.c_name)


def test_left_join_nulls(db):
    db.sql("create table lj_a (k int, v int) distributed by (k);"
           "create table lj_b (k int, w int) distributed by (k);"
           "insert into lj_a values (1, 10), (2, 20), (3, 30);"
           "insert into lj_b values (1, 100), (3, 300)")
    r = db.sql("select a.k, w from lj_a a left join lj_b b on a.k = b.k order by a.k")
    assert r.rows() == [(1, 100), (2, None), (3, 300)]


def test_duplicate_build_keys_multi_match(db):
    db.sql("create table dup_b (k int, v int) distributed by (k);"
           "insert into dup_b values (1, 1), (1, 2), (2, 3), (3, 4), (4, 5), "
           "(5, 6), (6, 7), (7, 8)")
    # self-join on a duplicated key: k=1 appears twice on the build side
    r = db.sql("select a.v av, b.v bv from dup_b a join dup_b b on a.k = b.k "
               "order by av, bv")
    df = pd.DataFrame({"k": [1, 1, 2, 3, 4, 5, 6, 7],
                       "v": [1, 2, 3, 4, 5, 6, 7, 8]})
    want = df.merge(df, on="k").sort_values(["v_x", "v_y"])
    got = r.to_pandas()
    assert len(got) == len(want) == 10  # k=1 expands 2x2, six other keys 1x1
    assert list(got.av) == list(want.v_x)
    assert list(got.bv) == list(want.v_y)
    # dist key == join key, so the planner chose the unique path first; the
    # runtime dup flag must have forced the multi re-plan (retry pinned)
    assert any(k[0].endswith("#multi") for k in db.executor.programs._plan_cache)
    # repeat must hit the cached multi plan, not re-fail on the stale program
    r2 = db.sql("select a.v av, b.v bv from dup_b a join dup_b b on a.k = b.k "
                "order by av, bv")
    assert len(r2) == 10


def test_fk_fk_join_planned_multi_directly(db, oracle):
    # join on a non-key column both sides (c_nationkey = s_nationkey):
    # neither side looks unique at plan time -> multi-match CSR join chosen
    # directly (no runtime retry involved)
    r = db.sql("select count(*) from customer, supplier "
               "where c_nationkey = s_nationkey")
    c, s = oracle["customer"], oracle["supplier"]
    want = len(c.merge(s, left_on="c_nationkey", right_on="s_nationkey"))
    assert r.rows()[0][0] == want


def test_left_join_duplicate_build(db):
    db.sql("create table ml_a (k int, v int) distributed by (k);"
           "create table ml_b (k int, w int) distributed by (k);"
           "insert into ml_a values (1, 10), (2, 20), (3, 30);"
           "insert into ml_b values (1, 100), (1, 101), (3, 300)")
    r = db.sql("select a.k, w from ml_a a left join ml_b b on a.k = b.k "
               "order by a.k, w nulls last")
    assert r.rows() == [(1, 100), (1, 101), (2, None), (3, 300)]


def test_having(db, oracle):
    r = db.sql("select l_returnflag, count(*) c from lineitem "
               "group by l_returnflag having count(*) > 100 order by 1")
    li = oracle["lineitem"]
    want = li.groupby("l_returnflag").size()
    want = want[want > 100]
    got = r.to_pandas()
    assert list(got.l_returnflag) == list(want.index)
    assert list(got.c) == list(want.values)


def test_scalar_agg_empty_result(db):
    r = db.sql("select count(*), sum(l_quantity) from lineitem where l_quantity < 0")
    assert r.rows() == [(0, None)]


def test_distinct_aggregates(db, oracle):
    li = oracle["lineitem"]
    r = db.sql("select count(distinct l_suppkey) from lineitem")
    assert r.rows()[0][0] == li.l_suppkey.nunique()
    r = db.sql("select l_returnflag, count(distinct l_shipmode) c from lineitem "
               "group by l_returnflag order by l_returnflag")
    want = li.groupby("l_returnflag").l_shipmode.nunique()
    got = r.to_pandas()
    assert list(got.c) == list(want.values)
