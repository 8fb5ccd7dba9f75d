"""The data modules a configuration names, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Every configuration without a `data` key resolves to `tpch_data` and keys
its cached cluster exactly as before the key existed (the literals below
were written from that code), and the roofline's byte counts stay. The
first module with more tables, `tpch_dims`, makes tpch_data's three tables
array for array and adds supplier, part, nation and region whose keys every
referencing column finds; a configuration naming it builds, caches and
re-opens its cluster through `ensure_cluster`, and a join across the new
tables counts as pandas does. CPU: answers and counts, never a time.
"""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

import run  # noqa: E402
import tpch_data  # noqa: E402
import tpch_dims  # noqa: E402

SEED = 2147483659
INTERFACE = ("GENERATOR_VERSION", "TABLES", "DDL", "table_rows", "generate",
             "column_types")
SF10 = {"lineitem": 59999997, "orders": 15000000, "customer": 1500000}
SF5 = {"lineitem": 29999994, "orders": 7500000, "customer": 750000}
SF001 = {"lineitem": 59997, "orders": 15000, "customer": 1500}
# config -> (scale factor, segments, rows at that scale)
TODAY = {"tpch_sf10_1chip": (10, 1, SF10), "tpch_sf10_4chip": (10, 4, SF10),
         "tpch_q18_1chip": (5, 1, SF5), "tpch_refresh_1chip": (5, 1, SF5),
         "tpch_q13_1chip": (5, 1, SF5), "tpch_q4_1chip": (5, 1, SF5)}
# roofline metric -> bytes its query must read at its cells' scale
BYTES = {"q1_roofline": 2639999868, "q6_roofline": 1679999916,
         "q18_roofline": 698999904, "q13_roofline": 156000000,
         "q4_roofline": 599999904}


def _config(name: str) -> dict:
    return {"name": name, **run.read_json(BENCH, "configs", name + ".json")}


def _columns_equal(a, b) -> bool:
    if hasattr(a, "codes"):
        return list(a.vocab) == list(b.vocab) and np.array_equal(a.codes, b.codes)
    return np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name", sorted(TODAY))
def test_configs_keep_tpch_data_and_their_cache_key(name):
    """The six configurations name no module, so each is made by tpch_data,
    and its cache directory and sidecar are what they were: a cached
    cluster stays valid and `setup_s` measures the same work."""
    config = _config(name)
    assert "data" not in config
    assert run.data_module(config) is tpch_data
    sf, nseg, rows = TODAY[name]
    for at, suffix, want_rows in ((sf, "", rows), (0.01, "-sf0.01", SF001)):
        key = tpch_data.cache_key(tpch_data, config, SEED, at)
        assert key == (f"{name}-seed{SEED}{suffix}",
                       {"generator": "b1", "seed": SEED, "sf": at,
                        "numsegments": nseg, "rows": want_rows})


def _cells() -> list:
    return [w["name"] for w in run.read_json(ROOT, "BENCHMARK.json")["workloads"]]


@pytest.mark.parametrize("cell", _cells())
def test_every_cell_finds_its_data_module(cell):
    """The cell's data module provides the interface, counts every table
    it declares, and types every column the cell's queries read."""
    c = run.load_cell(cell)
    for attr in INTERFACE:
        assert hasattr(c.data, attr), attr
    assert set(c.data.table_rows(0.01)) == set(c.data.TABLES)
    types = c.data.column_types()
    for q in c.queries.values():
        for table, cols in q["reads"].items():
            assert table in c.data.TABLES
            assert all(col in types for col in cols)


def test_roofline_bytes_unchanged():
    """Each roofline's bytes at its cells' scale, as the parent counted."""
    bench = run.read_json(ROOT, "BENCHMARK.json")
    configs = {w["name"]: w["config"] for w in bench["workloads"]}
    seen = set()
    for m in bench["per_layer"]:
        if not m["name"].endswith("_roofline"):
            continue
        spec = run.read_json(BENCH, "metrics", m["name"] + ".json")
        reads = run.read_json(BENCH, "queries", spec["query"] + ".json")["reads"]
        for cell in m["workloads"]:
            config = _config(configs[cell])
            data = run.data_module(config)
            got = tpch_data.query_bytes(
                reads, data.table_rows(config["scale_factor"]), data.column_types())
            assert got == BYTES[m["name"]], (m["name"], cell)
        seen.add(m["name"])
    assert seen == set(BYTES)


@pytest.mark.parametrize("seed", [SEED, 7])
def test_dims_keep_the_three_tables(seed):
    base = tpch_data.generate(0.01, seed)
    dims = tpch_dims.generate(0.01, seed)
    assert set(dims) == set(tpch_dims.TABLES)
    for table, cols in base.items():
        assert list(dims[table]) == list(cols)
        for col, values in cols.items():
            assert _columns_equal(values, dims[table][col]), (table, col)


@pytest.fixture(scope="module")
def dims_data():
    return tpch_dims.generate(0.01, SEED)


def _rows(cols: dict) -> int:
    return len(next(iter(cols.values())))


def test_dims_rows_and_keys(dims_data):
    d = dims_data
    assert {t: _rows(c) for t, c in d.items()} == tpch_dims.table_rows(0.01)
    for t, cols in d.items():
        assert len({len(v) for v in cols.values()}) == 1, t
    li, s, p, n = d["lineitem"], d["supplier"], d["part"], d["nation"]
    assert np.isin(li["l_suppkey"], s["s_suppkey"]).all()
    assert np.isin(li["l_partkey"], p["p_partkey"]).all()
    assert np.isin(s["s_nationkey"], n["n_nationkey"]).all()
    assert np.isin(d["customer"]["c_nationkey"], n["n_nationkey"]).all()
    assert np.isin(n["n_regionkey"], d["region"]["r_regionkey"]).all()
    # the parameters of Q21, Q14 and Q19 find rows
    assert "SAUDI ARABIA" in n["n_name"]
    types = p["p_type"].decode()
    assert len(set(p["p_type"].vocab)) == 150
    assert any(t.startswith("PROMO") for t in types)
    assert len(set(p["p_container"].vocab)) == 40
    assert {"SM CASE", "MED BAG", "LG PKG"} <= set(p["p_container"].decode())


def test_dims_column_types():
    types = tpch_dims.column_types()
    assert {k: v for k, v in types.items() if k[:2] in ("l_", "o_", "c_")} \
        == tpch_data.column_types()
    assert types["s_suppkey"] == "bigint" and types["s_acctbal"] == "decimal"
    assert types["n_name"] == "text" and types["p_size"] == "int"


@pytest.fixture(scope="module")
def dims_cluster(tmp_path_factory, dims_data):
    """A configuration naming tpch_dims at SF 0.01 on two segments, built
    through ensure_cluster, then found in the cache by a second call."""
    config = {"name": "dims_test", "scale_factor": 0.01, "numsegments": 2,
              "data": "tpch_dims"}
    data = run.data_module(config)
    assert data is tpch_dims
    cache = str(tmp_path_factory.mktemp("cache"))
    logs = []
    calls = [tpch_data.ensure_cluster(data, config, SEED, [], cache, {},
                                      logs.append)
             for _ in range(2)]
    return {"calls": calls, "logs": logs, "data": dims_data}


def test_dims_cluster_builds_then_is_found(dims_cluster):
    (root1, meta1, _a1), (root2, meta2, _a2) = dims_cluster["calls"]
    assert root1 == root2 and meta1 == meta2
    assert meta1["generator"] == tpch_dims.GENERATOR_VERSION
    assert meta1["rows"] == tpch_dims.table_rows(0.01)
    builds = [m for m in dims_cluster["logs"] if m.startswith("no usable cluster")]
    assert len(builds) == 1


def test_dims_cluster_counts_and_joins(dims_cluster):
    import pandas as pd

    import greengage_tpu

    root, meta, _answers = dims_cluster["calls"][1]
    db = greengage_tpu.connect(tpch_data.working_copy(root), numsegments=2)
    try:
        assert tpch_data.counts_match(db, meta["rows"])
        # a replicated table holds all 25 rows on each of the two segments
        assert not tpch_data.counts_match(db, {"nation": 50})
        assert not tpch_data.counts_match(db, {**meta["rows"], "supplier": 1})
        got = db.sql(
            "select n_name, count(*) from lineitem, supplier, nation"
            " where l_suppkey = s_suppkey and s_nationkey = n_nationkey"
            " group by n_name order by n_name").rows()
    finally:
        db.close()
    d = dims_cluster["data"]
    nation = np.asarray(d["nation"]["n_name"], dtype=object)
    supp_nation = d["supplier"]["s_nationkey"][d["lineitem"]["l_suppkey"] - 1]
    want = pd.Series(nation[supp_nation]).value_counts().sort_index()
    assert [(str(k), int(v)) for k, v in got] == list(zip(want.index, want.tolist()))
