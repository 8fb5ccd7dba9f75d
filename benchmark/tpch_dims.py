"""TPC-H data with four more tables: tpch_data's lineitem, orders and
customer, array for array, and supplier, part, nation and region.

A configuration names this module with `"data": "tpch_dims"`. The three
tables are `tpch_data.generate(sf, seed)` itself; the four added here are
drawn from a second stream, `np.random.default_rng([seed, 1])`, so no draw
of the three moves. Value ranges are clause 4.2.3's as
`greengage_tpu/utils/tpch.py` makes them, copied here so that the data does
not depend on the code it measures: the 25 nations with their regions, the
five regions, `s_suppkey` 1..SF x 10,000 and `p_partkey` 1..SF x 200,000
(the ranges `tpch_data` draws `l_suppkey` and `l_partkey` from, so every
line finds its supplier and its part), `s_nationkey` uniform over the 25
nations. `p_type` and `p_container` are clause 4.2.2.13's words: 150 types
(6 x 5 x 5 syllables) and 40 containers (5 x 8).

Where the data departs from dbgen (clause 4.2.3), which a statement over
these tables meets:

- `partsupp` is not made. `tpch_data` draws `l_suppkey` independently of
  `l_partkey`, so no partsupp could hold lineitem's (part, supplier) pairs
  as dbgen's does (each line's supplier is one of its part's four), and Q9
  and Q20, which join partsupp to lineitem, would read a broken schema.
- `o_custkey` is uniform over all customers. dbgen never takes a multiple
  of three, so a third of its customers place no order (Q13's zero group).
- `o_comment` is one of 500 strings `order comment <n>`. dbgen's follows
  clause 4.2.2.10's grammar, and ~1 % of it matches "special ... requests".
- `o_orderkey` is dense, 1..n. dbgen's is sparse: 8 keys of every 32.
- `l_commitdate` is `l_shipdate` +- 30 days. dbgen's is `o_orderdate` +
  30..90, so ~75 % of lines are late here against its ~63 % (Q4, Q21).
- `c_phone` and `s_phone` are one of 1,000 strings `phone <n>`. dbgen's
  begin with the nation key + 10, which Q22 reads.
- The lines of an order are a seeded permutation of the fixed multiset
  1..7; `l_partkey` and `l_suppkey` are uniform.
- `p_name` is one of 2,000 strings `part name <n>`. dbgen's are five of
  clause 4.2.2.13's 92 colours (Q9's `%green%`, Q20's `forest%`).
- `p_brand` is drawn apart from `p_mfgr` (dbgen's Brand#MN takes M from
  the manufacturer), and `p_retailprice` uniform in 900.00..2000.00
  (dbgen's is a formula of the key).
- Comments and addresses come from small vocabularies.
"""

from __future__ import annotations

import numpy as np

import tpch_data

# the three tables' generator with this module's four tables beside it
GENERATOR_VERSION = tpch_data.GENERATOR_VERSION + "+dims1"
TABLES = tpch_data.TABLES + ("supplier", "part", "nation", "region")

NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1), ("EGYPT", 4),
    ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3), ("INDIA", 2), ("INDONESIA", 2),
    ("IRAN", 4), ("IRAQ", 4), ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0),
    ("MOROCCO", 0), ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3), ("UNITED KINGDOM", 3),
    ("UNITED STATES", 1),
]
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
TYPE_WORDS = (["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"],
              ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"],
              ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"])
CONTAINER_WORDS = (["SM", "LG", "MED", "JUMBO", "WRAP"],
                   ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"])
TYPES = [f"{a} {b} {c}" for a in TYPE_WORDS[0] for b in TYPE_WORDS[1]
         for c in TYPE_WORDS[2]]
CONTAINERS = [f"{a} {b}" for a in CONTAINER_WORDS[0] for b in CONTAINER_WORDS[1]]

DDL = tpch_data.DDL + """
create table if not exists supplier (
  s_suppkey bigint, s_name text, s_address text, s_nationkey int,
  s_phone text, s_acctbal decimal(15,2), s_comment text
) distributed by (s_suppkey);
create table if not exists part (
  p_partkey bigint, p_name text, p_mfgr text, p_brand text, p_type text,
  p_size int, p_container text, p_retailprice decimal(15,2), p_comment text
) distributed by (p_partkey);
create table if not exists nation (
  n_nationkey int, n_name text, n_regionkey int, n_comment text
) distributed replicated;
create table if not exists region (
  r_regionkey int, r_name text, r_comment text
) distributed replicated;
"""


def column_types() -> dict[str, str]:
    return tpch_data.column_types(DDL)


def table_rows(sf: float) -> dict[str, int]:
    return {**tpch_data.table_rows(sf),
            "supplier": max(int(10_000 * sf), 3),
            "part": max(int(200_000 * sf), 5),
            "nation": len(NATIONS), "region": len(REGIONS)}


def generate(sf: float, seed: int) -> dict[str, dict]:
    """-> {table: {column: array | Coded}}, decimals as scaled int64."""
    from greengage_tpu.types import Coded

    out = tpch_data.generate(sf, seed)
    rng = np.random.default_rng([seed, 1])
    rows = table_rows(sf)
    n_supp, n_part = rows["supplier"], rows["part"]

    def dec(n, lo, hi):
        return rng.integers(int(lo * 100), int(hi * 100) + 1, n).astype(np.int64)

    def choice(n, values):
        return Coded(list(values),
                     rng.integers(0, len(values), n).astype(np.int32))

    def vocab(n, prefix, k):
        return choice(n, [f"{prefix}{i}" for i in range(k)])

    out["nation"] = {
        "n_nationkey": np.arange(len(NATIONS), dtype=np.int32),
        "n_name": [name for name, _ in NATIONS],
        "n_regionkey": np.array([r for _, r in NATIONS], dtype=np.int32),
        "n_comment": vocab(len(NATIONS), "nation comment ", 10),
    }
    out["region"] = {
        "r_regionkey": np.arange(len(REGIONS), dtype=np.int32),
        "r_name": list(REGIONS),
        "r_comment": vocab(len(REGIONS), "region comment ", 5),
    }
    out["supplier"] = {
        "s_suppkey": np.arange(1, n_supp + 1, dtype=np.int64),
        "s_name": [f"Supplier#{i:09d}" for i in range(1, n_supp + 1)],
        "s_address": vocab(n_supp, "addr ", 500),
        "s_nationkey": rng.integers(0, len(NATIONS), n_supp).astype(np.int32),
        "s_phone": vocab(n_supp, "phone ", 1000),
        "s_acctbal": dec(n_supp, -999.99, 9999.99),
        "s_comment": vocab(n_supp, "supp comment ", 200),
    }
    out["part"] = {
        "p_partkey": np.arange(1, n_part + 1, dtype=np.int64),
        "p_name": vocab(n_part, "part name ", 2000),
        "p_mfgr": choice(n_part, [f"Manufacturer#{i}" for i in range(1, 6)]),
        "p_brand": choice(n_part, [f"Brand#{i}{j}" for i in range(1, 6)
                                   for j in range(1, 6)]),
        "p_type": choice(n_part, TYPES),
        "p_size": rng.integers(1, 51, n_part).astype(np.int32),
        "p_container": choice(n_part, CONTAINERS),
        "p_retailprice": dec(n_part, 900.0, 2000.0),
        "p_comment": vocab(n_part, "part comment ", 100),
    }
    return out
