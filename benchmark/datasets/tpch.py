"""Dataset `tpch`: the benchmark's own TPC-H generator and loader.

The yardstick's copy of `citus_tpu/ingest/tpch.py` (schemas, value
lists, `generate_tables`, the classic Citus layout), taken at PR 25 so
that a later change to the program cannot change what the benchmark
measures or what its reference is computed from.  Two things differ
from the original, both because a run's `--seed` must give other
inputs without giving other shapes (one Q3 shape costs the chip's
compiler about five minutes, and feed capacities follow row counts and
filter selectivities to 128 rows):

* **structure** — row counts, keys, dates, flags, segments, every
  column a TPC-H filter, join or group key reads — is drawn from the
  constant `STRUCTURE_SEED` below, never from `--seed`;
* **measures** are drawn from `--seed`: one seeded permutation per
  table moves the measure tuples (`MEASURES`) between rows.  Every
  answer changes with the seed; every count, extent, dictionary and
  selectivity stays what it was, so every seed runs the same programs.

The loader feeds these arrays to the program's ingest path exactly as
`load_into_session` does (typed numpy columns through
`ingest.copy_from._ingest_batch(pre_typed=True)`): the program has no
public entry that takes arrays, and `load_into_session` itself can only
load the program's own generator's rows (PERF.md, Open questions).
"""

from __future__ import annotations

import time

import numpy as np

# bump when generate() would give other rows for the same parameters:
# cached data directories and references of another version are refused
GENERATOR_VERSION = 1
# what the structure is drawn from: a constant of the yardstick, and no
# configuration's knob
STRUCTURE_SEED = 0

SCHEMAS = {
    "region": """create table region (
        r_regionkey int, r_name text, r_comment text)""",
    "nation": """create table nation (
        n_nationkey int, n_name text, n_regionkey int, n_comment text)""",
    "supplier": """create table supplier (
        s_suppkey bigint, s_name text, s_address text, s_nationkey int,
        s_phone text, s_acctbal double precision, s_comment text)""",
    "customer": """create table customer (
        c_custkey bigint, c_name text, c_address text, c_nationkey int,
        c_phone text, c_acctbal double precision, c_mktsegment text,
        c_comment text)""",
    "part": """create table part (
        p_partkey bigint, p_name text, p_mfgr text, p_brand text,
        p_type text, p_size int, p_container text,
        p_retailprice double precision, p_comment text)""",
    "partsupp": """create table partsupp (
        ps_partkey bigint, ps_suppkey bigint, ps_availqty int,
        ps_supplycost double precision, ps_comment text)""",
    "orders": """create table orders (
        o_orderkey bigint, o_custkey bigint, o_orderstatus text,
        o_totalprice double precision, o_orderdate date,
        o_orderpriority text, o_clerk text, o_shippriority int,
        o_comment text)""",
    "lineitem": """create table lineitem (
        l_orderkey bigint, l_partkey bigint, l_suppkey bigint,
        l_linenumber int, l_quantity double precision,
        l_extendedprice double precision, l_discount double precision,
        l_tax double precision, l_returnflag text, l_linestatus text,
        l_shipdate date, l_commitdate date, l_receiptdate date,
        l_shipinstruct text, l_shipmode text, l_comment text)""",
}

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [  # (name, regionkey) — the real 25
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
SHIPINSTRUCT = ["DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN"]
TYPES_1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPES_2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
TYPES_3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
CONTAINERS = ["SM CASE", "SM BOX", "MED BAG", "MED BOX", "LG CASE",
              "LG BOX", "WRAP CASE", "JUMBO PKG"]
COLORS = ["almond", "azure", "blue", "chocolate", "coral", "forest",
          "green", "ivory", "linen", "magenta", "midnight", "olive",
          "red", "royal", "salmon", "steel", "tan", "violet", "white"]

_EPOCH_1992 = 8035   # days('1992-01-01')
_ORDER_DATE_RANGE = 2406  # through 1998-08-02


def table_rows(sf: float) -> dict[str, int]:
    return {
        "region": 5,
        "nation": 25,
        "supplier": max(int(10_000 * sf), 10),
        "customer": max(int(150_000 * sf), 30),
        "part": max(int(200_000 * sf), 40),
        "partsupp": max(int(200_000 * sf), 40) * 4,
        "orders": max(int(1_500_000 * sf), 150),
        # lineitems: 1..7 per order, avg ≈ 4
    }


def generate_tables(sf: float, seed: int = 0) -> dict[str, dict[str, np.ndarray]]:
    """→ {table: {column: np array}} with str columns as python-object
    arrays.  The program's generator, line for line: here it draws the
    STRUCTURE, from `STRUCTURE_SEED`."""
    rng = np.random.default_rng(seed)
    counts = table_rows(sf)
    out: dict[str, dict[str, np.ndarray]] = {}

    out["region"] = {
        "r_regionkey": np.arange(5, dtype=np.int32),
        "r_name": np.array(REGIONS, dtype=object),
        "r_comment": np.array([f"region comment {i}" for i in range(5)],
                              dtype=object),
    }
    out["nation"] = {
        "n_nationkey": np.arange(25, dtype=np.int32),
        "n_name": np.array([n for n, _ in NATIONS], dtype=object),
        "n_regionkey": np.array([r for _, r in NATIONS], dtype=np.int32),
        "n_comment": np.array([f"nation comment {i}" for i in range(25)],
                              dtype=object),
    }

    ns = counts["supplier"]
    out["supplier"] = {
        "s_suppkey": np.arange(1, ns + 1, dtype=np.int64),
        "s_name": np.array([f"Supplier#{i:09d}" for i in range(1, ns + 1)],
                           dtype=object),
        "s_address": np.array([f"addr s{i}" for i in range(ns)], dtype=object),
        "s_nationkey": rng.integers(0, 25, ns).astype(np.int32),
        "s_phone": np.array([f"{i % 35 + 10}-{i % 999:03d}" for i in range(ns)],
                            dtype=object),
        "s_acctbal": np.round(rng.uniform(-999.99, 9999.99, ns), 2),
        "s_comment": np.array([f"supplier comment {i}" for i in range(ns)],
                              dtype=object),
    }

    nc = counts["customer"]
    out["customer"] = {
        "c_custkey": np.arange(1, nc + 1, dtype=np.int64),
        "c_name": np.array([f"Customer#{i:09d}" for i in range(1, nc + 1)],
                           dtype=object),
        "c_address": np.array([f"addr c{i}" for i in range(nc)], dtype=object),
        "c_nationkey": rng.integers(0, 25, nc).astype(np.int32),
        "c_phone": np.array([f"{i % 35 + 10}-{i % 999:03d}"
                             for i in range(nc)], dtype=object),
        "c_acctbal": np.round(rng.uniform(-999.99, 9999.99, nc), 2),
        "c_mktsegment": np.array([SEGMENTS[i] for i in
                                  rng.integers(0, 5, nc)], dtype=object),
        "c_comment": np.array([f"customer comment {i}" for i in range(nc)],
                              dtype=object),
    }

    npart = counts["part"]
    type_full = np.array(
        [f"{TYPES_1[a]} {TYPES_2[b]} {TYPES_3[c]}"
         for a, b, c in zip(rng.integers(0, 6, npart),
                            rng.integers(0, 5, npart),
                            rng.integers(0, 5, npart))], dtype=object)
    out["part"] = {
        "p_partkey": np.arange(1, npart + 1, dtype=np.int64),
        "p_name": np.array(
            [f"{COLORS[i % len(COLORS)]} {COLORS[(i * 7 + 3) % len(COLORS)]} "
             f"part {i}" for i in range(npart)], dtype=object),
        "p_mfgr": np.array([f"Manufacturer#{1 + i % 5}"
                            for i in rng.integers(0, 5, npart)], dtype=object),
        "p_brand": np.array([f"Brand#{11 + i % 45}"
                             for i in rng.integers(0, 45, npart)],
                            dtype=object),
        "p_type": type_full,
        "p_size": rng.integers(1, 51, npart).astype(np.int32),
        "p_container": np.array([CONTAINERS[i] for i in
                                 rng.integers(0, len(CONTAINERS), npart)],
                                dtype=object),
        "p_retailprice": np.round(900 + (np.arange(1, npart + 1) % 1000)
                                  * 0.1, 2),
        "p_comment": np.array([f"part comment {i}" for i in range(npart)],
                              dtype=object),
    }

    nps = counts["partsupp"]
    ps_part = np.repeat(np.arange(1, npart + 1, dtype=np.int64), 4)
    ps_supp = np.empty(nps, dtype=np.int64)
    for j in range(4):
        ps_supp[j::4] = ((ps_part[j::4] + j * (ns // 4 + 1)) % ns) + 1
    out["partsupp"] = {
        "ps_partkey": ps_part,
        "ps_suppkey": ps_supp,
        "ps_availqty": rng.integers(1, 10_000, nps).astype(np.int32),
        "ps_supplycost": np.round(rng.uniform(1.0, 1000.0, nps), 2),
        "ps_comment": np.array([f"ps comment {i}" for i in range(nps)],
                               dtype=object),
    }

    no = counts["orders"]
    # dbgen: order keys are sparse (1 of every 4 key slots ×8 used); keep
    # them sparse to exercise sparse-key joins
    okey = (np.arange(no, dtype=np.int64) * 4) + 1
    odate = _EPOCH_1992 + rng.integers(0, _ORDER_DATE_RANGE, no)
    out["orders"] = {
        "o_orderkey": okey,
        "o_custkey": rng.integers(1, nc + 1, no).astype(np.int64),
        "o_orderstatus": np.array(["F", "O", "P"], dtype=object)[
            rng.integers(0, 3, no)],
        "o_totalprice": np.round(rng.uniform(1000.0, 450_000.0, no), 2),
        "o_orderdate": odate.astype(np.int32),
        "o_orderpriority": np.array(PRIORITIES, dtype=object)[
            rng.integers(0, 5, no)],
        "o_clerk": np.char.add(
            "Clerk#", np.char.zfill(
                rng.integers(1, max(ns, 2), no).astype("U9"), 9)
        ).astype(object),
        "o_shippriority": np.zeros(no, dtype=np.int32),
        "o_comment": np.array([f"order comment {i}" for i in range(no)],
                              dtype=object),
    }

    per_order = rng.integers(1, 8, no)
    nl = int(per_order.sum())
    l_okey = np.repeat(okey, per_order)
    l_odate = np.repeat(odate, per_order)
    # 1..k within each order, vectorized (global iota minus segment start)
    starts = np.cumsum(per_order) - per_order
    linenumber = np.arange(nl) - np.repeat(starts, per_order) + 1
    qty = rng.integers(1, 51, nl).astype(np.float64)
    pkey = rng.integers(1, npart + 1, nl).astype(np.int64)
    price_base = 900 + (pkey % 1000) * 0.1
    extended = np.round(price_base * qty, 2)
    ship_delta = rng.integers(1, 122, nl)
    commit_delta = rng.integers(30, 91, nl)
    receipt_delta = rng.integers(1, 31, nl)
    shipdate = (l_odate + ship_delta).astype(np.int32)
    returnflag = np.where(
        shipdate <= _EPOCH_1992 + 1277,  # ~ receiptdate cutoffs
        np.array(["R", "A"], dtype=object)[rng.integers(0, 2, nl)],
        "N")
    linestatus = np.where(shipdate > _EPOCH_1992 + 1656, "O", "F")
    supp_for_part = ((pkey + rng.integers(0, 4, nl) * (ns // 4 + 1)) % ns) + 1
    out["lineitem"] = {
        "l_orderkey": l_okey,
        "l_partkey": pkey,
        "l_suppkey": supp_for_part.astype(np.int64),
        "l_linenumber": linenumber.astype(np.int32),
        "l_quantity": qty,
        "l_extendedprice": extended,
        "l_discount": np.round(rng.integers(0, 11, nl) * 0.01, 2),
        "l_tax": np.round(rng.integers(0, 9, nl) * 0.01, 2),
        "l_returnflag": returnflag.astype(object),
        "l_linestatus": linestatus.astype(object),
        "l_shipdate": shipdate,
        "l_commitdate": (l_odate + commit_delta).astype(np.int32),
        "l_receiptdate": (shipdate + receipt_delta).astype(np.int32),
        "l_shipinstruct": np.array(SHIPINSTRUCT, dtype=object)[
            rng.integers(0, 4, nl)],
        "l_shipmode": np.array(SHIPMODES, dtype=object)[
            rng.integers(0, 7, nl)],
        "l_comment": np.array([f"li {i}" for i in range(nl)], dtype=object),
    }
    return out


DISTRIBUTION = {
    # (distribution column, colocate_with) — lineitem⋈orders colocated on
    # orderkey; partsupp⋈part colocated on partkey — the classic Citus
    # TPC-H layout
    "lineitem": ("l_orderkey", None),
    "orders": ("o_orderkey", "lineitem"),
    "customer": ("c_custkey", None),
    "part": ("p_partkey", None),
    "partsupp": ("ps_partkey", "part"),
    "supplier": ("s_suppkey", None),
}
REFERENCE_TABLES = ["region", "nation"]


# ---------------------------------------------------------------------------
# what `--seed` draws: per table, the columns that move together between
# rows under one seeded permutation.  None of them is a key, a date, a
# flag or a segment, so no TPC-H filter, join or group key reads them
# (l_partkey and l_suppkey move with the price that is derived from
# them; statements that join on them see other pairs, the same counts).

MEASURES = {
    "lineitem": ("l_partkey", "l_suppkey", "l_quantity", "l_extendedprice",
                 "l_discount", "l_tax"),
    "orders": ("o_totalprice",),
    "customer": ("c_acctbal",),
    "supplier": ("s_acctbal",),
    "partsupp": ("ps_availqty", "ps_supplycost"),
}
_MEASURE_STREAM = 0x7C4


def generate(params: dict, seed: int) -> dict[str, dict[str, np.ndarray]]:
    """The rows of one run: structure from `STRUCTURE_SEED`, measures
    permuted by `seed` (any non-negative whole number)."""
    data = generate_tables(float(params["scale_factor"]), STRUCTURE_SEED)
    for t_idx, (table, cols) in enumerate(MEASURES.items()):
        rng = np.random.default_rng([_MEASURE_STREAM, t_idx, int(seed)])
        perm = rng.permutation(len(data[table][cols[0]]))
        for c in cols:
            data[table][c] = data[table][c][perm]
    loaded = set(params["tables"])
    return {t: cols for t, cols in data.items() if t in loaded}


def row_counts(data: dict) -> dict[str, int]:
    return {t: len(next(iter(cols.values()))) for t, cols in data.items()}


def column_widths(compute_dtype: str = "float32") -> dict[str, int]:
    """Bytes a device-resident, decoded value of each column takes:
    what `device_program_roofline` charges a statement for reading it once.
    From the DDL above and the configuration's compute dtype (DOUBLE
    PRECISION is held in it); text is a 4-byte dictionary code."""
    import re

    by_type = {"int": 4, "bigint": 8, "date": 4, "text": 4,
               "double precision": {"float32": 4, "float64": 8}[
                   compute_dtype]}
    out = {}
    for ddl in SCHEMAS.values():
        body = ddl[ddl.index("(") + 1:ddl.rindex(")")]
        for col in body.split(","):
            name, sql_type = re.match(r"\s*(\w+)\s+(.+?)\s*$", col,
                                      re.S).groups()
            out[name] = by_type[" ".join(sql_type.split())]
    return out


def stored_row_counts(sess, tables) -> dict[str, int] | None:
    """Row counts the session's store holds, or None when the data
    directory is empty."""
    if not sess.catalog.has_table("lineitem"):
        return None
    return {t: sess.store.table_row_count(t) for t in tables}


def load(sess, data: dict, params: dict) -> dict[str, int]:
    """Create, distribute and load: every schema, the classic Citus
    layout, then each table's columns through the program's ingest
    path.  Returns the row counts the ingest reported."""
    from citus_tpu.ingest.copy_from import _ingest_batch

    for ddl in SCHEMAS.values():
        sess.execute(ddl)
    for table, (dist_col, colocate) in DISTRIBUTION.items():
        sess.create_distributed_table(table, dist_col,
                                      shard_count=params.get("shard_count"),
                                      colocate_with=colocate)
    for table in REFERENCE_TABLES:
        sess.create_reference_table(table)
    counts = {}
    for table, cols in data.items():
        names = list(cols)
        # numeric columns go as numpy, object (string) columns as lists
        # for interning: what load_into_session passes
        batch = [list(cols[c]) if cols[c].dtype == object else cols[c]
                 for c in names]
        counts[table] = _ingest_batch(sess, table, names, batch,
                                      pre_typed=True)[0]
    return counts
