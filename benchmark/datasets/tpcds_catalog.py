"""Dataset `tpcds_catalog`: the four TPC-DS tables query 57 reads (TPC-DS
specification v3.2, clause 2: `catalog_sales`, `call_center`, `item`,
`date_dim`, every column at its specification type) and their load on
the star layout Citus documents for a warehouse: the fact table
`catalog_sales` hash-distributed on `cs_item_sk`, the dimensions
reference tables.

`item` and `date_dim` are `datasets/tpcds.py`'s, row for row: the same
functions draw them from the same `STRUCTURE_SEED` stream.  The catalog
channel's own tables come from a second stream of that seed:

* **structure** — row counts, every key, every date and every call
  center attribute — is drawn from the constant `STRUCTURE_SEED`;
* **measures** are drawn from `--seed`: one seeded permutation moves the
  measure tuples (`MEASURES`: `cs_quantity` … `cs_net_profit`) between
  fact rows.  Every answer changes with the seed; no count, extent,
  dictionary or selectivity does, so every seed runs the same programs.

This is a stand-in generator written for the benchmark, not `dsdgen`;
its departures from the specification are listed one by one in the
configuration's `assumed`.  It imports nothing of `citus_tpu` but in
`load`, which hands the rows to the program's ingest path.
"""

from __future__ import annotations

import re

import numpy as np

from benchmark.datasets import tpcds

GENERATOR_VERSION = 1
STRUCTURE_SEED = tpcds.STRUCTURE_SEED
# the catalog channel's tables draw from their own stream of the seed,
# so that item (drawn first from tpcds.py's stream) is tpcds.py's
_CATALOG_STREAM = 0xC5

SCHEMAS = {
    "date_dim": tpcds.SCHEMAS["date_dim"],
    "item": tpcds.SCHEMAS["item"],
    "call_center": """create table call_center (
        cc_call_center_sk integer not null,
        cc_call_center_id char(16) not null,
        cc_rec_start_date date, cc_rec_end_date date,
        cc_closed_date_sk integer, cc_open_date_sk integer,
        cc_name varchar(50), cc_class varchar(50), cc_employees integer,
        cc_sq_ft integer, cc_hours char(20), cc_manager varchar(40),
        cc_mkt_id integer, cc_mkt_class char(50), cc_mkt_desc varchar(100),
        cc_market_manager varchar(40), cc_division integer,
        cc_division_name varchar(50), cc_company integer,
        cc_company_name char(50), cc_street_number char(10),
        cc_street_name varchar(60), cc_street_type char(15),
        cc_suite_number char(10), cc_city varchar(60), cc_county varchar(30),
        cc_state char(2), cc_zip char(10), cc_country varchar(20),
        cc_gmt_offset decimal(5,2), cc_tax_percentage decimal(5,2))""",
    "catalog_sales": """create table catalog_sales (
        cs_sold_date_sk integer, cs_sold_time_sk integer,
        cs_ship_date_sk integer, cs_bill_customer_sk integer,
        cs_bill_cdemo_sk integer, cs_bill_hdemo_sk integer,
        cs_bill_addr_sk integer, cs_ship_customer_sk integer,
        cs_ship_cdemo_sk integer, cs_ship_hdemo_sk integer,
        cs_ship_addr_sk integer, cs_call_center_sk integer,
        cs_catalog_page_sk integer, cs_ship_mode_sk integer,
        cs_warehouse_sk integer, cs_item_sk integer not null,
        cs_promo_sk integer, cs_order_number integer not null,
        cs_quantity integer, cs_wholesale_cost decimal(7,2),
        cs_list_price decimal(7,2), cs_sales_price decimal(7,2),
        cs_ext_discount_amt decimal(7,2), cs_ext_sales_price decimal(7,2),
        cs_ext_wholesale_cost decimal(7,2), cs_ext_list_price decimal(7,2),
        cs_ext_tax decimal(7,2), cs_coupon_amt decimal(7,2),
        cs_ext_ship_cost decimal(7,2), cs_net_paid decimal(7,2),
        cs_net_paid_inc_tax decimal(7,2), cs_net_paid_inc_ship decimal(7,2),
        cs_net_paid_inc_ship_tax decimal(7,2), cs_net_profit decimal(7,2))""",
}
FACT, FACT_KEY = "catalog_sales", "cs_item_sk"
DIMENSIONS = ("date_dim", "item", "call_center")

# clause 3's row counts of the two tables this module adds
CALL_CENTER_ROWS = {1: 6, 10: 24}
CATALOG_SALES_SF1 = 1_441_548
CATALOG_SALES_SF10 = 14_401_261
# the rows a referenced table that this data set does not make would
# hold at SF10, so that a foreign key spans the key range it would
FK_EXTENTS = {"cs_sold_time_sk": 86_400, "cs_bill_customer_sk": 500_000,
              "cs_bill_cdemo_sk": 1_920_800, "cs_bill_hdemo_sk": 7_200,
              "cs_bill_addr_sk": 250_000, "cs_catalog_page_sk": 12_000,
              "cs_ship_mode_sk": 20, "cs_warehouse_sk": 10,
              "cs_promo_sk": 500}
LINES_PER_ORDER = (4, 14)
SHIP_DAYS = (2, 90)
# a call center id has two revisions (a row each); its name is one of
# these, as dsdgen's `call_centers` distribution names them
CALL_CENTER_NAMES = ["NY Metro", "Mid Atlantic", "Pacific Northwest",
                     "North Midwest", "California", "Hawaii/Alaska",
                     "Southeast", "Central", "Northeast", "Southwest",
                     "Gulf Coast", "Mountain"]
CC_CLASSES = ["small", "medium", "large"]
CC_HOURS = ["8AM-4PM", "8AM-12AM", "8AM-8AM"]


def table_rows(sf: float) -> dict[str, int]:
    """Clause 3's counts at SF1 and SF10; under SF1 (the CPU tests) the
    fact table shrinks in proportion and `item` as `tpcds.py` shrinks
    it."""
    if sf in CALL_CENTER_ROWS:
        centers = CALL_CENTER_ROWS[sf]
        sales = CATALOG_SALES_SF1 if sf == 1 else CATALOG_SALES_SF10
    elif sf < 1:
        centers = CALL_CENTER_ROWS[1]
        sales = max(int(CATALOG_SALES_SF1 * sf), 1_000)
    else:
        raise ValueError(f"no TPC-DS row counts for scale factor {sf}")
    base = tpcds.table_rows(sf)
    return {"date_dim": base["date_dim"], "item": base["item"],
            "call_center": centers, "catalog_sales": sales}


def call_center_table(rng, n: int) -> dict[str, np.ndarray]:
    """`n` call center rows: n/2 ids of two revisions each (the second
    from 2000-01-01, the first ending the day before), named in id
    order from `CALL_CENTER_NAMES`, both revisions under one name."""
    ids = np.arange(n) // 2
    rev = np.arange(n) % 2
    start = np.array([tpcds._days("1998-01-01"), tpcds._days("2000-01-01")],
                     dtype=np.int32)
    end = [int(start[1] - 1) if r == 0 else None for r in rev]
    names = np.array(CALL_CENTER_NAMES, dtype=object)[ids
                                                      % len(CALL_CENTER_NAMES)]
    open_sk = tpcds.FIRST_DATE_SK + (
        tpcds._days("1998-01-01") - tpcds._days("1900-01-02")) \
        - rng.integers(0, 3_650, n)
    i32 = np.int32
    geo = rng.integers(0, len(tpcds.CITIES), n)
    return {
        "cc_call_center_sk": np.arange(1, n + 1, dtype=i32),
        "cc_call_center_id": tpcds._business_id(n // 2 + n % 2)[ids],
        "cc_rec_start_date": start[rev],
        "cc_rec_end_date": end,
        "cc_closed_date_sk": [None] * n,
        "cc_open_date_sk": open_sk.astype(i32),
        "cc_name": names,
        "cc_class": tpcds._pick(CC_CLASSES, rng.integers(0, 3, n)),
        "cc_employees": rng.integers(1, 7_000, n).astype(i32),
        "cc_sq_ft": rng.integers(1_000, 700_000, n).astype(i32),
        "cc_hours": tpcds._pick(CC_HOURS, rng.integers(0, 3, n)),
        "cc_manager": np.array([f"Manager {k}" for k in ids], dtype=object),
        "cc_mkt_id": rng.integers(1, 7, n).astype(i32),
        "cc_mkt_class": np.array(
            [" ".join(tpcds.WORDS[(5 * k + j) % len(tpcds.WORDS)]
                      for j in range(4)) for k in ids], dtype=object),
        "cc_mkt_desc": np.array(
            [" ".join(tpcds.WORDS[(11 * k + j) % len(tpcds.WORDS)]
                      for j in range(8)) for k in ids], dtype=object),
        "cc_market_manager": np.array(
            [f"Market manager {k}" for k in ids], dtype=object),
        "cc_division": rng.integers(1, 7, n).astype(i32),
        "cc_division_name": tpcds._pick(tpcds.SYLLABLES,
                                        rng.integers(0, 10, n)),
        "cc_company": rng.integers(1, 7, n).astype(i32),
        "cc_company_name": tpcds._pick(tpcds.SYLLABLES,
                                       rng.integers(0, 10, n)),
        "cc_street_number": np.array(
            [str(v) for v in rng.integers(1, 1_000, n)], dtype=object),
        "cc_street_name": tpcds._pick(tpcds.WORDS,
                                      rng.integers(0, len(tpcds.WORDS), n)),
        "cc_street_type": tpcds._pick(
            tpcds.STREET_TYPES, rng.integers(0, len(tpcds.STREET_TYPES), n)),
        "cc_suite_number": np.array(
            [f"Suite {v}" for v in rng.integers(0, 500, n)], dtype=object),
        "cc_city": tpcds._pick(tpcds.CITIES, geo),
        "cc_county": tpcds._pick(tpcds.COUNTIES, geo % len(tpcds.COUNTIES)),
        "cc_state": tpcds._pick(tpcds.STATES, geo),
        "cc_zip": np.array(
            [f"{v:05d}" for v in rng.integers(10_000, 99_999, n)],
            dtype=object),
        "cc_country": np.full(n, "United States", dtype=object),
        "cc_gmt_offset": np.full(n, -5.0),
        "cc_tax_percentage": np.round(rng.uniform(0.0, 0.12, n), 2),
    }


def generate_structure(sf: float) -> dict[str, dict[str, np.ndarray]]:
    counts = table_rows(sf)
    # item first from tpcds.py's own stream, as tpcds.generate_structure
    # draws it: the same rows as tpcds-sf10-1chip's
    item = tpcds.item_table(np.random.default_rng(STRUCTURE_SEED),
                            counts["item"])
    rng = np.random.default_rng([STRUCTURE_SEED, _CATALOG_STREAM])
    out = {"date_dim": tpcds.date_table(), "item": item,
           "call_center": call_center_table(rng, counts["call_center"])}

    n = counts[FACT]
    lo, hi = LINES_PER_ORDER
    per = rng.integers(lo, hi + 1, n // lo + 1)
    per = per[:int(np.searchsorted(np.cumsum(per), n)) + 1]
    per[-1] -= int(per.sum()) - n
    orders = len(per)
    n_items = counts["item"]
    day = rng.choice(tpcds.SALES_DAYS, orders, p=tpcds._sales_day_weights())
    sold_sk = tpcds.FIRST_DATE_SK + (tpcds.SALES_FIRST
                                     - tpcds.FIRST_DATE).days + day
    ship_sk = np.repeat(sold_sk, per) + rng.integers(SHIP_DAYS[0],
                                                     SHIP_DAYS[1] + 1, n)

    def per_order(hi_key: int) -> np.ndarray:
        return np.repeat(rng.integers(1, hi_key + 1, orders),
                         per).astype(np.int32)

    def per_line(hi_key: int) -> np.ndarray:
        return rng.integers(1, hi_key + 1, n).astype(np.int32)

    i32 = np.int32
    bill = {k: per_order(FK_EXTENTS[f"cs_bill_{k}_sk"])
            for k in ("customer", "cdemo", "hdemo", "addr")}
    fact = {
        "cs_sold_date_sk": np.repeat(sold_sk, per).astype(i32),
        "cs_sold_time_sk": per_order(FK_EXTENTS["cs_sold_time_sk"]),
        "cs_ship_date_sk": ship_sk.astype(i32),
        "cs_bill_customer_sk": bill["customer"],
        "cs_bill_cdemo_sk": bill["cdemo"],
        "cs_bill_hdemo_sk": bill["hdemo"],
        "cs_bill_addr_sk": bill["addr"],
        # an order ships to the customer who pays for it
        "cs_ship_customer_sk": bill["customer"].copy(),
        "cs_ship_cdemo_sk": bill["cdemo"].copy(),
        "cs_ship_hdemo_sk": bill["hdemo"].copy(),
        "cs_ship_addr_sk": bill["addr"].copy(),
        "cs_call_center_sk": per_order(counts["call_center"]),
        "cs_catalog_page_sk": per_line(FK_EXTENTS["cs_catalog_page_sk"]),
        "cs_ship_mode_sk": per_order(FK_EXTENTS["cs_ship_mode_sk"]),
        "cs_warehouse_sk": per_line(FK_EXTENTS["cs_warehouse_sk"]),
        "cs_item_sk": rng.integers(1, n_items + 1, n).astype(i32),
        "cs_promo_sk": per_line(FK_EXTENTS["cs_promo_sk"]),
        "cs_order_number": np.repeat(np.arange(1, orders + 1),
                                     per).astype(i32),
    }
    fact.update(_measures(rng, n))
    out[FACT] = fact
    return out


def _measures(rng, n: int) -> dict[str, np.ndarray]:
    """`tpcds._measures`' pricing tuple of a sales line, renamed to the
    catalog channel's columns, with its shipping cost (0-10 % of the
    extended list price) and the two totals that include it."""
    m = tpcds._measures(rng, n)
    cents = {k: np.round(v * 100).astype(np.int64) for k, v in m.items()
             if k != "ss_quantity"}
    ship = cents["ss_ext_list_price"] * rng.integers(0, 11, n) // 100
    out = {f"cs_{k[3:]}": v for k, v in m.items()}
    out["cs_ext_ship_cost"] = ship / 100.0
    out["cs_net_paid_inc_ship"] = (cents["ss_net_paid"] + ship) / 100.0
    out["cs_net_paid_inc_ship_tax"] = (cents["ss_net_paid_inc_tax"]
                                       + ship) / 100.0
    out["cs_net_profit"] = (cents["ss_net_profit"] - ship) / 100.0
    return {c: out[c] for c in MEASURES}


# what `--seed` draws: the columns that move together between fact rows
# under one seeded permutation.  None of them is a key or a date.
MEASURES = ("cs_quantity", "cs_wholesale_cost", "cs_list_price",
            "cs_sales_price", "cs_ext_discount_amt", "cs_ext_sales_price",
            "cs_ext_wholesale_cost", "cs_ext_list_price", "cs_ext_tax",
            "cs_coupon_amt", "cs_ext_ship_cost", "cs_net_paid",
            "cs_net_paid_inc_tax", "cs_net_paid_inc_ship",
            "cs_net_paid_inc_ship_tax", "cs_net_profit")
_MEASURE_STREAM = 0xC7


def generate(params: dict, seed: int) -> dict[str, dict[str, np.ndarray]]:
    """The rows of one run: structure from `STRUCTURE_SEED`, the fact
    table's measure tuples permuted by `seed` (any non-negative whole
    number)."""
    data = generate_structure(float(params["scale_factor"]))
    fact = data[FACT]
    perm = np.random.default_rng(
        [_MEASURE_STREAM, int(seed)]).permutation(len(fact[FACT_KEY]))
    for c in MEASURES:
        fact[c] = fact[c][perm]
    return data


def row_counts(data: dict) -> dict[str, int]:
    return tpcds.row_counts(data)


def column_widths(compute_dtype: str = "float32") -> dict[str, int]:
    """Bytes a device-resident, decoded value of each column takes: what
    `device_program_roofline` charges a statement for reading it once.
    From the DDL above; text is a 4-byte dictionary code, a date 4 bytes
    of days, a decimal the compute dtype."""
    dec = np.dtype(compute_dtype).itemsize
    by_type = {"integer": 4, "bigint": 8, "date": 4, "char": 4,
               "varchar": 4, "decimal": dec}
    out = {}
    for ddl in SCHEMAS.values():
        body = ddl[ddl.index("(") + 1:ddl.rindex(")")]
        for name, sql_type in re.findall(r"(\w+) (integer|bigint|date|char|"
                                         r"varchar|decimal)\b", body):
            out[name] = by_type[sql_type]
    return out


def stored_row_counts(sess, tables) -> dict[str, int] | None:
    """Row counts the session's store holds, or None when the data
    directory is empty."""
    if not sess.catalog.has_table(FACT):
        return None
    return {t: sess.store.table_row_count(t) for t in tables}


def load(sess, data: dict, params: dict) -> dict[str, int]:
    """Create the four tables on the star layout, then each table's
    columns through the program's ingest path (as `datasets/tpcds.py`
    does).  Returns the row counts the ingest reported."""
    from citus_tpu.ingest.copy_from import _ingest_batch

    for ddl in SCHEMAS.values():
        sess.execute(ddl)
    sess.create_distributed_table(FACT, FACT_KEY,
                                  shard_count=params.get("shard_count"))
    for table in DIMENSIONS:
        sess.create_reference_table(table)
    counts = {}
    for table, cols in data.items():
        names = list(cols)
        batch = [list(cols[c]) if isinstance(cols[c], list)
                 or cols[c].dtype == object else cols[c] for c in names]
        counts[table] = _ingest_batch(sess, table, names, batch,
                                      pre_typed=True)[0]
    return counts
