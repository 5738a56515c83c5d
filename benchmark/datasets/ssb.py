"""Dataset `ssb`: the Star Schema Benchmark's five tables (O'Neil, O'Neil,
Chen, "Star Schema Benchmark", revision 3, 2009, section 2) and their
load on the Citus star layout: `lineorder` hash-distributed on
`lo_orderkey`, the four dimensions reference tables.  The date table is
`dwdate` (`date` is a type name).

The interface and the seed rule of `datasets/tpch.py`:

* **structure** — row counts, every key and foreign key, every date and
  every dimension attribute (what an SSB filter, join or group key reads
  of a dimension) — is drawn from the constant `STRUCTURE_SEED`;
* **measures** are drawn from `--seed`: one seeded permutation moves the
  tuples (`MEASURES`) between fact rows.  Every answer changes with the
  seed; every count, extent, dictionary and join selectivity stays, so
  every seed runs the same programs.

Every numeric column is an integer as in the paper (prices in cents,
dates as `yyyymmdd`); nothing here is a float.  Departures from the
paper are listed one by one in the configuration's `assumed`.
"""

from __future__ import annotations

import math
import re

import numpy as np

GENERATOR_VERSION = 1
STRUCTURE_SEED = 0

SCHEMAS = {
    "customer": """create table customer (
        c_custkey int, c_name text, c_address text, c_city text,
        c_nation text, c_region text, c_phone text, c_mktsegment text)""",
    "supplier": """create table supplier (
        s_suppkey int, s_name text, s_address text, s_city text,
        s_nation text, s_region text, s_phone text)""",
    "part": """create table part (
        p_partkey int, p_name text, p_mfgr text, p_category text,
        p_brand1 text, p_color text, p_type text, p_size int,
        p_container text)""",
    "dwdate": """create table dwdate (
        d_datekey int, d_date text, d_dayofweek text, d_month text,
        d_year int, d_yearmonthnum int, d_yearmonth text,
        d_daynuminweek int, d_daynuminmonth int, d_daynuminyear int,
        d_monthnuminyear int, d_weeknuminyear int, d_sellingseason text,
        d_lastdayinweekfl int, d_lastdayinmonthfl int, d_holidayfl int,
        d_weekdayfl int)""",
    "lineorder": """create table lineorder (
        lo_orderkey bigint, lo_linenumber int, lo_custkey int,
        lo_partkey int, lo_suppkey int, lo_orderdate int,
        lo_orderpriority text, lo_shippriority text, lo_quantity int,
        lo_extendedprice int, lo_ordtotalprice int, lo_discount int,
        lo_revenue int, lo_supplycost int, lo_tax int, lo_commitdate int,
        lo_shipmode text)""",
}
FACT, FACT_KEY = "lineorder", "lo_orderkey"
DIMENSIONS = ("customer", "supplier", "part", "dwdate")

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [  # (name, region) — TPC-H's 25, five a region
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECI", "5-LOW"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
TYPES_1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPES_2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
TYPES_3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
CONTAINERS = [f"{a} {b}" for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
              for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN",
                        "DRUM")]
COLORS = [
    "almond", "antique", "aquamarine", "azure", "beige", "bisque", "black",
    "blanched", "blue", "blush", "brown", "burlywood", "burnished",
    "chartreuse", "chiffon", "chocolate", "coral", "cornflower", "cornsilk",
    "cream", "cyan", "dark", "deep", "dim", "dodger", "drab", "firebrick",
    "floral", "forest", "frosted", "gainsboro", "ghost", "goldenrod", "green",
    "grey", "honeydew", "hot", "indian", "ivory", "khaki", "lace", "lavender",
    "lawn", "lemon", "light", "lime", "linen", "magenta", "maroon", "medium",
    "metallic", "midnight", "mint", "misty", "moccasin", "navajo", "navy",
    "olive", "orange", "orchid", "pale", "papaya", "peach", "peru", "pink",
    "plum", "powder", "puff", "purple", "red", "rose", "rosy", "royal",
    "saddle", "salmon", "sandy", "seashell", "sienna", "sky", "slate",
    "smoke", "snow", "spring", "steel", "tan", "thistle", "tomato",
    "turquoise", "violet", "wheat", "white", "yellow"]
MONTHS = ["January", "February", "March", "April", "May", "June", "July",
          "August", "September", "October", "November", "December"]
WEEKDAYS = ["Sunday", "Monday", "Tuesday", "Wednesday", "Thursday",
            "Friday", "Saturday"]
SEASONS = {12: "Christmas", 1: "Winter", 2: "Winter", 3: "Spring",
           4: "Spring", 5: "Spring", 6: "Summer", 7: "Summer", 8: "Summer",
           9: "Fall", 10: "Fall", 11: "Fall"}
HOLIDAYS = {(1, 1), (2, 14), (7, 4), (10, 31), (11, 11), (12, 25)}

DATE_ROWS = 2556          # 1992-01-01 … 1998-12-30, as dbgen writes them
_ORDER_DATE_RANGE = 2406  # orders through 1998-08-02, TPC-H's range


def table_rows(sf: float) -> dict[str, int]:
    """The paper's counts; under SF1 (the CPU tests) the part table
    shrinks with the others instead of staying at 200,000."""
    return {
        "customer": max(int(30_000 * sf), 30),
        "supplier": max(int(2_000 * sf), 10),
        "part": (200_000 * (1 + int(math.log2(sf))) if sf >= 1
                 else max(int(200_000 * sf), 40)),
        "dwdate": DATE_ROWS,
        "orders": max(int(1_500_000 * sf), 150),
        # lineorder: 1..7 lines an order, 4 on average
    }


def _pick(values, idx) -> np.ndarray:
    return np.array(values, dtype=object)[idx]


def _numbered(prefix: str, n: int) -> np.ndarray:
    return np.char.add(prefix, np.char.zfill(
        np.arange(1, n + 1).astype("U9"), 9)).astype(object)


def _place(rng, n: int) -> dict[str, np.ndarray]:
    """city, nation, region and phone of `n` customers or suppliers:
    nation uniform over the 25, ten cities a nation (the nation's first
    nine characters, padded, and a digit)."""
    nation = rng.integers(0, 25, n)
    digit = rng.integers(0, 10, n)
    cities = [f"{name[:9]:<9}{d}" for name, _ in NATIONS for d in range(10)]
    local = rng.integers(100, 1000, (3, n))
    phone = np.array([f"{10 + k}-{a}-{b}-{c + 1000}" for k, a, b, c in
                      zip(nation, *local)], dtype=object)
    return {"city": _pick(cities, nation * 10 + digit),
            "nation": _pick([name for name, _ in NATIONS], nation),
            "region": _pick(REGIONS, np.array([r for _, r in NATIONS])[nation]),
            "phone": phone}


def retail_price_cents(partkey: np.ndarray) -> np.ndarray:
    """TPC-H clause 4.2.3's P_RETAILPRICE, in cents: 900.00 … 2098.99."""
    return 90_000 + (partkey // 10) % 20_001 + 100 * (partkey % 1_000)


def generate_structure(sf: float) -> dict[str, dict[str, np.ndarray]]:
    rng = np.random.default_rng(STRUCTURE_SEED)
    counts = table_rows(sf)
    out: dict[str, dict[str, np.ndarray]] = {}

    nc = counts["customer"]
    place = _place(rng, nc)
    out["customer"] = {
        "c_custkey": np.arange(1, nc + 1, dtype=np.int32),
        "c_name": _numbered("Customer#", nc),
        "c_address": np.array([f"addr c{i}" for i in range(nc)], dtype=object),
        "c_city": place["city"], "c_nation": place["nation"],
        "c_region": place["region"], "c_phone": place["phone"],
        "c_mktsegment": _pick(SEGMENTS, rng.integers(0, 5, nc)),
    }

    ns = counts["supplier"]
    place = _place(rng, ns)
    out["supplier"] = {
        "s_suppkey": np.arange(1, ns + 1, dtype=np.int32),
        "s_name": _numbered("Supplier#", ns),
        "s_address": np.array([f"addr s{i}" for i in range(ns)], dtype=object),
        "s_city": place["city"], "s_nation": place["nation"],
        "s_region": place["region"], "s_phone": place["phone"],
    }

    npart = counts["part"]
    mfgr = rng.integers(1, 6, npart)
    cat = rng.integers(1, 6, npart)
    brand = rng.integers(1, 41, npart)
    color = rng.integers(0, len(COLORS), (2, npart))
    out["part"] = {
        "p_partkey": np.arange(1, npart + 1, dtype=np.int32),
        "p_name": np.array([f"{COLORS[a]} {COLORS[b]}"
                            for a, b in zip(*color)], dtype=object),
        "p_mfgr": _pick([f"MFGR#{m}" for m in range(6)], mfgr),
        "p_category": _pick([f"MFGR#{v}" for v in range(56)], mfgr * 10 + cat),
        "p_brand1": np.array([f"MFGR#{m}{c}{b}" for m, c, b in
                              zip(mfgr, cat, brand)], dtype=object),
        "p_color": _pick(COLORS, color[0]),
        "p_type": np.array(
            [f"{TYPES_1[a]} {TYPES_2[b]} {TYPES_3[c]}"
             for a, b, c in zip(rng.integers(0, 6, npart),
                                rng.integers(0, 5, npart),
                                rng.integers(0, 5, npart))], dtype=object),
        "p_size": rng.integers(1, 51, npart).astype(np.int32),
        "p_container": _pick(CONTAINERS, rng.integers(0, 40, npart)),
    }

    out["dwdate"] = date_table()

    no = counts["orders"]
    i = np.arange(no, dtype=np.int64)
    okey = (i // 8) * 32 + i % 8 + 1  # TPC-H's sparse keys: 8 of every 32
    oday = rng.integers(0, _ORDER_DATE_RANGE, no)
    per_order = rng.integers(1, 8, no)
    nl = int(per_order.sum())
    starts = np.cumsum(per_order) - per_order
    datekey = out["dwdate"]["d_datekey"]
    l_oday = np.repeat(oday, per_order)
    out[FACT] = {
        "lo_orderkey": np.repeat(okey, per_order),
        "lo_linenumber": (np.arange(nl) - np.repeat(starts, per_order)
                          + 1).astype(np.int32),
        "lo_custkey": np.repeat(rng.integers(1, nc + 1, no),
                                per_order).astype(np.int32),
        "lo_partkey": rng.integers(1, npart + 1, nl).astype(np.int32),
        "lo_suppkey": rng.integers(1, ns + 1, nl).astype(np.int32),
        "lo_orderdate": datekey[l_oday],
        "lo_orderpriority": np.repeat(
            _pick(PRIORITIES, rng.integers(0, 5, no)), per_order),
        "lo_shippriority": np.full(nl, "0", dtype=object),
        "lo_commitdate": datekey[l_oday + rng.integers(30, 91, nl)],
        "lo_shipmode": _pick(SHIPMODES, rng.integers(0, 7, nl)),
    }
    # the measure tuples, one a line, consistent in themselves: the
    # price is that of a part drawn for the tuple (SSB's part table has
    # no price column, so no statement can tell it from lo_partkey's)
    qty = rng.integers(1, 51, nl)
    price = retail_price_cents(rng.integers(1, npart + 1, nl))
    disc = rng.integers(0, 11, nl)
    ext = qty * price
    out[FACT].update({
        "lo_quantity": qty.astype(np.int32),
        "lo_extendedprice": ext.astype(np.int32),
        "lo_discount": disc.astype(np.int32),
        "lo_revenue": (ext * (100 - disc) // 100).astype(np.int32),
        "lo_supplycost": (6 * price // 10).astype(np.int32),
        "lo_tax": rng.integers(0, 9, nl).astype(np.int32),
    })
    return out


def date_table() -> dict[str, np.ndarray]:
    days = np.datetime64("1992-01-01") + np.arange(DATE_ROWS)
    year = days.astype("datetime64[Y]").astype(int) + 1970
    month = days.astype("datetime64[M]").astype(int) % 12 + 1
    dom = (days - days.astype("datetime64[M]")).astype(int) + 1
    doy = (days - days.astype("datetime64[Y]")).astype(int) + 1
    dow = (days.astype(int) + 4) % 7  # 0 = Sunday (1970-01-01: a Thursday)
    last_dom = (days + 1).astype("datetime64[M]") != days.astype(
        "datetime64[M]")
    i32 = np.int32
    return {
        "d_datekey": (year * 10_000 + month * 100 + dom).astype(i32),
        "d_date": np.array([f"{MONTHS[m - 1]} {d}, {y}" for m, d, y in
                            zip(month, dom, year)], dtype=object),
        "d_dayofweek": _pick(WEEKDAYS, dow),
        "d_month": _pick(MONTHS, month - 1),
        "d_year": year.astype(i32),
        "d_yearmonthnum": (year * 100 + month).astype(i32),
        "d_yearmonth": np.array([f"{MONTHS[m - 1][:3]}{y}" for m, y in
                                 zip(month, year)], dtype=object),
        "d_daynuminweek": (dow + 1).astype(i32),
        "d_daynuminmonth": dom.astype(i32),
        "d_daynuminyear": doy.astype(i32),
        "d_monthnuminyear": month.astype(i32),
        "d_weeknuminyear": ((doy - 1) // 7 + 1).astype(i32),
        "d_sellingseason": _pick([SEASONS[m] for m in range(1, 13)],
                                 month - 1),
        "d_lastdayinweekfl": (dow == 6).astype(i32),
        "d_lastdayinmonthfl": last_dom.astype(i32),
        "d_holidayfl": np.array([(m, d) in HOLIDAYS for m, d in
                                 zip(month, dom)]).astype(i32),
        "d_weekdayfl": ((dow >= 1) & (dow <= 5)).astype(i32),
    }


# what `--seed` draws: the columns that move together between fact rows
# under one seeded permutation.  None of them is a key or a date.
MEASURES = ("lo_quantity", "lo_extendedprice", "lo_discount", "lo_revenue",
            "lo_supplycost", "lo_tax")
_MEASURE_STREAM = 0x55B


def generate(params: dict, seed: int) -> dict[str, dict[str, np.ndarray]]:
    """The rows of one run: structure from `STRUCTURE_SEED`, the fact
    table's measure tuples permuted by `seed` (any non-negative whole
    number), `lo_ordtotalprice` summed over each order's lines after
    that."""
    data = generate_structure(float(params["scale_factor"]))
    lo = data[FACT]
    perm = np.random.default_rng(
        [_MEASURE_STREAM, int(seed)]).permutation(len(lo[FACT_KEY]))
    for c in MEASURES:
        lo[c] = lo[c][perm]
    # an order's lines are neighbours: number the orders, sum by number
    order_no = np.cumsum(lo["lo_linenumber"] == 1) - 1
    line_total = (lo["lo_extendedprice"].astype(np.int64)
                  * (100 - lo["lo_discount"]) * (100 + lo["lo_tax"])
                  // 10_000)
    lo["lo_ordtotalprice"] = np.bincount(
        order_no, weights=line_total)[order_no].astype(np.int32)
    return data


def row_counts(data: dict) -> dict[str, int]:
    return {t: len(next(iter(cols.values()))) for t, cols in data.items()}


def column_widths(compute_dtype: str = "float32") -> dict[str, int]:
    """Bytes a device-resident, decoded value of each column takes: what
    `device_program_roofline` charges a statement for reading it once.
    From the DDL above; text is a 4-byte dictionary code, and no column
    is held in the compute dtype."""
    by_type = {"int": 4, "bigint": 8, "text": 4}
    out = {}
    for ddl in SCHEMAS.values():
        body = ddl[ddl.index("(") + 1:ddl.rindex(")")]
        for col in body.split(","):
            name, sql_type = re.match(r"\s*(\w+)\s+(\w+)\s*$", col).groups()
            out[name] = by_type[sql_type]
    return out


def stored_row_counts(sess, tables) -> dict[str, int] | None:
    """Row counts the session's store holds, or None when the data
    directory is empty."""
    if not sess.catalog.has_table(FACT):
        return None
    return {t: sess.store.table_row_count(t) for t in tables}


def load(sess, data: dict, params: dict) -> dict[str, int]:
    """Create the five tables on the star layout, then each table's
    columns through the program's ingest path (as `datasets/tpch.py`
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
        batch = [list(cols[c]) if cols[c].dtype == object else cols[c]
                 for c in names]
        counts[table] = _ingest_batch(sess, table, names, batch,
                                      pre_typed=True)[0]
    return counts
