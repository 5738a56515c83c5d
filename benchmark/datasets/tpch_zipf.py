"""Dataset `tpch_zipf`: the benchmark's TPC-H rows with one foreign key
redrawn from a Zipf distribution, after the skewed TPC-D/TPC-H `dbgen`
of Chaudhuri and Narasayya (Microsoft Research, "Program for TPC-D data
generation with skew": one parameter z, 0 = uniform … 4).

Everything but two columns of `orders` is `datasets/tpch.py`'s, row for
row; schemas, loader, widths and counts are delegated to it.  What this
module draws itself:

* **`o_custkey`** — rank r of the `nc` customers with probability
  ∝ r^(−`zipf_z`), sampled with replacement, rank → key by one fixed
  permutation (so the hot customers are not the low keys, and the
  shards they hash to are no artefact of key order).  Both draws come
  from the constant `STRUCTURE_SEED` stream, never from `--seed`: the
  key multiset, its extent and every shard's row count are the same on
  every seed, so every seed runs the same programs.
* **`o_comment`** — `round(special_share × orders)` orders carry the
  one text `SPECIAL_COMMENT`, which Q13's `%special%requests%` matches;
  the others keep `order comment <i>`.  WHICH orders is what `--seed`
  draws, over all orders at once and without replacement: Q13 reads no
  measure column, so `tpch.generate`'s measure permutation alone would
  leave its answer the same on every seed.  Row 0 carries the text on
  every seed, so the text is the first value the column's dictionary
  interns and its code — the literal the `LIKE` binds to, a part of the
  plan's fingerprint — never moves; the count of special orders and
  the dictionary's length are the same on every seed too.  What DOES
  move with the seed is how many special orders a shard, an exchange
  bucket or the hot customer holds, by ± √n: the program sizes its
  buffers from such counts in 128-row classes, so two seeds may run
  programs one class apart (PERF.md §6, PR 35: on the chip that is
  two latency modes, and the cell reports both).

`lineitem` is still drawn and thrown away where the configuration does
not load it: it is the last table of `tpch.generate_tables`' stream, so
skipping it would need a second copy of that generator to keep the
other tables row for row (6 of 9 s at SF1, once a seed, in set-up).
"""

from __future__ import annotations

import numpy as np

from benchmark.datasets import tpch

# bump when generate() would give other rows for the same parameters
GENERATOR_VERSION = 1
STRUCTURE_SEED = tpch.STRUCTURE_SEED
SCHEMAS = tpch.SCHEMAS
# dbgen's text grammar puts "special … requests" into about 1 % of the
# order comments; here it is this one text
SPECIAL_COMMENT = "carefully special packages wake; final requests nag"

_ZIPF_STREAM = 0x21BF
_SPECIAL_STREAM = 0x5BEC

row_counts = tpch.row_counts
column_widths = tpch.column_widths
stored_row_counts = tpch.stored_row_counts
load = tpch.load


def zipf_probabilities(n: int, z: float) -> np.ndarray:
    """P(rank r), r = 1..n, ∝ r^(−z); z = 0 is uniform."""
    w = np.arange(1, n + 1, dtype=np.float64) ** -float(z)
    return w / w.sum()


def zipf_custkeys(n_customers: int, n_orders: int, z: float) -> np.ndarray:
    """`n_orders` customer keys in 1..n_customers: Zipf ranks, sampled
    with replacement by inverting the cumulative distribution, mapped to
    keys by one fixed permutation.  From `STRUCTURE_SEED` alone."""
    rng = np.random.default_rng([_ZIPF_STREAM, STRUCTURE_SEED])
    cdf = np.cumsum(zipf_probabilities(n_customers, z))
    ranks = np.searchsorted(cdf, rng.random(n_orders) * cdf[-1],
                            side="right")
    np.minimum(ranks, n_customers - 1, out=ranks)
    key_of_rank = rng.permutation(n_customers).astype(np.int64) + 1
    return key_of_rank[ranks]


def special_rows(n_orders: int, share: float, seed: int) -> np.ndarray:
    """Rows of `orders` that carry `SPECIAL_COMMENT`:
    `round(share × n_orders)` of them, row 0 and a draw by `seed` over
    the others."""
    n_special = max(1, int(round(float(share) * n_orders)))
    rng = np.random.default_rng([_SPECIAL_STREAM, int(seed)])
    others = rng.choice(n_orders - 1, size=n_special - 1, replace=False)
    return np.concatenate([[0], others + 1])


def generate(params: dict, seed: int) -> dict[str, dict[str, np.ndarray]]:
    """`tpch.generate`'s rows with `o_custkey` and `o_comment` redrawn."""
    data = tpch.generate(params, seed)
    orders = data["orders"]
    n_orders = len(orders["o_orderkey"])
    n_customers = tpch.table_rows(float(params["scale_factor"]))["customer"]
    orders["o_custkey"] = zipf_custkeys(n_customers, n_orders,
                                        float(params["zipf_z"]))
    comment = orders["o_comment"].copy()
    comment[special_rows(n_orders, params["special_share"],
                         seed)] = SPECIAL_COMMENT
    orders["o_comment"] = comment
    return data
