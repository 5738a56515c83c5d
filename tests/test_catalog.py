"""Catalog + hash distribution semantics tests.

Covers the behaviors surveyed from create_shards.c (token ranges),
colocation_utils.c (colocation groups), and node_metadata.c (node lifecycle).
"""

import numpy as np
import pytest

from citus_tpu.catalog import (
    Catalog,
    DistributionMethod,
    INT32_MAX,
    INT32_MIN,
    hash_token,
    shard_index_for_token,
    shard_index_for_values,
    shard_interval_bounds,
)
from citus_tpu.errors import CatalogError
from citus_tpu.types import ColumnDef, DataType, TableSchema


def make_schema(*cols):
    return TableSchema(tuple(ColumnDef(n, t) for n, t in cols))


ORDERS = make_schema(("o_orderkey", DataType.INT64),
                     ("o_custkey", DataType.INT64),
                     ("o_totalprice", DataType.FLOAT64))
LINEITEM = make_schema(("l_orderkey", DataType.INT64),
                       ("l_quantity", DataType.FLOAT64))
NATION = make_schema(("n_nationkey", DataType.INT32),
                     ("n_name", DataType.STRING))


class TestShardIntervals:
    def test_bounds_cover_token_space(self):
        for count in (1, 2, 3, 8, 32, 7):
            bounds = shard_interval_bounds(count)
            assert bounds[0][0] == INT32_MIN
            assert bounds[-1][1] == INT32_MAX
            for (lo1, hi1), (lo2, _) in zip(bounds, bounds[1:]):
                assert hi1 + 1 == lo2
                assert lo1 <= hi1

    def test_uniform_increment_matches_reference_formula(self):
        # hashTokenIncrement = HASH_TOKEN_COUNT / shardCount (create_shards.c:144)
        bounds = shard_interval_bounds(8)
        inc = (1 << 32) // 8
        for i, (lo, hi) in enumerate(bounds[:-1]):
            assert lo == INT32_MIN + i * inc
            assert hi == lo + inc - 1

    def test_owner_closed_form_agrees_with_ranges(self, rng):
        count = 7  # non-power-of-two stresses the clamp
        bounds = shard_interval_bounds(count)
        tokens = rng.integers(INT32_MIN, INT32_MAX + 1, size=5000, dtype=np.int64)
        idx = shard_index_for_token(tokens.astype(np.int32), count)
        for tok, i in zip(tokens, idx):
            lo, hi = bounds[i]
            assert lo <= tok <= hi

    def test_hash_token_deterministic_and_typed(self):
        a = hash_token(np.array([1, 2, 3], dtype=np.int64))
        b = hash_token(np.array([1, 2, 3], dtype=np.int64))
        np.testing.assert_array_equal(a, b)
        assert a.dtype == np.int32
        # int32 and int64 of the same value may differ (different mixers) but
        # each must be internally consistent
        c = hash_token(np.array([1, 2, 3], dtype=np.int32))
        assert c.dtype == np.int32

    def test_hash_distributes_evenly(self, rng):
        values = np.arange(200_000, dtype=np.int64)
        idx = shard_index_for_values(values, 8)
        counts = np.bincount(idx, minlength=8)
        assert counts.min() > 0.8 * counts.mean()
        assert counts.max() < 1.2 * counts.mean()


class TestCatalog:
    def _catalog_with_nodes(self, n=4):
        cat = Catalog()
        for i in range(n):
            cat.add_node(f"tpu:{i}")
        return cat

    def test_create_distributed_table_round_robin(self):
        cat = self._catalog_with_nodes(4)
        cat.create_distributed_table("orders", ORDERS, "o_orderkey", 8)
        shards = cat.table_shards("orders")
        assert len(shards) == 8
        owners = [cat.active_placement(s.shard_id).node_id for s in shards]
        assert owners == [1, 2, 3, 4, 1, 2, 3, 4]

    def test_colocated_table_follows_placements(self):
        cat = self._catalog_with_nodes(3)
        cat.create_distributed_table("orders", ORDERS, "o_orderkey", 6)
        cat.create_distributed_table("lineitem", LINEITEM, "l_orderkey", 6,
                                     colocate_with="orders")
        assert cat.tables_colocated("orders", "lineitem")
        for a, b in zip(cat.table_shards("orders"), cat.table_shards("lineitem")):
            assert (a.min_value, a.max_value) == (b.min_value, b.max_value)
            assert (cat.active_placement(a.shard_id).node_id
                    == cat.active_placement(b.shard_id).node_id)

    def test_default_colocation_by_shape(self):
        # same shard_count + distcol type ⇒ implicit colocation group reuse
        cat = self._catalog_with_nodes(2)
        cat.create_distributed_table("orders", ORDERS, "o_orderkey", 4)
        cat.create_distributed_table("lineitem", LINEITEM, "l_orderkey", 4)
        assert cat.tables_colocated("orders", "lineitem")

    def test_colocation_type_mismatch_rejected(self):
        cat = self._catalog_with_nodes(2)
        cat.create_distributed_table("orders", ORDERS, "o_orderkey", 4)
        other = make_schema(("k", DataType.INT32))
        with pytest.raises(CatalogError, match="matching distribution column"):
            cat.create_distributed_table("t2", other, "k", 4,
                                         colocate_with="orders")

    def test_reference_table_on_all_nodes(self):
        cat = self._catalog_with_nodes(3)
        cat.create_reference_table("nation", NATION)
        meta = cat.table("nation")
        assert meta.method == DistributionMethod.REFERENCE
        shards = cat.table_shards("nation")
        assert len(shards) == 1
        assert len(cat.shard_placements(shards[0].shard_id)) == 3

    def test_drop_table_removes_shards_and_placements(self):
        cat = self._catalog_with_nodes(2)
        cat.create_distributed_table("orders", ORDERS, "o_orderkey", 4)
        cat.drop_table("orders")
        assert not cat.has_table("orders")
        assert not cat.shards and not cat.placements

    def test_add_node_replicates_reference_tables(self):
        cat = self._catalog_with_nodes(2)
        cat.create_reference_table("nation", NATION)
        cat.add_node("tpu:9")
        shard = cat.table_shards("nation")[0]
        assert len(cat.shard_placements(shard.shard_id)) == 3

    def test_reference_tables_share_colocation_group(self):
        cat = self._catalog_with_nodes(2)
        cat.create_reference_table("nation", NATION)
        cat.create_reference_table("region", NATION)
        assert cat.tables_colocated("nation", "region")

    def test_remove_node_drops_reference_replicas(self):
        cat = self._catalog_with_nodes(3)
        cat.create_reference_table("nation", NATION)
        cat.remove_node("tpu:2")
        shard = cat.table_shards("nation")[0]
        assert len(cat.shard_placements(shard.shard_id)) == 2
        assert all(p.node_id in cat.nodes for p in cat.placements.values())

    def test_remove_node_with_placements_blocked(self):
        cat = self._catalog_with_nodes(2)
        cat.create_distributed_table("orders", ORDERS, "o_orderkey", 4)
        with pytest.raises(CatalogError, match="rebalance"):
            cat.remove_node("tpu:0")

    def test_duplicate_table_rejected(self):
        cat = self._catalog_with_nodes(1)
        cat.create_distributed_table("orders", ORDERS, "o_orderkey", 2)
        with pytest.raises(CatalogError, match="already distributed"):
            cat.create_distributed_table("orders", ORDERS, "o_orderkey", 2)

    def test_persistence_round_trip(self, tmp_path):
        cat = self._catalog_with_nodes(3)
        cat.create_distributed_table("orders", ORDERS, "o_orderkey", 6)
        cat.create_reference_table("nation", NATION)
        path = str(tmp_path / "catalog.json")
        cat.save(path)
        loaded = Catalog.load(path)
        assert loaded.to_json() == cat.to_json()
        # id allocators keep moving after reload
        assert loaded.allocate_shard_id() == cat._next_shard_id

    def test_version_bumps_on_ddl(self):
        cat = self._catalog_with_nodes(1)
        v0 = cat.version
        cat.create_distributed_table("orders", ORDERS, "o_orderkey", 2)
        assert cat.version > v0


class TestConfig:
    def test_defaults_and_set(self):
        from citus_tpu import Settings

        s = Settings()
        assert s.get("shard_count") == 8
        s.set("shard_count", 32)
        assert s.get("shard_count") == 32

    def test_validation(self):
        from citus_tpu import Settings
        from citus_tpu.errors import ConfigError

        s = Settings()
        with pytest.raises(ConfigError):
            s.set("shard_count", 0)
        with pytest.raises(ConfigError):
            s.set("columnar_compression", "lzma")
        with pytest.raises(ConfigError):
            s.set("no_such_var", 1)

    def test_override_context(self):
        from citus_tpu import Settings

        s = Settings()
        with s.override(shard_count=4):
            assert s.get("shard_count") == 4
        assert s.get("shard_count") == 8

    def test_bool_parsing(self):
        from citus_tpu import Settings
        from citus_tpu.errors import ConfigError

        s = Settings()
        s.set("enable_repartition_joins", "off")
        assert s.get("enable_repartition_joins") is False
        with pytest.raises(ConfigError, match="invalid boolean"):
            s.set("enable_repartition_joins", "treu")


class TestMaybeReloadPreservesTemps:
    """catalog.maybe_reload must MERGE the fresh on-disk catalog with
    this session's live in-memory temp reference tables — a wholesale
    replacement drops a mid-statement __intermediate_* CTE
    materialization the outer query is about to scan (ADVICE r5)."""

    def _disk_catalog(self, tmp_path):
        cat = Catalog()
        cat.add_node("device:1")
        cat.create_local_table("base", ORDERS)
        path = str(tmp_path / "catalog.json")
        cat.save(path)
        return path

    def test_reload_keeps_live_temp_tables(self, tmp_path):
        path = self._disk_catalog(tmp_path)
        mine = Catalog.load(path)
        # a statement materializes a CTE as a temp reference table
        # (in memory only — temps are never persisted)
        mine.create_reference_table("__intermediate_7", NATION)
        temp_shard = mine.table_shards("__intermediate_7")[0]
        # meanwhile another session commits DDL to the shared catalog
        other = Catalog.load(path)
        other.create_local_table("newtab", LINEITEM)
        other.save(path)
        assert mine.maybe_reload(path)
        # the committed DDL was adopted AND the live temp survived
        assert mine.has_table("newtab")
        assert mine.has_table("__intermediate_7")
        shards = mine.table_shards("__intermediate_7")
        assert [s.shard_id for s in shards] == [temp_shard.shard_id]
        assert mine.shard_placements(temp_shard.shard_id)
        # temps allocate from the reserved high range so the merge can
        # never clobber a shard id another session committed to disk
        from citus_tpu.catalog.catalog import TEMP_ID_BASE

        assert temp_shard.shard_id >= TEMP_ID_BASE
        # and the other session's committed shards all survived intact
        assert mine.table_shards("newtab")
        assert mine.table_shards("base")

    def test_reload_during_statement_with_live_temp(self, tmp_path):
        """End-to-end: a session holding a live temp mid-statement
        adopts another session's commit without losing the temp's scan
        (the seam session.execute hits via catalog.maybe_reload)."""
        import citus_tpu

        data_dir = str(tmp_path / "data")
        s1 = citus_tpu.connect(data_dir=data_dir, n_devices=2)
        s2 = citus_tpu.connect(data_dir=data_dir, n_devices=2)
        s1.execute("CREATE TABLE t (id INT, v INT)")
        s1.execute("SELECT create_distributed_table('t', 'id', 2)")
        s1.execute("INSERT INTO t VALUES (1, 10), (2, 20), (3, 30)")
        # hook the store so the reload fires while the temp is live:
        # after the CTE materializes (its rows handed to the store),
        # another session commits DDL and s1's catalog reloads
        # mid-statement
        orig_hold = s1.store.hold_resident
        fired = {"n": 0}

        def hold_hook(table, *a, **kw):
            rec = orig_hold(table, *a, **kw)
            if not fired["n"]:
                fired["n"] += 1
                s2.execute("CREATE TABLE other (x INT)")
                import os

                s1.catalog.maybe_reload(
                    os.path.join(data_dir, "catalog.json"))
            return rec

        s1.store.hold_resident = hold_hook
        try:
            r = s1.execute(
                "WITH c AS (SELECT id, v FROM t WHERE v >= 20) "
                "SELECT count(*), sum(v) FROM c")
        finally:
            del s1.store.hold_resident
        assert fired["n"] == 1
        assert [tuple(int(x) for x in row) for row in r.rows()] == \
            [(2, 50)]
        assert s1.catalog.has_table("other")
        s1.close()
        s2.close()
