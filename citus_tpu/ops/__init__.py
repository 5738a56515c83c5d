from .aggregate import distinct, segment_aggregate
from .groupby import (
    bucketed_grid_aggregate,
    group_bucket_count,
    group_bucket_eligible,
)
from .hashing import (
    combine_hash64,
    fmix32_jax,
    hash_token_jax,
    shard_index_for_values_jax,
    shard_index_from_token,
)
from .join import (
    dense_unique_lookup,
    expand_join,
    expand_join_pairs,
    lookup_join,
    lower_bound,
    match_counts,
    sort_build_side,
)
from .partition import pack_by_target

__all__ = [
    "distinct", "segment_aggregate",
    "bucketed_grid_aggregate", "group_bucket_count",
    "group_bucket_eligible",
    "combine_hash64", "fmix32_jax",
    "hash_token_jax", "shard_index_for_values_jax", "shard_index_from_token",
    "dense_unique_lookup",
    "expand_join", "expand_join_pairs", "lookup_join", "lower_bound",
    "match_counts",
    "sort_build_side", "pack_by_target",
]
