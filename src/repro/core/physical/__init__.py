"""Unified physical-operator layer shared by every backend.

This package is the single home of physical execution: explicit operators
(scan helpers, the row-preserving pipeline, hash/sort join, group-by, sort,
distinct, reductions, segment handoff) over a common *table protocol* that
each backend binds to its native representation:

* eager       — whole-table ``dict[str, jnp.ndarray]`` on the default device
* streaming   — ``dict[str, np.ndarray]`` partition chunks (pull streams)
* distributed — :class:`ShardedTable` ``(n_shards, rows)`` device-sharded
                columns + validity mask

Module map
----------
``table``    host-table protocol helpers + handoff payload normalization
``rowwise``  row-preserving pipeline ops (filter/project/assign/…)
``groupby``  factorization + dense segment aggregation + partial/combine
``join``     host hash/sort join and aligned key factorization
``sort``     sort + distinct (host kernels)
``reduce``   whole-column reductions and partial forms
``sharded``  ShardedTable + *native distributed* join / sort / distinct
             (broadcast-hash and shuffle-by-dict-code exchanges)

``repro.core.exec_common`` re-exports everything here for back-compat.
"""
from __future__ import annotations

from .table import (Table, apply_concat, handoff_value, host_array, is_jax,
                    table_nbytes, table_rows, to_host_value, to_jax, to_numpy,
                    xp_of)
from .rowwise import (apply_assign, apply_astype, apply_fillna, apply_filter,
                      apply_fused_rowwise, apply_head, apply_map_rows,
                      apply_project, apply_rename)
from .groupby import (_factorize, _factorize_multi, apply_groupby_agg,
                      combine_partials, partial_aggs)
from .join import _factorize_multi_np_pair, apply_join
from .sort import apply_drop_duplicates, apply_sort, apply_top_k
from .reduce import REDUCE_PARTIAL, apply_reduce
from .sharded import (BROADCAST_BUILD_BYTES, ShardedTable, shard_host_table,
                      sharded_distinct, sharded_head, sharded_join,
                      sharded_sort)

__all__ = [
    "Table", "is_jax", "xp_of", "table_rows", "table_nbytes", "to_numpy",
    "to_jax", "host_array", "to_host_value", "handoff_value", "apply_concat",
    "apply_filter", "apply_project", "apply_assign", "apply_rename",
    "apply_astype", "apply_fillna", "apply_fused_rowwise", "apply_head",
    "apply_map_rows",
    "_factorize", "_factorize_multi", "apply_groupby_agg", "partial_aggs",
    "combine_partials", "apply_join", "_factorize_multi_np_pair",
    "apply_sort", "apply_top_k", "apply_drop_duplicates", "apply_reduce",
    "REDUCE_PARTIAL",
    "ShardedTable", "shard_host_table", "sharded_join", "sharded_sort",
    "sharded_distinct", "sharded_head", "BROADCAST_BUILD_BYTES",
]
