"""Chunk/shard table protocol.

A host *table* is ``dict[str, array]`` of equal-length 1-D columns; arrays
are either numpy (host / streaming chunks) or jax (eager whole-table).  The
distributed backend's :class:`~repro.core.physical.sharded.ShardedTable`
binds the same column-dict shape to ``(n_shards, rows)`` device-sharded
arrays plus a validity mask.  Physical operators dispatch on the array type
(``xp_of``), so one implementation serves every chunk granularity.

Segment handoff payloads (``graph.Handoff``) are normalized here: host
tables, scalars, or — for distributed→distributed chains — device-resident
``ShardedTable`` values that never round-trip through host memory.

Every move of column data between numpy and the device goes through
:func:`to_numpy`, :func:`to_jax` or :func:`host_array`, which put it under
a ``transfer`` span named by its ``site`` and count its bytes.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ...obs.spans import engine_span

Table = dict


def is_jax(arr) -> bool:
    return isinstance(arr, jax.Array)


def xp_of(table: Table):
    for v in table.values():
        return jnp if is_jax(v) else np
    return np


def table_rows(table: Table) -> int:
    for v in table.values():
        return int(v.shape[0])
    return 0


def table_nbytes(table: Table) -> int:
    return sum(int(v.nbytes) for v in table.values())


def device_nbytes(arr) -> int:
    """Bytes a host array takes on the device, where 8-byte numbers narrow
    to 4 while x64 is off."""
    return int(np.size(arr)) * jax.dtypes.canonicalize_dtype(
        np.dtype(arr.dtype)).itemsize


def to_numpy(table: Table, site: str) -> Table:
    """Host copy of a table; device columns move under a d2h transfer."""
    moved = sum(int(v.nbytes) for v in table.values() if is_jax(v))
    if not moved:
        return {k: np.asarray(v) for k, v in table.items()}
    with engine_span("transfer", site, dir="d2h", bytes=moved):
        return {k: np.asarray(v) for k, v in table.items()}


def to_jax(table: Table, site: str) -> Table:
    """Device copy of a table; host columns move under an h2d transfer.
    The span covers the host's part of the copy (staging and enqueue), not
    the wait for it to land: the traced program is the untraced one."""
    moved = sum(device_nbytes(v) for v in table.values() if not is_jax(v))
    if not moved:
        return {k: jnp.asarray(v) for k, v in table.items()}
    with engine_span("transfer", site, dir="h2d", bytes=moved):
        return {k: jnp.asarray(v) for k, v in table.items()}


def host_array(arr, site: str) -> np.ndarray:
    """``np.asarray`` of one value; a device array moves under a d2h
    transfer."""
    if not is_jax(arr):
        return np.asarray(arr)
    with engine_span("transfer", site, dir="d2h", bytes=int(arr.nbytes)):
        return np.asarray(arr)


def apply_concat(tables: list[Table]) -> Table:
    xp = xp_of(tables[0])
    cols = set(tables[0])
    for t in tables[1:]:
        cols &= set(t)
    return {c: xp.concatenate([t[c] for t in tables]) for c in sorted(cols)}


# ---------------------------------------------------------------------------
# Segment handoff (operator-granular hybrid placement)
#
# When the planner splits one plan across engines, values crossing a segment
# boundary are normalized to host representation: tables become numpy column
# dicts, device scalars become python numbers.  This is the explicit
# materialization the cost model charges as transfer at every cut edge.
# The one exception is a distributed→distributed boundary, where the payload
# stays a device-resident ShardedTable (see ``runtime.execute_segments``).


def to_host_value(value):
    """Normalize a segment output for transfer to another engine."""
    from .sharded import ShardedTable
    if isinstance(value, ShardedTable):
        return value.gather()
    if isinstance(value, dict):
        return to_numpy(value, "handoff")
    if isinstance(value, (jax.Array, np.generic)):
        arr = host_array(value, "handoff")
        return arr.item() if arr.ndim == 0 else arr
    return value


def handoff_value(node, device_arrays: bool = False):
    """Evaluate a ``graph.Handoff`` leaf inside a backend: return its
    pre-materialized payload, converting tables onto the device when the
    consuming engine wants device-resident columns.  A device-resident
    ``ShardedTable`` payload is gathered defensively — only the distributed
    backend consumes it in place (``DistributedBackend._eval_inner``)."""
    from .sharded import ShardedTable
    v = node.value
    if isinstance(v, ShardedTable):
        v = v.gather()
    if isinstance(v, dict):
        return to_jax(v, "handoff") if device_arrays else v
    return v
