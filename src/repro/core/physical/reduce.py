"""Whole-column reductions to scalars, plus the partial forms the streaming
backend combines across partitions."""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from .table import Table, table_rows, xp_of
from ...obs.spans import NOOP_SPAN, engine_span, traced_op


@traced_op("reduce")
def apply_reduce(table: Table, column: str | None, fn: str):
    xp = xp_of(table)
    if fn == "count":
        return table_rows(table) if column is None else int(table[column].shape[0])
    vals = table[column]
    if xp is jnp and vals.dtype.kind in "iub" and vals.dtype.itemsize < 4:
        vals = vals.astype(jnp.int32)   # widen: no int8 accumulation
    if fn == "sum":
        return xp.sum(vals)
    if fn == "mean":
        return xp.mean(vals.astype(xp.float64 if xp is np else jnp.float32))
    if fn == "min":
        return xp.min(vals)
    if fn == "max":
        return xp.max(vals)
    if fn == "nunique":
        with (engine_span("sync", "nunique") if xp is jnp else NOOP_SPAN):
            return int(xp.unique(vals).shape[0])
    if fn == "median":
        # pandas skipna semantics; float64 on host like mean (jnp computes
        # in its native f32 precision)
        if vals.shape[0] == 0:
            return float("nan")
        if xp is np:
            return float(np.nanmedian(vals.astype(np.float64)))
        return jnp.nanmedian(vals.astype(jnp.float32))
    raise ValueError(fn)


REDUCE_PARTIAL = {
    "sum": ("sum", lambda xs, xp: xp.sum(xp.asarray(xs))),
    "min": ("min", lambda xs, xp: xp.min(xp.asarray(xs))),
    "max": ("max", lambda xs, xp: xp.max(xp.asarray(xs))),
    "count": ("count", lambda xs, xp: int(np.sum(xs))),
}
