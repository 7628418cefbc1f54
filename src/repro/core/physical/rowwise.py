"""Row-preserving pipeline operators (np/jnp dispatch via the table
protocol).  These run identically on whole tables (eager), partition chunks
(streaming), and — lifted over ``(n_shards, rows)`` arrays — inside the
distributed backend's shard programs."""
from __future__ import annotations

from typing import Mapping, Sequence

import numpy as np

from .table import Table, host_array, table_rows, to_jax, to_numpy, xp_of
from ...obs.spans import engine_span, traced_op


@traced_op("filter")
def apply_filter(table: Table, predicate) -> Table:
    mask = predicate.evaluate(table)
    # boolean advanced indexing works eagerly for both np and jnp
    return {k: v[mask] for k, v in table.items()}


@traced_op("project")
def apply_project(table: Table, columns: Sequence[str]) -> Table:
    return {c: table[c] for c in columns}


@traced_op("assign")
def apply_assign(table: Table, name: str, expr) -> Table:
    out = dict(table)
    val = expr.evaluate(table)
    xp = xp_of(table)
    if np.isscalar(val) or getattr(val, "ndim", 1) == 0:
        val = xp.full((table_rows(table),), val)
    out[name] = val
    return out


@traced_op("rename")
def apply_rename(table: Table, mapping: Mapping[str, str]) -> Table:
    return {mapping.get(k, k): v for k, v in table.items()}


@traced_op("astype")
def apply_astype(table: Table, dtypes: Mapping[str, str]) -> Table:
    out = dict(table)
    for c, dt in dtypes.items():
        out[c] = out[c].astype(dt)
    return out


@traced_op("fillna")
def apply_fillna(table: Table, value, columns=None) -> Table:
    xp = xp_of(table)
    out = dict(table)
    for c in (columns or table.keys()):
        arr = out[c]
        if arr.dtype.kind == "f":
            out[c] = xp.where(xp.isnan(arr), xp.asarray(value, dtype=arr.dtype), arr)
    return out


@traced_op("head")
def apply_head(table: Table, n: int) -> Table:
    return {k: v[:n] for k, v in table.items()}


# ---------------------------------------------------------------------------
# Fused rowwise chains (graph.FusedRowwise, built by core.fuse)


def _apply_member(table: Table, m) -> Table:
    """One chain member, op-at-a-time (streaming chunks + the non-jit
    fallback).  Dispatches on op name so this module needs no graph import."""
    op = m.op
    if op == "filter":
        return apply_filter(table, m.predicate)
    if op == "project":
        return apply_project(table, m.columns)
    if op == "assign":
        return apply_assign(table, m.name, m.expr)
    if op == "rename":
        return apply_rename(table, m.mapping)
    if op == "astype":
        return apply_astype(table, m.dtypes)
    if op == "fillna":
        return apply_fillna(table, m.value, m.columns)
    raise NotImplementedError(f"fused member {op}")


# jitted composed chains keyed by (member params, kernel impl); jax caches
# compiled executables per input aval under each entry
_FUSED_JIT_CACHE: dict[tuple, object] = {}
_FUSED_JIT_CACHE_MAX = 256


def _kernel_cfg(impl: str | None):
    from ...kernels import ops as K
    if impl is None or impl == "auto":
        return K.get_kernel_config()
    return K.KernelConfig(impl=impl)


def _fused_jax_fn(ops: tuple, cfg):
    """Build (and cache) the single-dispatch jitted chain body.  Compute
    members run on full columns while Filter members AND into one deferred
    validity mask (every fusable op is elementwise, so values at surviving
    rows are unchanged).  Compaction happens in the caller: shapes depend
    on data, so packing inside the jit would force the scatter-based path
    even where a dynamic gather is cheaper."""
    import jax

    key = (tuple(m.key()[:-1] for m in ops), cfg.resolved())
    fn = _FUSED_JIT_CACHE.get(key)
    if fn is not None:
        return fn

    def composed(cols):
        import jax.numpy as jnp
        mask = None
        for m in ops:
            if m.op == "filter":
                pred = m.predicate.evaluate(cols)
                mask = pred if mask is None else (mask & pred)
            elif m.op == "project":
                cols = {c: cols[c] for c in m.columns}
            elif m.op == "assign":
                val = m.expr.evaluate(cols)
                if np.isscalar(val) or getattr(val, "ndim", 1) == 0:
                    val = jnp.full((table_rows(cols),), val)
                cols = dict(cols)
                cols[m.name] = val
            elif m.op == "rename":
                cols = {m.mapping.get(c, c): v for c, v in cols.items()}
            elif m.op == "astype":
                cols = dict(cols)
                for c, dt in m.dtypes.items():
                    cols[c] = cols[c].astype(dt)
            elif m.op == "fillna":
                cols = dict(cols)
                for c in (m.columns or tuple(cols)):
                    arr = cols[c]
                    if arr.dtype.kind == "f":
                        cols[c] = jnp.where(
                            jnp.isnan(arr),
                            jnp.asarray(m.value, dtype=arr.dtype), arr)
            else:
                raise NotImplementedError(f"fused member {m.op}")
        return cols, mask

    fn = jax.jit(composed)
    if len(_FUSED_JIT_CACHE) >= _FUSED_JIT_CACHE_MAX:
        _FUSED_JIT_CACHE.clear()
    _FUSED_JIT_CACHE[key] = fn
    return fn


def _output_columns(names, ops):
    """Column order the member chain would produce — jax.jit returns dict
    pytrees with *sorted* keys, so the caller must restore pandas order."""
    names = list(names)
    for m in ops:
        if m.op == "project":
            names = list(m.columns)
        elif m.op == "assign":
            if m.name not in names:
                names.append(m.name)
        elif m.op == "rename":
            names = [m.mapping.get(c, c) for c in names]
    return names


@traced_op("fused_rowwise")
def apply_fused_rowwise(table: Table, ops, impl: str | None = None) -> Table:
    """Execute a FusedRowwise chain as one composed pass.

    jnp tables: one device dispatch through a cached jitted body (no
    intermediate tables); Filter-terminated chains compact survivors on
    the device through XLA's scatter when ``impl`` resolves to "pallas"
    (TPU), and via a host boolean gather on "xla" hosts.  numpy tables
    (streaming chunks) and chains whose members call host code fall back
    to op-at-a-time members — identical semantics, just without the
    single-dispatch win."""
    if xp_of(table) is np:
        out = table
        for m in ops:
            out = _apply_member(out, m)
        return out
    import jax.errors as je
    cfg = _kernel_cfg(impl)
    try:
        cols, mask = _fused_jax_fn(tuple(ops), cfg)(dict(table))
    except (je.TracerArrayConversionError, je.ConcretizationTypeError,
            je.TracerBoolConversionError, je.TracerIntegerConversionError):
        # a member calls host code on traced values (an opaque UDF): run
        # unfused; compile and runtime failures propagate
        out = table
        for m in ops:
            out = _apply_member(out, m)
        return out
    cols = {c: cols[c] for c in _output_columns(table.keys(), ops)}
    if mask is None:
        return cols
    if cfg.resolved() == "pallas":
        # on the TPU, XLA's scatter compacts a 2e7-row column in 0.15 s
        # warm against 3.4 s for the Pallas kernel and its stitch (v5e,
        # chip_smoke.py), so the device path compacts through XLA
        from ...kernels import ops as K
        packed, count = K.filter_compact_columns(
            tuple(cols.values()), mask, K.KernelConfig(impl="xla"))
        with engine_span("sync", "compact"):
            k = int(count)
        return {c: v[:k] for c, v in zip(cols, packed)}
    # xla hosts: jax's eager dynamic gather re-dispatches per column and
    # loses badly to one host boolean gather; arrays round-trip through
    # numpy (near zero-copy on CPU) and come back device-resident
    host_mask = host_array(mask, "compact")
    return to_jax({c: v[host_mask]
                   for c, v in to_numpy(cols, "compact").items()}, "compact")


@traced_op("map_rows")
def apply_map_rows(table: Table, fn) -> Table:
    return fn(dict(table))
