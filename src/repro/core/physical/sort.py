"""Sort and distinct (host + device whole-table kernels).

Stable lexsort keeps pandas row-order semantics (descending = reversed
ascending, ties included); distinct keeps first occurrences in input order.
The distributed shuffle variants in ``sharded.py`` reuse these as their
per-shard local kernels."""
from __future__ import annotations

from typing import Sequence

import numpy as np

import jax.numpy as jnp

from .groupby import _factorize_multi
from .table import Table, host_array, xp_of
from ...obs.spans import engine_span, traced_op


@traced_op("sort")
def apply_sort(table: Table, by: Sequence[str], ascending: bool = True) -> Table:
    xp = xp_of(table)
    # lexsort: last key is primary in np.lexsort; jnp has lexsort too.
    keys = tuple(table[b] for b in reversed(by))
    idx = xp.lexsort(keys) if len(keys) > 1 else xp.argsort(keys[0], stable=True)
    if not ascending:
        idx = idx[::-1]
    return {k: v[idx] for k, v in table.items()}


def _order_indices(cols, ascending: bool, ties_first: bool, xp):
    """Stable row ordering by ``cols`` (first column primary).

    ``ties_first=True`` keeps the first occurrence of equal keys first in
    the output (pandas ``keep='first'``); ``ties_first=False`` with
    descending reproduces the reversed-stable-ascending order of
    ``apply_sort(ascending=False)`` exactly."""
    def asc(cs):
        if len(cs) > 1:
            return xp.lexsort(tuple(reversed(cs)))
        return xp.argsort(cs[0], stable=True)

    if ascending:
        return asc(cols)                   # stable ascending ⇒ ties first
    if not ties_first:
        return asc(cols)[::-1]             # reversed stable ⇒ ties last
    # descending with first-occurrence ties: argsort the reversed arrays so
    # stability prefers the original first occurrence, then map back.
    n_rows = int(cols[0].shape[0])
    rev = asc(tuple(c[::-1] for c in cols))
    return ((n_rows - 1) - rev)[::-1]


@traced_op("top_k")
def apply_top_k(table: Table, by: Sequence[str], n: int,
                ascending: bool = True, mode: str = "sort") -> Table:
    """First ``n`` rows of the stable sort by ``by`` without materializing
    the full sorted table (only ``n`` rows of every column are gathered).

    ``mode="sort"`` equals ``apply_sort(table, by, ascending)[:n]`` row for
    row (ties, NaN placement included); ``mode="select"`` is pandas
    ``nlargest``/``nsmallest``: rows with NaN sort keys are dropped and
    ties keep the first occurrence.  The k selection indices are always
    computed on host numpy — they are tiny, the host partition/argsort
    avoids per-call device dispatch, and device columns are only gathered
    at the final k-row index — with an O(rows) ``np.partition`` threshold
    pass for single numeric keys so only ~n candidate rows are argsorted."""
    keys = [host_array(table[b], "top_k") for b in by]
    sel = None
    if mode == "select":
        mask = None
        for kk in keys:
            if kk.dtype.kind == "f":
                m = np.isnan(kk)
                mask = m if mask is None else (mask | m)
        if mask is not None and mask.any():
            sel = np.nonzero(~mask)[0]
            keys = [kk[sel] for kk in keys]
    total = int(keys[0].shape[0]) if keys else 0
    k = max(0, min(int(n), total))
    if k == 0:
        return {c: v[:0] for c, v in table.items()}
    ties_first = ascending or mode == "select"
    cand = None
    first = keys[0]
    if (len(keys) == 1 and k < total
            and first.dtype.kind in "biuf"
            and not (first.dtype.kind == "f" and np.isnan(first).any())):
        pos = k - 1 if ascending else total - k
        thr = np.partition(first, pos)[pos]
        cand = np.nonzero(first <= thr if ascending else first >= thr)[0]
        keys = [first[cand]]
    order = _order_indices(tuple(keys), ascending, ties_first, np)[:k]
    idx = cand[order] if cand is not None else order
    if sel is not None:
        idx = sel[idx]
    return {c: v[idx] for c, v in table.items()}


@traced_op("drop_duplicates")
def apply_drop_duplicates(table: Table, subset=None) -> Table:
    cols = list(subset) if subset else list(table.keys())
    codes, _ = _factorize_multi(table, cols)
    xp = xp_of(table)
    if xp is jnp:
        with engine_span("sync", "distinct"):    # the size of the result
            _, first_idx = jnp.unique(codes, return_index=True)
        idx = jnp.sort(first_idx)
    else:
        _, first_idx = np.unique(codes, return_index=True)
        idx = np.sort(first_idx)
    return {k: v[idx] for k, v in table.items()}
