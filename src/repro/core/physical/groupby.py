"""Group-by aggregation: factorize keys → dense segment reductions.

The jnp path routes its sum-shaped reductions (sum/mean/count) through
``repro.kernels.ops.groupby_sum`` — the Pallas masked-sum kernel when the
kernel config resolves to "pallas", its jnp oracle otherwise; the partial/combine
pair is what the
streaming backend uses for out-of-core aggregation (memory scales with the
number of groups, not rows)."""
from __future__ import annotations

from typing import Mapping, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from .table import Table, is_jax, to_jax, to_numpy
from ...obs.spans import engine_span, traced_op


def _factorize(arr):
    """codes, uniques — order of uniques is sorted-value order.  On the
    device the number of uniques is read back before the codes exist: a
    sync."""
    if is_jax(arr):
        with engine_span("sync", "factorize"):
            uniques, codes = jnp.unique(arr, return_inverse=True)
    else:
        uniques, codes = np.unique(arr, return_inverse=True)
    return codes, uniques


def _factorize_multi(table: Table, cols: Sequence[str]):
    """Multi-column factorize via mixed-radix combination.

    Returns (codes, key_arrays_fn) where key_arrays_fn(group_codes) maps the
    final group code array back to per-column key values.
    """
    per = []
    radices = []
    for c in cols:
        codes, uniques = _factorize(table[c])
        per.append((codes, uniques))
        radices.append(int(uniques.shape[0]))
    xp = jnp if is_jax(per[0][0]) else np
    combined = per[0][0].astype(np.int64 if xp is np else jnp.int32)
    for (codes, _), r in zip(per[1:], radices[1:]):
        combined = combined * r + codes

    def decode(group_codes):
        out = {}
        rem = group_codes
        for (c, (_, uniques)), r in zip(
                reversed(list(zip(cols, per))), reversed(radices)):
            out[c] = uniques[rem % r]
            rem = rem // r
        return out

    return combined, decode


@traced_op("groupby_agg")
def apply_groupby_agg(table: Table, keys: Sequence[str],
                      aggs: Mapping[str, tuple[str, str]]) -> Table:
    """Dense aggregation: factorize keys → segment reductions.

    Device (jnp) tables dispatch sum-shaped reductions through the kernel
    layer (``repro.kernels.ops.groupby_sum``)."""
    combined, decode = _factorize_multi(table, list(keys))
    if is_jax(combined):
        with engine_span("sync", "factorize"):
            groups, inv = jnp.unique(combined, return_inverse=True)
        num = int(groups.shape[0])
        out = decode(groups)
        for out_name, (col, fn) in aggs.items():
            out[out_name] = _segment_agg_jax(table, col, fn, inv, num)
        if not all(is_jax(v) for v in out.values()):
            # an int sum left int32: the (num-row) result moves to the
            # host, where its int64 column can live
            out = to_numpy(out, "int_sum")
    else:
        groups, inv = np.unique(combined, return_inverse=True)
        num = int(groups.shape[0])
        out = decode(groups)
        for out_name, (col, fn) in aggs.items():
            out[out_name] = _segment_agg_np(table, col, fn, inv, num)
    return out


def _segment_agg_jax(table, col, fn, seg_ids, num):
    # sum-shaped aggregations dispatch through the kernel layer: the Pallas
    # kernel on TPU ("pallas"), the segment_sum oracle elsewhere
    from ...kernels import ops as K
    ones = jnp.ones((seg_ids.shape[0],), jnp.int32)   # exact counts
    if fn == "count":
        return K.groupby_sum(seg_ids, ones, num).astype(jnp.int64)
    vals = table[col]
    if fn == "sum" and vals.dtype.kind == "f":
        return K.groupby_sum(seg_ids, vals, num)
    if fn == "sum":
        # exact int sums: device int32 while every total fits, else host
        # int64 (x64 is off, so the device has no wider int)
        total = K.words_to_int64(*K.groupby_sum_words(seg_ids, vals, num))
        info = np.iinfo(np.int32)
        if total.size and (total.min() < info.min or total.max() > info.max):
            return total
        return to_jax({"sum": total.astype(np.int32)}, "int_sum")["sum"]
    if fn == "mean":
        s = K.groupby_sum(seg_ids, vals.astype(jnp.float32), num)
        c = K.groupby_sum(seg_ids, ones, num)
        return s / c
    if fn == "min":
        return jax.ops.segment_min(vals, seg_ids, num)
    if fn == "max":
        return jax.ops.segment_max(vals, seg_ids, num)
    if fn == "nunique":
        sub_codes, _ = _factorize(vals)
        pair = seg_ids.astype(jnp.int64) * (jnp.max(sub_codes) + 1) + sub_codes
        with engine_span("sync", "nunique"):
            uniq_pairs = jnp.unique(pair)
        seg_of_pair = uniq_pairs // (jnp.max(sub_codes) + 1)
        return jax.ops.segment_sum(jnp.ones_like(seg_of_pair), seg_of_pair, num)
    raise ValueError(f"unknown agg fn {fn}")


def _segment_agg_np(table, col, fn, seg_ids, num):
    if fn == "count":
        return np.bincount(seg_ids, minlength=num).astype(np.int64)
    vals = table[col]
    if fn == "sum":
        return np.bincount(seg_ids, weights=vals, minlength=num).astype(
            vals.dtype if vals.dtype.kind == "f" else np.float64)
    if fn == "mean":
        s = np.bincount(seg_ids, weights=vals.astype(np.float64), minlength=num)
        c = np.bincount(seg_ids, minlength=num)
        return s / np.maximum(c, 1)
    if fn in ("min", "max"):
        out = np.full(num, np.inf if fn == "min" else -np.inf, dtype=np.float64)
        ufn = np.minimum if fn == "min" else np.maximum
        ufn.at(out, seg_ids, vals.astype(np.float64))
        return out.astype(vals.dtype) if vals.dtype.kind == "f" else out
    if fn == "nunique":
        sub_codes, _ = _factorize(vals)
        pair = seg_ids.astype(np.int64) * (int(sub_codes.max()) + 1) + sub_codes
        uniq = np.unique(pair)
        seg = (uniq // (int(sub_codes.max()) + 1)).astype(np.int64)
        return np.bincount(seg, minlength=num).astype(np.int64)
    raise ValueError(f"unknown agg fn {fn}")


# partial/combine pairs for the streaming backend (out-of-core group-by).

_PARTIAL_FORMS = {
    "sum": ["sum"], "count": ["count"], "min": ["min"], "max": ["max"],
    "mean": ["sum", "count"],
}


def partial_aggs(aggs: Mapping[str, tuple[str, str]]):
    """Decompose logical aggs into partial aggs computable per partition."""
    partial = {}
    for out_name, (col, fn) in aggs.items():
        for p in _PARTIAL_FORMS[fn]:
            partial[f"{out_name}::{p}"] = (col, p)
    return partial


@traced_op("combine_partials")
def combine_partials(keys, parts: list[Table],
                     aggs: Mapping[str, tuple[str, str]]) -> Table:
    """Re-aggregate concatenated per-partition partials, then finalize."""
    xp = jnp if (parts and is_jax(next(iter(parts[0].values())))) else np
    concat = {k: xp.concatenate([p[k] for p in parts]) for k in parts[0]}
    combine_spec = {}
    for pname in concat:
        if "::" not in pname:
            continue
        _out, p = pname.rsplit("::", 1)
        combine_spec[pname] = (pname, "max" if p == "max" else
                               ("min" if p == "min" else "sum"))
    merged = apply_groupby_agg(concat, list(keys), combine_spec)
    out = {k: merged[k] for k in keys}
    for out_name, (_col, fn) in aggs.items():
        if fn == "mean":
            out[out_name] = (merged[f"{out_name}::sum"] /
                             xp.maximum(merged[f"{out_name}::count"], 1))
        elif fn == "count":
            # combining count partials goes through a weighted-sum path that
            # widens to float; counts are integral (pandas conformance)
            out[out_name] = merged[f"{out_name}::count"].astype(
                np.int64 if xp is np else jnp.int64)
        else:
            out[out_name] = merged[f"{out_name}::{fn}"]
    return out
