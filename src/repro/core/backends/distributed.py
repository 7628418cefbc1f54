"""Distributed backend: shard_map execution over the mesh ``data`` axis (the
Modin/cluster analogue of paper §2.6).

Physical model: each source partition group is padded to a fixed per-shard
row count and stacked to ``(n_shards, rows)`` with a validity mask
(``physical.ShardedTable``).  Row-wise ops and mask updates run inside a
single jit+shard_map program per pipeline stage; reductions and group-bys
compute shard-local partials and combine with ``jax.lax.psum`` over the data
axis.  Group-by keys must be dictionary-coded / small-domain ints (the
metadata store guarantees this for category columns), giving a dense
``segment_sum`` of size G per shard — the same dense code domain the group-by
kernel uses on TPU.

Join, sort, and distinct are *native* (``physical.sharded``): broadcast-hash
join for small unique-key build sides (device-resident, shape-preserving),
shuffle-by-dict-code join / sort / distinct otherwise, all producing
device-resident ``ShardedTable`` outputs.  Only genuinely unsupported cases
(non-integer keys, unbounded key domains, exotic ``how=``) fall back to the
eager kernel — mirroring the paper's "convert to Pandas, run, convert back"
fallback for unsupported Dask ops.

Segment handoffs: ``execute(..., keep_sharded=...)`` lets the runtime keep
named roots device-resident, so distributed→distributed segment chains pass
``ShardedTable`` payloads through ``graph.Handoff`` without a host gather;
incoming sharded handoffs are consumed in place.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .. import graph as G
from .. import physical as X
from ..context import LaFPContext
from ..physical.sharded import ShardedTable
from .eager import EagerBackend

_DIST_OPS = ("scan", "filter", "project", "assign", "rename", "astype",
             "fillna", "fused_rowwise")


def _default_mesh() -> Mesh:
    devs = np.array(jax.devices())
    return Mesh(devs.reshape(len(devs)), ("data",))


class DistributedBackend:
    name = "distributed"
    supports_device_handoff = True

    def __init__(self, mesh: Mesh | None = None, axis: str = "data"):
        self.mesh = mesh or _default_mesh()
        self.axis = axis
        self._fallback = EagerBackend()

    # -- planning: greatest distributable subgraphs -------------------------
    def execute(self, roots: list[G.Node], ctx: LaFPContext,
                keep_sharded: frozenset[int] = frozenset()) -> dict[int, Any]:
        """Evaluate ``roots``.  Results are host values except for root ids
        in ``keep_sharded``, whose ``ShardedTable`` stays device-resident —
        the runtime requests this for distributed→distributed handoffs."""
        self._ctx = ctx
        results: dict[int, Any] = {}
        memo: dict[int, Any] = {}        # shared: CSE'd subtrees run once
        for r in roots:
            v = self._eval(r, memo)
            if isinstance(v, ShardedTable) and r.id not in keep_sharded:
                # ShardedTable is internal representation; callers (runtime
                # _wrap, host segment handoffs) expect host tables
                v = v.gather()
            results[r.id] = v
        return results

    def _eval(self, n: G.Node, memo: dict[int, Any]) -> Any:
        if n.id in memo:
            return memo[n.id]
        key = getattr(n, "cache_key", None) or n.key()
        if not isinstance(n, G.SinkPrint) and key in self._ctx.persist_cache:
            self._ctx.persist_stats["hits"] += 1
            memo[n.id] = self._ctx.persist_cache[key]
            return memo[n.id]
        out = self._eval_inner(n, memo)
        if n.persist and not isinstance(n, (G.SinkPrint, G.Materialized)):
            val = out.gather() if isinstance(out, ShardedTable) else out
            self._ctx.persist_cache[key] = val
            self._ctx.persist_stats["misses"] += 1
            out = val
        memo[n.id] = out
        return out

    def _eval_inner(self, n: G.Node, memo) -> Any:
        if isinstance(n, G.Handoff):
            v = n.value
            if isinstance(v, ShardedTable):
                if v.n_shards == self._n_shards():
                    return v                  # device-resident, no re-shard
                return X.shard_host_table(v.gather(), self.mesh, self.axis)
            return X.handoff_value(n)
        if isinstance(n, G.Materialized):
            return dict(n.table)
        if isinstance(n, G.SinkPrint):
            if len(n.inputs) > n.n_data:
                self._eval(n.inputs[n.n_data], memo)
            vals = []
            for i in n.inputs[: n.n_data]:
                v = self._eval(i, memo)
                vals.append(v.gather() if isinstance(v, ShardedTable) else v)
            from ..sinks import render_sink
            render_sink(n, vals, self._ctx)
            return None
        if isinstance(n, G.Scan):
            return self._load_sharded(n)
        if n.op in _DIST_OPS:
            child = self._eval(n.inputs[0], memo)
            if isinstance(child, ShardedTable):
                try:
                    return self._rowwise_sharded(n, child)
                except Exception as e:  # noqa: BLE001 — e.g. host-numpy UDF
                    # exprs that cannot be jit-traced: gather and delegate
                    # like any other unsupported op — but never silently
                    # (a genuine native-kernel bug must stay visible)
                    from ...obs.events import PlannerEvent
                    from ...obs.spans import metric_inc
                    self._ctx.planner_trace.append(PlannerEvent(
                        f"distributed: {n.op}#{n.id} native path failed, "
                        f"falling back ({type(e).__name__}: {e})",
                        kind="native-fallback", op=n.op, node_id=n.id,
                        error=type(e).__name__))
                    metric_inc("distributed.native_fallbacks")
                    return self._fallback_node(n, [child])
            return self._fallback_node(n, [child])
        if isinstance(n, G.Reduce):
            child = self._eval(n.inputs[0], memo)
            if isinstance(child, ShardedTable) and n.fn in ("sum", "mean",
                                                            "count", "min", "max"):
                return self._reduce_sharded(n, child)
            return self._fallback_node(n, [child])
        if isinstance(n, G.Length):
            child = self._eval(n.inputs[0], memo)
            if isinstance(child, ShardedTable):
                return int(jnp.sum(child.valid))
            return self._fallback_node(n, [child])
        if isinstance(n, G.GroupByAgg):
            child = self._eval(n.inputs[0], memo)
            if isinstance(child, ShardedTable):
                dense = self._try_groupby_sharded(n, child)
                if dense is not None:
                    return dense
            return self._fallback_node(
                n, [child.gather() if isinstance(child, ShardedTable) else child])
        if isinstance(n, G.Join):
            left = self._eval(n.inputs[0], memo)
            right = self._eval(n.inputs[1], memo)
            if isinstance(left, ShardedTable):
                build = right.gather() if isinstance(right, ShardedTable) else right
                if isinstance(build, dict):
                    out = X.sharded_join(left, build, n.on, n.how, n.suffixes,
                                         self.mesh, self.axis)
                    if out is not None:
                        return out
            return self._fallback_node(n, [left, right])
        if isinstance(n, G.SortValues):
            child = self._eval(n.inputs[0], memo)
            if isinstance(child, ShardedTable):
                out = X.sharded_sort(child, n.by, n.ascending,
                                     self.mesh, self.axis)
                if out is not None:
                    return out
            return self._fallback_node(n, [child])
        if isinstance(n, G.DropDuplicates):
            child = self._eval(n.inputs[0], memo)
            if isinstance(child, ShardedTable):
                out = X.sharded_distinct(child, n.subset, self.mesh, self.axis)
                if out is not None:
                    return out
            return self._fallback_node(n, [child])
        if isinstance(n, G.Head):
            child = self._eval(n.inputs[0], memo)
            if isinstance(child, ShardedTable) and n.n >= 0:
                # native head: serve from the leading shard(s) by masking —
                # no gather, no re-shard (physical.sharded_head).  Negative
                # n (pandas all-but-last-n) takes the host fallback.
                return X.sharded_head(child, n.n)
            return self._fallback_node(n, [child])
        # fallback for concat/maprows and unsupported native cases
        vals = []
        for i in n.inputs:
            v = self._eval(i, memo)
            vals.append(v.gather() if isinstance(v, ShardedTable) else v)
        return self._fallback_node(n, vals)

    def _fallback_node(self, n: G.Node, vals: list[Any]):
        vals = [v.gather() if isinstance(v, ShardedTable) else v for v in vals]
        return self._fallback.eval_node(n, vals, self._ctx)

    # -- sharded physical ops -------------------------------------------------
    def _n_shards(self) -> int:
        return self.mesh.shape[self.axis]

    def _load_sharded(self, n: G.Scan) -> ShardedTable:
        # shared pushdown-aware loader (repro.io): per-partition column
        # projection + pushed-down predicate, io.* accounting
        from repro.io.scan import (empty_scan_table, load_scan_partition,
                                   scan_partition_indices)
        ctx = self._ctx
        metrics = getattr(ctx, "metrics", None)
        tracer = getattr(ctx, "tracer", None)
        if metrics is not None and n.skip_partitions:
            metrics.inc("io.partitions_pruned", len(n.skip_partitions))
        parts = [load_scan_partition(n, pi, metrics=metrics, tracer=tracer)
                 for pi in scan_partition_indices(n)]
        if not parts:
            parts = [empty_scan_table(n)]
        full = {c: np.concatenate([p[c] for p in parts]) for c in parts[0]}
        return X.shard_host_table(full, self.mesh, self.axis)

    def _rowwise_sharded(self, n: G.Node, t: ShardedTable) -> ShardedTable:
        if isinstance(n, G.Filter):
            pred = n.predicate

            @partial(jax.jit)
            def upd(cols, valid):
                mask = pred.evaluate(cols)
                return valid & mask

            valid = upd(t.cols, t.valid)
            return ShardedTable(dict(t.cols), valid)
        if isinstance(n, G.Project):
            return ShardedTable({c: t.cols[c] for c in n.columns}, t.valid)
        if isinstance(n, G.Assign):
            expr = n.expr

            @partial(jax.jit)
            def mk(cols):
                return expr.evaluate(cols)

            val = mk(t.cols)
            if getattr(val, "ndim", 0) != 2:
                val = jnp.broadcast_to(val, t.valid.shape)
            out = dict(t.cols)
            out[n.name] = val
            return ShardedTable(out, t.valid)
        if isinstance(n, G.Rename):
            return ShardedTable({n.mapping.get(c, c): v
                                 for c, v in t.cols.items()}, t.valid)
        if isinstance(n, G.AsType):
            out = dict(t.cols)
            for c, dt in n.dtypes.items():
                out[c] = out[c].astype(dt)
            return ShardedTable(out, t.valid)
        if isinstance(n, G.FillNa):
            out = dict(t.cols)
            for c in (n.columns or list(out)):
                arr = out[c]
                if arr.dtype.kind == "f":
                    out[c] = jnp.where(jnp.isnan(arr),
                                       jnp.asarray(n.value, arr.dtype), arr)
            return ShardedTable(out, t.valid)
        if isinstance(n, G.FusedRowwise):
            # members reuse the per-op sharded paths above; the validity
            # mask plays the deferred-filter role, so no compaction needed
            out = t
            for m in n.ops:
                out = self._rowwise_sharded(m, out)
            return out
        raise NotImplementedError(n.op)

    def _reduce_sharded(self, n: G.Reduce, t: ShardedTable):
        fn = n.fn
        mesh, axis = self.mesh, self.axis

        col = t.cols[n.column] if n.column else None
        valid = t.valid

        @partial(jax.jit)
        def run(col, valid):
            def local(col, valid):
                v = valid
                if fn == "count":
                    r = jnp.sum(v, dtype=jnp.int32)
                elif fn == "sum":
                    r = jnp.sum(jnp.where(v, col, 0))
                elif fn == "mean":
                    s = jnp.sum(jnp.where(v, col.astype(jnp.float32), 0.0))
                    c = jnp.sum(v, dtype=jnp.float32)
                    r = jnp.stack([s, c])
                elif fn == "min":
                    r = jnp.min(jnp.where(v, col, jnp.inf if col.dtype.kind == "f"
                                          else jnp.iinfo(col.dtype).max))
                elif fn == "max":
                    r = jnp.max(jnp.where(v, col, -jnp.inf if col.dtype.kind == "f"
                                          else jnp.iinfo(col.dtype).min))
                return r

            f = jax.shard_map(
                lambda c, v: _psum_combine(fn, local(c[0], v[0]), axis),
                mesh=mesh,
                in_specs=(P(axis), P(axis)),
                out_specs=P())
            if col is None:
                zero = jnp.zeros_like(valid, dtype=jnp.int32)
                return f(zero, valid)
            return f(col, valid)

        out = run(col if col is not None else None, valid)
        if fn == "mean":
            return float(out[0] / jnp.maximum(out[1], 1))
        if fn == "count":
            return int(out)
        return out

    def _try_groupby_sharded(self, n: G.GroupByAgg, t: ShardedTable):
        """Dense group-by when the key domain is small & known (dict codes)."""
        if len(n.keys) != 1:
            return None
        key = n.keys[0]
        karr = t.cols.get(key)
        if karr is None or karr.dtype.kind not in "iu":
            return None
        kmax = int(jnp.max(jnp.where(t.valid, karr, 0)))
        G_dom = kmax + 1
        if G_dom > 1 << 16:
            return None
        mesh, axis = self.mesh, self.axis
        fns = {out: fn for out, (_c, fn) in n.aggs.items()}
        if not set(fns.values()) <= {"sum", "count", "mean", "min", "max"}:
            return None
        cols_needed = {c for (c, _fn) in n.aggs.values() if c is not None}
        value_cols = {c: t.cols[c] for c in cols_needed}

        @partial(jax.jit, static_argnames=("gdom",))
        def run(karr, valid, vals, gdom):
            def local(k, v, vals):
                # sums and counts go through the kernel layer (the Pallas
                # group-by on TPU): int sums as exact (hi, lo) words, and
                # f32 sums accumulate per lane and block rather than in
                # XLA's scatter-add, which drifts by ~3e-4 at 1e6+ rows a
                # group
                from ...kernels import ops as K
                k = jnp.where(v, k, gdom)  # invalid rows to overflow bucket
                outs = {}
                cnt = K.groupby_sum(k, v.astype(jnp.int32), gdom + 1)
                for out_name, (c, fn) in n.aggs.items():
                    if fn == "count":
                        outs[out_name] = cnt
                    elif fn in ("sum", "mean"):
                        x = jnp.where(v, vals[c], 0)
                        outs[out_name] = (
                            K.groupby_sum(k, x, gdom + 1) if x.dtype.kind == "f"
                            else jnp.stack(K.groupby_sum_words(k, x, gdom + 1)))
                    else:
                        x = vals[c]
                        lo, hi = ((-jnp.inf, jnp.inf) if x.dtype.kind == "f"
                                  else (jnp.iinfo(x.dtype).min,
                                        jnp.iinfo(x.dtype).max))
                        seg = (jax.ops.segment_min if fn == "min"
                               else jax.ops.segment_max)
                        x = jnp.where(v, x, hi if fn == "min" else lo)
                        outs[out_name] = seg(x, k, gdom + 1)
                outs["__count"] = cnt
                return outs

            def shard_fn(k, v, *vlist):
                vals_d = {name: arr[0] for name, arr in
                          zip(sorted(value_cols), vlist)}
                outs = local(k[0], v[0], vals_d)
                comb = {}
                for name, arr in outs.items():
                    fn = fns.get(name, "count" if name == "__count" else "sum")
                    comb[name] = _psum_combine(
                        "min" if fn == "min" else ("max" if fn == "max" else "sum"),
                        arr, axis)
                return comb

            # check_vma off: the Pallas kernel's outputs carry no
            # varying-axes annotation for shard_map to check
            return jax.shard_map(
                shard_fn, mesh=mesh,
                in_specs=(P(axis), P(axis)) + tuple(P(axis) for _ in value_cols),
                out_specs=P(), check_vma=False)(karr, valid,
                               *[vals[c] for c in sorted(value_cols)])

        from ...kernels import ops as K
        vals = {c: value_cols[c] for c in sorted(value_cols)}
        outs = run(karr, t.valid, vals, G_dom)
        cnt = np.asarray(outs["__count"][:G_dom])
        groups = np.nonzero(cnt > 0)[0]
        result = {key: groups.astype(np.dtype(karr.dtype))}
        for out_name, (_c, fn) in n.aggs.items():
            arr = outs[out_name]
            # int sums come back as (hi, lo) words, exact in int64 here
            arr = (K.words_to_int64(arr[0], arr[1]) if arr.ndim == 2
                   else np.asarray(arr))[:G_dom]
            if fn == "mean":
                result[out_name] = (arr / np.maximum(cnt, 1))[groups]
            elif fn == "count":
                result[out_name] = arr.astype(np.int64)[groups]
            else:
                result[out_name] = arr[groups]
        return result


def _psum_combine(fn: str, arr, axis: str):
    if fn == "min":
        return jax.lax.pmin(arr, axis)
    if fn == "max":
        return jax.lax.pmax(arr, axis)
    return jax.lax.psum(arr, axis)
