"""ShardedTable — the distributed backend's binding of the table protocol —
plus *native* distributed join, sort, and distinct.

Physical model: columns are ``(n_shards, rows)`` device-sharded arrays over
the mesh ``data`` axis with a validity mask (fixed per-shard row count so
shapes stay static for XLA).

Native operators (previously eager fallbacks):

* join — **broadcast-hash** when the build side is small with unique keys:
  the build table is replicated, the probe side binary-searches the sorted
  build key codes entirely on device, and the output keeps the probe's
  shard layout (shape-preserving: validity-mask update + payload gather).
  Otherwise **shuffle-by-dict-code**: both sides are exchanged so equal key
  codes co-locate (``code % n_shards``), each shard runs the host hash-join
  kernel on its bucket, and an order-restoring exchange by probe row id
  reproduces the exact pandas (probe-order) output.
* sort — range partition by sampled splitters on the primary key, local
  stable lexsort per shard; shard-major gather order is globally sorted.
* distinct — shuffle by key code so duplicates co-locate, local keep-first
  by global row id, order-restoring exchange.

The exchanges are host-mediated here (on a CPU mesh every shard is
host-backed anyway); on a real multi-host mesh they correspond to all-to-all
collectives.  Native paths require integer (dictionary-coded) key columns —
the metadata store guarantees this for category columns; anything else
returns ``None`` and the caller falls back to the eager kernel.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .join import apply_join
from .sort import apply_drop_duplicates
from .table import device_nbytes, to_numpy
from ...obs.spans import engine_span, metric_inc, traced_op

# build sides at or below this many bytes replicate to every shard
# (broadcast-hash join); larger builds go through the shuffle exchange
BROADCAST_BUILD_BYTES = 4 << 20

_ROWID = "__lafp_rowid"
_VALID = "__lafp_valid"     # the validity mask, carried beside the columns
_CODES = "__lafp_codes"     # the build side's sorted key codes, likewise


def _put(arrays: dict, sharding, site: str) -> dict:
    """``jax.device_put`` of host arrays under one h2d transfer."""
    moved = sum(device_nbytes(v) for v in arrays.values())
    with engine_span("transfer", site, dir="h2d", bytes=moved):
        return {k: jax.device_put(v, sharding) for k, v in arrays.items()}


class ShardedTable:
    """(n_shards, rows) column arrays + validity mask, device-sharded."""

    def __init__(self, cols: dict[str, jax.Array], valid: jax.Array):
        self.cols = cols
        self.valid = valid  # (n_shards, rows) bool

    @property
    def n_shards(self) -> int:
        return int(self.valid.shape[0])

    def rows(self) -> int:
        """Valid (unpadded) row count across all shards."""
        with engine_span("sync", "rows"):
            return int(jnp.sum(self.valid))

    def nbytes(self) -> int:
        return sum(int(v.nbytes) for v in self.cols.values())

    def gather(self) -> dict[str, np.ndarray]:
        host = to_numpy(dict(self.cols, **{_VALID: self.valid}), "gather")
        mask = host.pop(_VALID).reshape(-1)
        return {k: v.reshape(-1)[mask] for k, v in host.items()}


@traced_op("sharded_head")
def sharded_head(t: ShardedTable, n: int) -> ShardedTable:
    """Native distributed ``head(n)``: keep the first ``n`` valid rows in
    partition-major order by masking — no gather, no re-shard.

    Row order is the flattened ``(shard, row)`` order (how
    ``shard_host_table`` laid the table out), so a global running count of
    valid rows identifies exactly the leading-shard prefix; trailing shards
    end up fully masked and the table stays device-resident and
    shape-preserving for downstream sharded operators."""
    flat = jnp.cumsum(t.valid.reshape(-1).astype(jnp.int32))
    keep = (flat <= n).reshape(t.valid.shape) & t.valid
    return ShardedTable(dict(t.cols), keep)


# ---------------------------------------------------------------------------
# Host <-> shard layout


@traced_op("shard_host_table")
def shard_host_table(full: dict[str, np.ndarray], mesh, axis: str
                     ) -> ShardedTable:
    """Pad a host table to a fixed per-shard row count and device-shard it."""
    S = mesh.shape[axis]
    rows = len(next(iter(full.values()))) if full else 0
    per = -(-max(rows, 1) // S)
    pad = S * per - rows
    valid = np.arange(S * per) < rows
    host = {}
    for c, v in full.items():
        v = np.asarray(v)
        vp = np.concatenate([v, np.zeros(pad, v.dtype)]) if pad else v
        host[c] = vp.reshape(S, per)
    host[_VALID] = valid.reshape(S, per)
    cols = _put(host, NamedSharding(mesh, P(axis)), "shard")
    return ShardedTable(cols, cols.pop(_VALID))


def _host_shards(t: ShardedTable) -> tuple[list[dict], list[np.ndarray], int]:
    """Per-shard host tables (valid rows only) plus global row ids.

    Global row id == position in ``gather()`` order, so restoring ascending
    row-id order after an exchange reproduces the pre-exchange row order."""
    cols = to_numpy(dict(t.cols, **{_VALID: t.valid}), "exchange")
    valid = cols.pop(_VALID)
    parts, rowids = [], []
    offset = 0
    for s in range(valid.shape[0]):
        m = valid[s]
        n = int(m.sum())
        parts.append({k: v[s][m] for k, v in cols.items()})
        rowids.append(offset + np.arange(n, dtype=np.int64))
        offset += n
    return parts, rowids, offset


def _restack(parts: list[dict[str, np.ndarray]], mesh, axis: str,
             template: dict[str, np.dtype]) -> ShardedTable:
    """Stack per-shard host tables (ragged row counts) back into a padded
    device-sharded layout.  ``template`` supplies dtypes for empty shards."""
    S = mesh.shape[axis]
    assert len(parts) == S, (len(parts), S)
    lens = [len(next(iter(p.values()))) if p else 0 for p in parts]
    per = max(max(lens), 1)
    host = {}
    for c, dt in template.items():
        stacked = np.zeros((S, per), dtype=dt)
        for s, p in enumerate(parts):
            if lens[s]:
                stacked[s, : lens[s]] = p[c]
        host[c] = stacked
    valid = np.zeros((S, per), dtype=bool)
    for s, n in enumerate(lens):
        valid[s, :n] = True
    host[_VALID] = valid
    cols = _put(host, NamedSharding(mesh, P(axis)), "exchange")
    return ShardedTable(cols, cols.pop(_VALID))


def _template(table: dict) -> dict[str, np.dtype]:
    return {k: np.asarray(v[:0]).dtype if hasattr(v, "__getitem__")
            else np.asarray(v).dtype for k, v in table.items()}


# ---------------------------------------------------------------------------
# Key coding: dictionary-coded (integer) key columns combine into one int64
# code via mixed radix over the union of both sides' value ranges, so equal
# tuples get equal codes with no cross-shard factorization pass.


def _int_keys(table_cols: dict, on: Sequence[str]) -> bool:
    for c in on:
        arr = table_cols.get(c)
        if arr is None or np.dtype(arr.dtype).kind not in "iu":
            return False
    return True


def _key_ranges(host_tables: list[dict], dev: ShardedTable | None,
                on: Sequence[str]) -> dict[str, tuple[int, int]] | None:
    """Per-key (min, max) over every participating table; None if any side
    has no rows to bound the range with."""
    ranges: dict[str, tuple[int, int]] = {}
    for c in on:
        los, his = [], []
        for t in host_tables:
            arr = np.asarray(t[c])
            if arr.size:
                los.append(int(arr.min()))
                his.append(int(arr.max()))
        if dev is not None and dev.rows():
            k = dev.cols[c]
            big = jnp.iinfo(k.dtype).max
            small = jnp.iinfo(k.dtype).min
            with engine_span("sync", "key_range"):
                los.append(int(jnp.min(jnp.where(dev.valid, k, big))))
                his.append(int(jnp.max(jnp.where(dev.valid, k, small))))
        if not los:
            return None
        ranges[c] = (min(los), max(his))
    return ranges


def _combined_radix(ranges: dict[str, tuple[int, int]],
                    on: Sequence[str]) -> list[tuple[int, int]] | None:
    """(offset, radix) per key column; None when the mixed-radix product
    overflows the device integer width (x32 mode → int32)."""
    out = []
    prod = 1
    for c in on:
        lo, hi = ranges[c]
        radix = hi - lo + 1
        prod *= radix
        out.append((lo, radix))
    if prod > (1 << 31) - 1:
        return None
    return out


def _host_code(table: dict, on: Sequence[str],
               spec: list[tuple[int, int]]) -> np.ndarray:
    code = np.zeros(len(np.asarray(table[on[0]])), np.int64)
    for c, (lo, radix) in zip(on, spec):
        code = code * radix + (np.asarray(table[c]).astype(np.int64) - lo)
    return code


def _device_code(t: ShardedTable, on: Sequence[str],
                 spec: list[tuple[int, int]]) -> jax.Array:
    code = jnp.zeros(t.valid.shape, jnp.int32)
    for c, (lo, radix) in zip(on, spec):
        code = code * radix + (t.cols[c].astype(jnp.int32) - lo)
    return code


# ---------------------------------------------------------------------------
# Native distributed join


@traced_op("sharded_join")
def sharded_join(probe: ShardedTable, build: dict, on: Sequence[str],
                 how: str, suffixes, mesh, axis: str) -> ShardedTable | None:
    """Join with the probe side device-resident.  ``build`` is a host table
    (a gathered/handoff/materialized right side).  Returns ``None`` when no
    native path applies — the caller falls back to the eager kernel."""
    on = list(on)
    if how not in ("inner", "left"):
        return None
    build = to_numpy(build, "join")
    if not (_int_keys(probe.cols, on) and _int_keys(build, on)):
        return None
    build_rows = len(next(iter(build.values()))) if build else 0
    if build_rows == 0 or probe.rows() == 0:
        return None
    ranges = _key_ranges([build], probe, on)
    if ranges is None:
        return None
    spec = _combined_radix(ranges, on)
    if spec is None:
        return None
    bcode = _host_code(build, on, spec)
    build_nbytes = sum(int(v.nbytes) for v in build.values())
    unique_build = np.unique(bcode).shape[0] == build_rows
    if unique_build and build_nbytes <= BROADCAST_BUILD_BYTES:
        pcode = _device_code(probe, on, spec)
        return _broadcast_hash_join(probe, pcode, build, bcode, on, how,
                                    suffixes)
    return _shuffle_join(probe, build, bcode, on, how, suffixes, spec,
                         mesh, axis)


def _broadcast_hash_join(probe: ShardedTable, pcode: jax.Array, build: dict,
                         bcode: np.ndarray, on, how, suffixes
                         ) -> ShardedTable:
    """Shape-preserving probe: replicate the (small, unique-key) build side,
    binary-search its sorted key codes on device, and emit the probe layout
    with gathered payload columns and an updated validity mask.  Never
    touches host memory for the probe side."""
    # the build side is replicated on the probe's mesh (jnp.asarray would
    # commit it to one device and pull every shard's probe through it)
    replicated = NamedSharding(probe.valid.sharding.mesh, P())
    order = np.argsort(bcode, kind="stable")
    payload = {k: v[order] for k, v in build.items() if k not in on}
    payload[_CODES] = bcode[order].astype(np.int32)
    payload = _put(payload, replicated, "broadcast")
    bsorted = payload.pop(_CODES)
    B = int(bsorted.shape[0])
    idx = jnp.searchsorted(bsorted, pcode.astype(jnp.int32))
    idx_c = jnp.clip(idx, 0, B - 1)
    matched = (idx < B) & (jnp.take(bsorted, idx_c) == pcode)
    overlap = (set(probe.cols) & set(build)) - set(on)
    out: dict[str, jax.Array] = {}
    for k in on:
        out[k] = probe.cols[k]
    for k, v in probe.cols.items():
        if k in on:
            continue
        out[k + suffixes[0] if k in overlap else k] = v
    for k, v in build.items():
        if k in on:
            continue
        name = k + suffixes[1] if k in overlap else k
        taken = jnp.take(payload[k], idx_c)
        if how == "left":
            if v.dtype.kind == "f":
                taken = jnp.where(matched, taken, jnp.nan)
            else:
                # mirror the host kernel: unmatched rows read build row 0
                taken = jnp.where(matched, taken,
                                  jnp.asarray(v[0], taken.dtype))
        out[name] = taken
    valid = probe.valid & matched if how == "inner" else probe.valid
    return ShardedTable(out, valid)


def _shuffle_join(probe: ShardedTable, build: dict, bcode: np.ndarray,
                  on, how, suffixes, spec, mesh, axis: str) -> ShardedTable:
    """Exchange both sides by key code so equal keys co-locate, run the host
    hash-join kernel per shard, then restore probe-row order by a second
    exchange on the carried global row id."""
    S = mesh.shape[axis]
    metric_inc("exchange.shuffles")
    metric_inc("exchange.shards", S)
    with engine_span("exchange", "join", shards=S):
        parts, rowids, total = _host_shards(probe)
        # exchange 1: co-locate by key code (shard-major iteration keeps
        # rows in global order inside every destination bucket)
        probe_buckets = [[] for _ in range(S)]
        for part, rid in zip(parts, rowids):
            if not len(rid):
                continue
            code = _host_code(part, on, spec)
            dest = code % S
            for s in range(S):
                m = dest == s
                if m.any():
                    b = {k: v[m] for k, v in part.items()}
                    b[_ROWID] = rid[m]
                    probe_buckets[s].append(b)
        build_buckets = []
        bdest = bcode % S
        for s in range(S):
            m = bdest == s
            build_buckets.append({k: v[m] for k, v in build.items()})
        # per-shard local join (the worker kernel)
        joined: list[dict] = []
        out_template: dict[str, np.dtype] | None = None
        for s in range(S):
            if probe_buckets[s]:
                pb = {k: np.concatenate([b[k] for b in probe_buckets[s]])
                      for k in probe_buckets[s][0]}
            else:
                pb = {k: np.asarray(v[:0]) for k, v in parts[0].items()}
                pb[_ROWID] = np.zeros(0, np.int64)
            j = apply_join(pb, build_buckets[s], on, how, suffixes)
            joined.append(j)
            if out_template is None:
                out_template = _template(j)
        # exchange 2: restore probe-row order — balanced row-id ranges per
        # shard, then a local stable sort by row id (stability keeps the
        # build-side match order the host kernel emitted)
        out_buckets: list[list[dict]] = [[] for _ in range(S)]
        for j in joined:
            rid = j[_ROWID]
            if not len(rid):
                continue
            dest = (rid * S) // max(total, 1)
            for s in range(S):
                m = dest == s
                if m.any():
                    out_buckets[s].append({k: v[m] for k, v in j.items()})
        final_parts = []
        for s in range(S):
            if out_buckets[s]:
                t = {k: np.concatenate([b[k] for b in out_buckets[s]])
                     for k in out_buckets[s][0]}
                order = np.argsort(t[_ROWID], kind="stable")
                t = {k: v[order] for k, v in t.items()}
            else:
                t = {k: np.zeros(0, dt) for k, dt in out_template.items()}
            t.pop(_ROWID, None)
            final_parts.append(t)
        template = {k: dt for k, dt in out_template.items() if k != _ROWID}
        return _restack(final_parts, mesh, axis, template)


# ---------------------------------------------------------------------------
# Native distributed sort


@traced_op("sharded_sort")
def sharded_sort(t: ShardedTable, by: Sequence[str], ascending: bool,
                 mesh, axis: str) -> ShardedTable | None:
    """Range-partition by sampled splitters on the primary key, then a local
    stable lexsort per shard; shard-major gather order is globally sorted
    (descending = globally reversed ascending, matching the host kernel)."""
    by = list(by)
    if any(b not in t.cols for b in by):
        return None
    S = mesh.shape[axis]
    with engine_span("exchange", "sort", shards=S):
        parts, _rowids, total = _host_shards(t)
        template = _template(parts[0])
        if total == 0:
            return _restack([dict(p) for p in parts[:S]], mesh, axis, template)
        # splitters from per-shard samples of the primary sort key
        samples = []
        for p in parts:
            key = np.asarray(p[by[0]])
            if key.size:
                step = max(1, key.size // 64)
                samples.append(np.sort(key)[::step])
        merged = np.sort(np.concatenate(samples))
        cut = [merged[(i * merged.size) // S] for i in range(1, S)]
        splitters = np.asarray(cut, dtype=merged.dtype)
        metric_inc("exchange.shuffles")
        metric_inc("exchange.shards", S)
        buckets: list[list[dict]] = [[] for _ in range(S)]
        for p in parts:
            key = np.asarray(p[by[0]])
            if not key.size:
                continue
            dest = np.searchsorted(splitters, key, side="right")
            for s in range(S):
                m = dest == s
                if m.any():
                    buckets[s].append({k: v[m] for k, v in p.items()})
        sorted_parts = []
        for s in range(S):
            if buckets[s]:
                merged_b = {k: np.concatenate([b[k] for b in buckets[s]])
                            for k in buckets[s][0]}
                keys = tuple(merged_b[b] for b in reversed(by))
                idx = (np.lexsort(keys) if len(keys) > 1
                       else np.argsort(keys[0], kind="stable"))
                sorted_parts.append({k: v[idx] for k, v in merged_b.items()})
            else:
                sorted_parts.append({k: np.zeros(0, dt)
                                     for k, dt in template.items()})
        if not ascending:
            sorted_parts = [{k: v[::-1] for k, v in p.items()}
                            for p in reversed(sorted_parts)]
        return _restack(sorted_parts, mesh, axis, template)


# ---------------------------------------------------------------------------
# Native distributed distinct


@traced_op("sharded_distinct")
def sharded_distinct(t: ShardedTable, subset, mesh, axis: str
                     ) -> ShardedTable | None:
    """Shuffle by key code so duplicate keys co-locate, keep the first
    occurrence (minimum global row id) per shard, then restore input order
    by an exchange on the kept row ids."""
    cols = list(subset) if subset else list(t.cols)
    if not _int_keys(t.cols, cols):
        return None
    S = mesh.shape[axis]
    with engine_span("exchange", "distinct", shards=S):
        parts, rowids, total = _host_shards(t)
        template = _template(parts[0])
        if total == 0:
            return _restack([dict(p) for p in parts[:S]], mesh, axis, template)
        ranges = _key_ranges(parts, None, cols)
        if ranges is None:
            return None
        spec = _combined_radix(ranges, cols)
        if spec is None:
            return None
        metric_inc("exchange.shuffles")
        metric_inc("exchange.shards", S)
        buckets: list[list[dict]] = [[] for _ in range(S)]
        for part, rid in zip(parts, rowids):
            if not len(rid):
                continue
            code = _host_code(part, cols, spec)
            dest = code % S
            for s in range(S):
                m = dest == s
                if m.any():
                    b = {k: v[m] for k, v in part.items()}
                    b[_ROWID] = rid[m]
                    buckets[s].append(b)
        # local keep-first (bucket rows arrive in ascending row-id order)
        kept: list[dict] = []
        for s in range(S):
            if buckets[s]:
                merged = {k: np.concatenate([b[k] for b in buckets[s]])
                          for k in buckets[s][0]}
                kept.append(apply_drop_duplicates(merged, cols))
            else:
                kept.append(None)
        # order-restoring exchange by kept row id
        out_buckets: list[list[dict]] = [[] for _ in range(S)]
        for k in kept:
            if k is None or not len(k[_ROWID]):
                continue
            dest = (k[_ROWID] * S) // max(total, 1)
            for s in range(S):
                m = dest == s
                if m.any():
                    out_buckets[s].append({c: v[m] for c, v in k.items()})
        final_parts = []
        for s in range(S):
            if out_buckets[s]:
                merged = {k: np.concatenate([b[k] for b in out_buckets[s]])
                          for k in out_buckets[s][0]}
                order = np.argsort(merged[_ROWID], kind="stable")
                merged = {k: v[order] for k, v in merged.items()}
            else:
                merged = {k: np.zeros(0, dt) for k, dt in template.items()}
            merged.pop(_ROWID, None)
            final_parts.append(merged)
        return _restack(final_parts, mesh, axis, template)
