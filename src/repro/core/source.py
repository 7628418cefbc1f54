"""Partitioned columnar sources.

A Source is the leaf of the task graph: an ordered list of partitions, each a
dict of 1-D column arrays.  Partition-major order is the engine's row order
(this replaces Dask's "no row order" caveat from the paper — our streaming
and distributed backends preserve partition-major order, see DESIGN §2).

Per-partition zone maps (min/max/rows) back the metadata store (§3.6) and
beyond-paper partition pruning.
"""
from __future__ import annotations

import json
import os
from typing import Mapping, Sequence

import numpy as np

from .schema import TableSchema, infer_schema, narrow_int_dtype


class Source:
    """Protocol: subclasses provide schema, dicts, n_partitions,
    load_partition, partition_meta."""

    schema: TableSchema
    dicts: dict[str, list]          # vocab per dict-encoded column
    name: str = "source"
    # scan-layer capabilities: whether the optimizer may sink filter
    # conjuncts into scans over this source (predicate evaluation happens
    # in the shared loader, so any host-array source qualifies), and
    # whether the streaming backend should decode partitions ahead on the
    # prefetch thread (only worthwhile when load_partition does real IO)
    supports_pushdown: bool = False
    prefetchable: bool = False

    @property
    def n_partitions(self) -> int:
        raise NotImplementedError

    def load_partition(self, i: int, columns: Sequence[str] | None = None
                       ) -> dict[str, np.ndarray]:
        raise NotImplementedError

    def partition_meta(self, i: int) -> dict:
        """{'rows': int, 'zonemap': {col: (min, max)}} — may be {} if stats
        were never computed."""
        return {}

    def cache_token(self):
        """Identity token used in ``Scan.key()``.  Disk-backed sources
        override with a path-stable token so plan keys (and therefore the
        persisted stats store's cardinality feedback) survive process
        restarts; in-memory sources stay identity-keyed."""
        return ("mem", id(self))

    def total_rows(self) -> int | None:
        metas = [self.partition_meta(i) for i in range(self.n_partitions)]
        if any("rows" not in m for m in metas):
            return None
        return sum(m["rows"] for m in metas)

    # -- planner-facing statistics extraction ------------------------------
    def total_bytes(self) -> int | None:
        """Estimated resident size of the full table (rows × schema width)."""
        rows = self.total_rows()
        if rows is None:
            return None
        return rows * self.schema.row_bytes()

    def column_ndv(self, name: str) -> int | None:
        """Distinct-count estimate for one column, from metadata only:
        exact vocab size for dict-encoded columns; integer zone-map span
        (capped by row count) for integer columns; None when unknown."""
        if name in self.dicts:
            return len(self.dicts[name])
        try:
            cs = self.schema.col(name)
        except KeyError:
            return None
        if cs.np_dtype.kind not in "iu":
            return None
        lo = hi = None
        for pi in range(self.n_partitions):
            zm = self.partition_meta(pi).get("zonemap", {})
            if name not in zm:
                return None
            plo, phi = zm[name]
            lo = plo if lo is None else min(lo, plo)
            hi = phi if hi is None else max(hi, phi)
        if lo is None:
            return None
        span = int(hi) - int(lo) + 1
        rows = self.total_rows()
        return min(span, rows) if rows is not None else span


def _zonemap(arrays: Mapping[str, np.ndarray]) -> dict:
    """Per-partition (min, max) column stats for partition skipping.

    When the kernel config resolves to a device implementation ("pallas"
    on TPU hosts) numeric columns route through the blocked
    ``repro.kernels.ops.zonemap`` kernel; host builds keep the numpy fast
    path — same contract, no device round-trip.  The bounds must hold for
    the host values that pushed-down predicates see: 64-bit ints, which
    the device would wrap to 32 bits, stay on the host, and float64 bounds
    computed in f32 are widened outward by one f32 step."""
    from ..kernels import ops as K
    on_device = K.get_kernel_config().resolved() == "pallas"
    zm = {}
    for name, arr in arrays.items():
        if arr.dtype.kind not in "ifu" or not arr.size:
            continue
        if on_device and (arr.dtype.kind == "f" or arr.dtype.itemsize <= 4):
            mins, maxs = K.zonemap(arr)
            lo, hi = np.asarray(mins).min(), np.asarray(maxs).max()
            if arr.dtype.itemsize > 4:
                lo = np.nextafter(lo, np.float32(-np.inf))
                hi = np.nextafter(hi, np.float32(np.inf))
            zm[name] = (lo.item(), hi.item())
        else:
            zm[name] = (arr.min().item(), arr.max().item())
    return zm


class InMemorySource(Source):
    """Arrays held in memory, split into fixed-size partitions."""

    supports_pushdown = True

    def __init__(self, arrays: Mapping[str, np.ndarray],
                 partition_rows: int = 1 << 16,
                 dicts: Mapping[str, Sequence] | None = None,
                 datetimes: Sequence[str] = (),
                 name: str = "mem"):
        lengths = {len(a) for a in arrays.values()}
        if len(lengths) != 1:
            raise ValueError("ragged columns")
        self._arrays = {k: np.asarray(v) for k, v in arrays.items()}
        self._rows = lengths.pop()
        self._part_rows = partition_rows
        self.dicts = {k: list(v) for k, v in (dicts or {}).items()}
        self.schema = infer_schema(self._arrays, self.dicts, datetimes)
        self.name = name
        self._metas = None
        self._token = None

    def cache_token(self):
        """Content fingerprint (dtype + shape + full-bytes hash) instead of
        object identity, so structural plan keys — and therefore the
        persisted stats store's cardinality/peak feedback — survive process
        restarts for in-memory plans too: a fresh process ingesting the
        same data produces the same token.

        The hash covers the *complete* column bytes: the token feeds
        correctness-bearing consumers (the persist cache serves results by
        plan key), so a sampled digest that collides for tables differing
        only in unsampled rows is not acceptable.  blake2b streams at
        ~1 GB/s and the digest is computed once per source and cached; the
        engine treats sources as immutable after ingest (as the identity
        token did)."""
        if self._token is None:
            import hashlib
            h = hashlib.blake2b(digest_size=16)
            h.update(str(self._rows).encode())
            for cname in sorted(self._arrays):
                arr = self._arrays[cname]
                h.update(cname.encode())
                h.update(str(arr.dtype).encode())
                h.update(str(arr.shape).encode())
                if arr.size:
                    h.update(np.ascontiguousarray(arr).tobytes())
            for cname in sorted(self.dicts):
                h.update(cname.encode())
                h.update(repr(self.dicts[cname]).encode())
            self._token = ("mem", self._rows, h.hexdigest())
        return self._token

    @property
    def n_partitions(self):
        return max(1, -(-self._rows // self._part_rows))

    def _bounds(self, i):
        lo = i * self._part_rows
        return lo, min(lo + self._part_rows, self._rows)

    def load_partition(self, i, columns=None):
        lo, hi = self._bounds(i)
        names = columns if columns is not None else list(self._arrays)
        return {n: self._arrays[n][lo:hi] for n in names}

    def partition_meta(self, i):
        if self._metas is None:
            self._metas = {}
        if i not in self._metas:
            lo, hi = self._bounds(i)
            part = {n: a[lo:hi] for n, a in self._arrays.items()}
            self._metas[i] = {"rows": hi - lo, "zonemap": _zonemap(part)}
        return self._metas[i]


class NpzDirectorySource(Source):
    """Out-of-core source: directory of part-NNNNN.npz files + _meta.json.

    This is the engine's "larger than memory" substrate — partitions are
    loaded one at a time by the streaming backend.  ``write_npz_source``
    builds one (and its metadata, incl. zone maps) from arrays or a
    generator.
    """

    supports_pushdown = True
    prefetchable = True

    def __init__(self, path: str):
        self.path = path
        with open(os.path.join(path, "_meta.json")) as f:
            meta = json.load(f)
        self._parts = meta["partitions"]          # list of {file, rows, zonemap}
        self.dicts = meta.get("dicts", {})
        cols = meta["columns"]                    # {name: {dtype, is_dict, is_datetime}}
        from .schema import ColumnSchema
        self.schema = TableSchema(tuple(
            ColumnSchema(n, c["dtype"], is_dict=c.get("is_dict", False),
                         dict_size=len(self.dicts.get(n, [])) or None,
                         is_datetime=c.get("is_datetime", False))
            for n, c in cols.items()))
        self.name = os.path.basename(path.rstrip("/"))
        if any("rows" not in p or "zonemap" not in p for p in self._parts):
            self._restore_stats()
        # content fingerprint over the partition metadata (files, row
        # counts, zone maps): a rewritten directory gets a fresh token, so
        # correctness-bearing key consumers (persist cache) never serve
        # stale results for structurally-identical plans over changed data
        import hashlib
        self._fingerprint = hashlib.md5(
            json.dumps(meta, sort_keys=True).encode()).hexdigest()[:16]

    def _restore_stats(self):
        """Fill missing per-partition rows/zone maps from the ``_stats.json``
        sidecar — or, when the sidecar is absent/stale, with ONE data scan
        whose result is persisted to the sidecar, so the next open of this
        directory is metadata-only.  (``_meta.json`` written by
        ``write_npz_source`` already carries stats; this path serves
        hand-built or pre-sidecar directories.)"""
        # function-level import: repro.io.parquet imports this module
        from repro.io import sidecar as SC
        files = [os.path.join(self.path, p["file"]) for p in self._parts]
        payload = SC.read_sidecar(self.path, data_files=files)
        if payload is None:
            stats = []
            for p in self._parts:
                with np.load(os.path.join(self.path, p["file"])) as z:
                    arrays = {n: z[n] for n in z.files}
                rows = len(next(iter(arrays.values()))) if arrays else 0
                stats.append({"file": p["file"], "rows": rows,
                              "zonemap": _zonemap(arrays)})
            payload = SC.write_sidecar(self.path, stats, data_files=files)
        by_file = {sp.get("file"): sp for sp in payload["partitions"]}
        for p in self._parts:
            sp = by_file.get(p["file"], {})
            if "rows" not in p and "rows" in sp:
                p["rows"] = sp["rows"]
            if "zonemap" not in p:
                p["zonemap"] = sp.get("zonemap", {})

    def cache_token(self):
        """Path-stable, covering file identity: the _meta.json content
        fingerprint plus the stats sidecar's mtime (0 when absent) — a
        rewritten directory or refreshed sidecar yields a fresh token."""
        from repro.io import sidecar as SC
        return ("npz", os.path.abspath(self.path), self._fingerprint,
                SC.sidecar_mtime_ns(self.path))

    @property
    def n_partitions(self):
        return len(self._parts)

    def load_partition(self, i, columns=None):
        with np.load(os.path.join(self.path, self._parts[i]["file"])) as z:
            names = columns if columns is not None else list(z.files)
            return {n: z[n] for n in names}

    def partition_meta(self, i):
        p = self._parts[i]
        return {"rows": p["rows"],
                "zonemap": {k: tuple(v) for k, v in p.get("zonemap", {}).items()}}


def write_npz_source(path: str, arrays: Mapping[str, np.ndarray],
                     partition_rows: int = 1 << 18,
                     dicts: Mapping[str, Sequence] | None = None,
                     datetimes: Sequence[str] = ()) -> NpzDirectorySource:
    os.makedirs(path, exist_ok=True)
    arrays = {k: np.asarray(v) for k, v in arrays.items()}
    dicts = {k: list(v) for k, v in (dicts or {}).items()}
    rows = len(next(iter(arrays.values())))
    parts = []
    for pi, lo in enumerate(range(0, rows, partition_rows)):
        hi = min(lo + partition_rows, rows)
        part = {k: a[lo:hi] for k, a in arrays.items()}
        fname = f"part-{pi:05d}.npz"
        np.savez(os.path.join(path, fname), **part)
        parts.append({"file": fname, "rows": hi - lo, "zonemap": _zonemap(part)})
    cols = {}
    for name, arr in arrays.items():
        cols[name] = {"dtype": str(arr.dtype), "is_dict": name in dicts,
                      "is_datetime": name in datetimes}
    meta = {"partitions": parts, "columns": cols, "dicts": dicts}
    with open(os.path.join(path, "_meta.json"), "w") as f:
        json.dump(meta, f)
    # stats sidecar at ingest: reopening never rescans data even if the
    # partition list is later rewritten without stats
    from repro.io import sidecar as SC
    SC.write_sidecar(path, parts, columns=cols, dicts=dicts,
                     datetimes=list(datetimes),
                     data_files=[os.path.join(path, p["file"])
                                 for p in parts])
    return NpzDirectorySource(path)


def encode_strings(values: Sequence[str]) -> tuple[np.ndarray, list]:
    """Dictionary-encode a string column (paper §3.6 category optimization)."""
    vocab, codes = np.unique(np.asarray(values, dtype=object), return_inverse=True)
    return codes.astype(np.int32), [str(v) for v in vocab]


def narrow_arrays(arrays: Mapping[str, np.ndarray],
                  float32: bool = True) -> dict[str, np.ndarray]:
    """Metadata-driven dtype narrowing (paper §3.6): ints to the smallest
    width that fits; float64→float32 when allowed."""
    out = {}
    for name, arr in arrays.items():
        if arr.dtype.kind == "i" and arr.size:
            out[name] = arr.astype(narrow_int_dtype(int(arr.min()), int(arr.max())))
        elif arr.dtype == np.float64 and float32:
            out[name] = arr.astype(np.float32)
        else:
            out[name] = arr
    return out
