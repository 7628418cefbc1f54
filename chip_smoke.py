#!/usr/bin/env python3
"""Bring-up check of the main path on a TPU (a check, not a benchmark).

One chip (default): the paper's ten programs (``benchmarks/programs.py``)
run through their normal entry points on the ``eager`` engine and its
Pallas kernels, at the paper's 1.4 GB taxi scale (2e7 rows, plus the
ratings and startups tables), all generated from a fixed seed.  Each result
is compared with the same program written in plain pandas on the same
arrays.  Before that, each kernel is run at the programs' sizes against its
pure-jnp oracle (``repro/kernels/ref.py``) and checked to be compiled by
Mosaic (a ``tpu_custom_call`` in its lowering), not interpreted, and the
compaction path is timed warm against ``ref.py``'s XLA scatter.

``--chips 4``: only the ``distributed`` engine over a mesh of every visible
device, at the paper's 4.2 GB scale (6e7 taxi rows), on the programs it runs
natively, against the same pandas reference; every sharded scan must place
shards on all devices.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # four chips, distributed engine

It exits non-zero and prints no result when JAX finds no TPU (there is no
CPU fallback), when ``repro`` cannot be imported next to this script, when
a result disagrees with the reference, when a kernel would run interpreted,
or when any phase raises.  The last line of a passing run is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.  JAX's
compile cache is kept where ``JAX_COMPILATION_CACHE_DIR`` says, else in
``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# Float results are sums and means accumulated in f32 on the device: the
# group-by kernel adds per-lane block partials one block after another
# (about 300 sequential adds per lane at 2e7 rows) and XLA reduces
# pairwise, so with f32's 6e-8 epsilon the worst-case relative error is
# ~2e-5.  1e-4 keeps a 5x margin.  The reference computes in f64 from the
# same f32 values.  Ints, counts, keys, maxima and selected rows must match
# exactly.
FLOAT_RTOL = 1e-4

ONE_CHIP_ROWS = 20_000_000       # paper's 1.4 GB taxi data set
FOUR_CHIP_ROWS = 60_000_000      # paper's 4.2 GB taxi data set
PARTITION_ROWS = ONE_CHIP_ROWS // 16   # zone-map partition of the 1.4 GB set
SEED = 0

EPOCH_LO, EPOCH_HI = 1_577_836_800, 1_609_459_200   # taxi pickup_datetime

# programs the distributed engine runs with no eager fallback (filter,
# group-by, join, distinct); sort+head is rewritten to TopK, which is not
DISTRIBUTED_PROGRAMS = ("taxi_agg", "taxi_feature", "taxi_filter",
                        "ratings_join", "startup_distinct",
                        "wide_projection")


class Mismatch(AssertionError):
    pass


def log(*parts) -> None:
    print(*parts, flush=True)


# ---------------------------------------------------------------------------
# compile accounting


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events, and each backend compile's seconds by function."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.backend: list[tuple[float, str]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, fun_name="?", **_):
        if event in self.EVENTS:
            self.seconds += duration
            if event == self.EVENTS[2]:
                self.backend.append((duration, fun_name))

    def mark(self) -> tuple[float, int]:
        return self.seconds, len(self.backend)

    def slowest(self, since: int, k: int = 3) -> str:
        """The ``k`` longest backend compiles after mark ``since``."""
        top = sorted(self.backend[since:], reverse=True)[:k]
        return ", ".join(f"{name} {sec:.3f}s" for sec, name in top)


# ---------------------------------------------------------------------------
# kernels against their oracles


def check_kernels(rows: int, part_rows: int) -> None:
    import jax
    from repro.kernels import ops as K, ref

    cfg = K.KernelConfig(impl="pallas")
    k = jax.random.split(jax.random.key(SEED), 6)
    codes = jax.random.randint(k[0], (rows,), 0, 7)
    fvals = jax.random.uniform(k[1], (rows,), minval=-5.0, maxval=100.0)
    ivals = jax.random.randint(k[2], (rows,), 0, 1 << 20)  # sums ≫ 2^31
    epoch = jax.random.randint(k[3], (rows,), EPOCH_LO, EPOCH_HI)
    mask = jax.random.uniform(k[4], (rows,)) > 0.5

    def compiled(fn, *args):
        if "tpu_custom_call" not in jax.jit(fn).lower(*args).as_text():
            raise Mismatch(f"{fn.__name__}: no Mosaic kernel in the lowering")

    def gb(c, v):
        return K.groupby_sum(c, v, 7, cfg)

    def gbw(c, v):
        return K.groupby_sum_words(c, v, 7, cfg)

    def fc(v, m):
        return K.filter_compact(v, m, cfg)

    def zm(v):
        return K.zonemap(v, 4096, cfg)

    compiled(gb, codes, fvals)
    compiled(gbw, codes, ivals)
    compiled(fc, fvals, mask)
    compiled(zm, fvals[:part_rows])

    # f32 sums against exact f64 host sums: XLA's own f32 segment sum (the
    # oracle) drifts on the chip at millions of rows a group
    host_codes = np.asarray(codes)
    exact = np.bincount(host_codes, minlength=7,
                        weights=np.asarray(fvals).astype(np.float64))
    got = np.asarray(gb(codes, fvals))
    oracle = np.asarray(ref.groupby_sum_ref(codes, fvals, 7))
    _close("groupby_sum f32", got, exact)
    log(f"kernels: groupby_sum f32 max relative error vs f64 "
        f"{np.max(np.abs(got / exact - 1))}, ref.py's XLA segment sum "
        f"{np.max(np.abs(oracle / exact - 1))}")
    # int sums past 2^31 (each total is exact in f64, below 2^53)
    want = np.bincount(host_codes, minlength=7,
                       weights=np.asarray(ivals).astype(np.float64))
    _exact("groupby_sum_words int32", K.words_to_int64(*gbw(codes, ivals)),
           want.astype(np.int64))
    _exact("groupby_sum_words ref",
           K.words_to_int64(*ref.groupby_sum_words_ref(codes, ivals, 7)),
           want.astype(np.int64))
    for name, v in (("f32", fvals), ("int32 epoch", epoch)):
        (got, n), (want, wn) = fc(v, mask), ref.filter_compact_ref(v, mask)
        _exact(f"filter_compact {name} count", int(n), int(wn))
        _exact(f"filter_compact {name}", np.asarray(got), np.asarray(want))
        part = v[:part_rows]
        for got, want in zip(zm(part), ref.zonemap_ref(part, 4096)):
            _exact(f"zonemap {name}", np.asarray(got), np.asarray(want))
    jax.block_until_ready(got)
    log(f"kernels: groupby_sum/filter_compact at {rows} rows, zonemap at "
        f"{part_rows} rows: compiled, match ref")

    # compaction of one and of two columns under one mask, warm: the Pallas
    # kernel against the XLA scatter the engine compacts with
    xla = K.KernelConfig(impl="xla")
    for cols in ((fvals,), (fvals, epoch)):
        (got, n), (want, wn) = (K.filter_compact_columns(cols, mask, c)
                                for c in (cfg, xla))
        _exact("filter_compact_columns count", int(n), int(wn))
        for g, w in zip(got, want):
            _exact("filter_compact_columns", np.asarray(g), np.asarray(w))
        pallas_s, xla_s = (_best_seconds(
            lambda c=c: K.filter_compact_columns(cols, mask, c))
            for c in (cfg, xla))
        log(f"kernels: compaction of {len(cols)} column(s) of {rows} rows, "
            f"best of 3 warm: pallas {pallas_s} s, xla {xla_s} s")


def _best_seconds(fn, reps: int = 3) -> float:
    import jax
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t)
    return best


# ---------------------------------------------------------------------------
# comparison helpers


def _exact(what, got, want) -> None:
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or not np.array_equal(
            got.astype(want.dtype) if want.dtype.kind != "O" else got, want):
        raise Mismatch(f"{what}: got {got!r:.300} want {want!r:.300}")


def _close(what, got, want) -> None:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.allclose(got, want, rtol=FLOAT_RTOL,
                                                  atol=0.0):
        raise Mismatch(f"{what}: got {got!r:.300} want {want!r:.300} "
                       f"(rtol {FLOAT_RTOL})")


def _grouped(what, res, key, col, series, exact: bool) -> None:
    got = res.columns
    _exact(f"{what} keys", got[key].astype(np.int64),
           series.index.to_numpy().astype(np.int64))
    (_exact if exact else _close)(what, got[col], series.to_numpy())


def _rows(what, res, frame) -> None:
    got = res.columns
    if list(got) != list(frame.columns):
        raise Mismatch(f"{what}: columns {list(got)} != {list(frame.columns)}")
    for c in frame.columns:
        _exact(f"{what}.{c}", got[c], frame[c].to_numpy())


def _printed_frame(text: str) -> "_HostResult":
    """Columns of a Result as the lazy print sink rendered it."""
    lines = text.splitlines()
    names = [c.strip() for c in lines[1].split("|")]
    rows = [[float(x) for x in ln.split("|")] for ln in lines[2:]]
    return _HostResult({c: np.asarray(v) for c, v in zip(names, zip(*rows))})


def to_host(value):
    """Pull a program's result to host memory (ends the timed window)."""
    if hasattr(value, "columns"):
        return _HostResult({c: np.asarray(v) for c, v in value.columns.items()})
    if isinstance(value, tuple):
        return tuple(to_host(v) for v in value)
    if hasattr(value, "shape"):
        return np.asarray(value).item()
    return value


class _HostResult:
    """A program result pulled to the host, with the ``columns`` view the
    reference compares."""

    def __init__(self, columns):
        self.columns = columns


# ---------------------------------------------------------------------------
# the ten programs in plain pandas


class Reference:
    """Each program of ``benchmarks/programs.py`` in plain pandas over the
    same arrays.  Predicates that the scan layer pushes down are evaluated
    on the host's f64 values, as the engine does; columns the device
    computes are computed in f32, as the device does; aggregates are f64."""

    def __init__(self, arrays):
        import pandas as pd
        self.pd = pd

        def frames(table):
            full = pd.DataFrame(table)
            f32 = pd.DataFrame({c: (a.astype(np.float32) if a.dtype.kind == "f"
                                    else a) for c, a in table.items()})
            return full, f32

        self.taxi, self.taxi32 = frames(arrays["taxi"])
        self.ratings, self.ratings32 = frames(arrays["ratings"])
        self.movies = pd.DataFrame(arrays["movies"])
        self.startups, self.startups32 = frames(arrays["startups"])

    def check(self, name, result, printed) -> None:
        getattr(self, name)(result, printed)

    def taxi_agg(self, res, _):
        t = self.taxi
        m = t.fare_amount > 0
        day = (t.pickup_datetime[m] // 86400 + 3) % 7
        want = t.passenger_count[m].groupby(day.to_numpy()).sum()
        _grouped("taxi_agg", res, "day", "passenger_count", want, exact=True)

    def taxi_feature(self, res, _):
        t = self.taxi32
        total = (t.fare_amount + t.tip) + t.tolls          # f32, device order
        m = total > 20
        want = total[m].astype(np.float64).groupby(
            t.vendor[m].to_numpy()).mean()
        _grouped("taxi_feature", res, "vendor", "total", want, exact=False)

    def taxi_filter(self, res, _):
        t = self.taxi
        m = (t.trip_miles > 10.0) & (t.fare_amount > 30.0)
        _close("taxi_filter", res, self.taxi32.tip[m].astype(np.float64).mean())

    def ratings_join(self, res, _):
        r = self.ratings32.assign(movie_id=self.ratings.movie_id)
        j = r.merge(self.movies, on="movie_id")
        j = j[j.year >= 2000]
        want = j.rating.astype(np.float64).groupby(j.genre.to_numpy()).mean()
        _grouped("ratings_join", res, "genre", "rating", want, exact=False)

    def ratings_top(self, res, _):
        r = self.ratings32
        means = r.rating.astype(np.float64).groupby(
            self.ratings.movie_id.to_numpy()).mean()
        top = means.sort_values(ascending=False, kind="stable").head(10)
        got = res.columns
        # near-equal means may swap ranks between f32 and f64: each returned
        # movie's own mean must match, and so must the ranked values
        _close("ratings_top ranked", got["rating"], top.to_numpy())
        _close("ratings_top per movie", got["rating"],
               means.loc[got["movie_id"].astype(np.int64)].to_numpy())

    def startup_sort(self, res, _):
        s = self.startups32[self.startups.funding > 1e6]
        want = s.sort_values("funding", ascending=False, kind="stable").head(50)
        _rows("startup_sort", res, want)

    def startup_distinct(self, res, _):
        s = self.startups32[self.startups.employees > 100]
        _rows("startup_distinct", res, s.drop_duplicates(subset=["sector"]))

    def multi_print(self, res, printed):
        t, t32 = self.taxi, self.taxi32
        m = t.fare_amount > 0
        per_vendor = t32.trip_miles[m].astype(np.float64).groupby(
            t.vendor[m].to_numpy()).mean()
        avg = t32.fare_amount[m].astype(np.float64).mean()
        lines = [p[0] for p in printed]
        if res is not True or len(lines) != 3 or lines[0] != "rows loaded":
            raise Mismatch(f"multi_print: returned {res!r}, printed {lines!r}")
        _grouped("multi_print per-vendor", _printed_frame(lines[1]), "vendor",
                 "trip_miles", per_vendor, exact=False)
        label, value = lines[2].split(":")
        if label != "avg fare":
            raise Mismatch(f"multi_print line 3: {lines[2]!r}")
        _close("multi_print avg fare", float(value), avg)

    def reuse_stu(self, res, _):
        from benchmarks.programs import _heavy_feature
        t, t32 = self.taxi, self.taxi32
        m = (t.fare_amount > 0).to_numpy()
        total = _heavy_feature((t32.fare_amount + t32.tip).to_numpy()[m])
        total = self.pd.Series(total.astype(np.float64))
        a, b, c = res
        _grouped("reuse_stu a", a, "vendor", "total",
                 total.groupby(t.vendor.to_numpy()[m]).mean(), exact=False)
        _grouped("reuse_stu b", b, "passenger_count", "total",
                 total.groupby(t.passenger_count.to_numpy()[m]).sum(),
                 exact=False)
        _close("reuse_stu c", c, total.mean())

    def wide_projection(self, res, _):
        want = self.taxi32.fare_amount.groupby(
            self.taxi.vendor.to_numpy()).max()
        _grouped("wide_projection", res, "vendor", "fare_amount", want,
                 exact=True)


# ---------------------------------------------------------------------------
# program runs


def run_programs(engine: str, rows: int, names, clock: CompileClock) -> dict:
    """Each program cold (compile seconds) then warm (seconds to the
    result on the host), checked against the pandas reference."""
    import repro.core as core
    from benchmarks.programs import PROGRAMS, build_arrays, build_sources

    t0 = time.perf_counter()
    arrays = build_arrays(rows, SEED)
    sources = build_sources(rows, seed=SEED, arrays=arrays)
    reference = Reference(arrays)
    log(f"data: {rows} taxi rows, seed {SEED}, built with its reference in "
        f"{time.perf_counter() - t0:.3f} s")
    counters: dict[str, int] = {}
    for name in names:
        runs = []
        for phase in ("cold", "warm"):
            printed: list[tuple] = []
            with core.session(engine, name=f"{name}-{phase}") as ctx:
                ctx.print_fn = lambda *a, **_: printed.append(a)
                c0, n0 = clock.mark()
                t = time.perf_counter()
                result = to_host(PROGRAMS[name](sources))
                seconds = time.perf_counter() - t
                c1, n1 = clock.mark()
                for key, v in ctx.metrics.snapshot().items():
                    if key.startswith("fallback.") or \
                            key == "distributed.native_fallbacks":
                        counters[key] = counters.get(key, 0) + v
            runs.append((seconds, c1 - c0, n1 - n0, n0))
        reference.check(name, result, printed)
        (cold_s, cold_c, cold_n, since), (warm_s, _, warm_n, _) = runs
        log(f"program {name}: compile_s={cold_c} ({cold_n} compiles) "
            f"cold_s={cold_s} warm_s={warm_s} (warm compiles {warm_n}) "
            f"match=True; slowest compiles: {clock.slowest(since)}")
    return counters


# ---------------------------------------------------------------------------
# entry


def device_summary(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def one_chip() -> None:
    from repro.kernels import ops as K
    from repro.kernels.platform import interpret_mode

    impl, interpret = K.get_kernel_config().resolved(), interpret_mode()
    log(f"kernels: impl={impl} interpret={interpret}")
    if impl != "pallas" or interpret:
        raise Mismatch(f"kernels would run as impl={impl} "
                       f"interpret={interpret} on this TPU")
    clock = CompileClock()
    check_kernels(ONE_CHIP_ROWS, PARTITION_ROWS)
    from benchmarks.programs import PROGRAMS
    counters = run_programs("eager", ONE_CHIP_ROWS, list(PROGRAMS), clock)
    log(f"counters: {json.dumps(counters, sort_keys=True)}")


def four_chips(rows: int = FOUR_CHIP_ROWS) -> None:
    import jax
    import repro.core.physical as X
    from repro.core.backends.distributed import DistributedBackend

    devices = set(jax.devices())
    placed: list[int] = []
    shard = X.shard_host_table

    def shard_and_check(full, mesh, axis):
        t = shard(full, mesh, axis)
        for c, a in list(t.cols.items()) + [("valid", t.valid)]:
            if set(a.sharding.device_set) != devices:
                raise Mismatch(f"column {c} sharded over "
                               f"{len(a.sharding.device_set)} of "
                               f"{len(devices)} devices")
        placed.append(len(t.cols))
        return t

    eager_nodes: list[str] = []
    fallback = DistributedBackend._fallback_node

    def count_fallback(self, n, vals):
        eager_nodes.append(n.op)
        return fallback(self, n, vals)

    X.shard_host_table = shard_and_check
    DistributedBackend._fallback_node = count_fallback
    try:
        counters = run_programs("distributed", rows, DISTRIBUTED_PROGRAMS,
                                CompileClock())
    finally:
        X.shard_host_table = shard
        DistributedBackend._fallback_node = fallback
    log(f"distributed: {len(placed)} sharded scans, every column on all "
        f"{len(devices)} devices; eager fallback nodes {eager_nodes}")
    log(f"counters: {json.dumps(counters, sort_keys=True)}")
    if eager_nodes or counters.get("distributed.native_fallbacks"):
        raise Mismatch("distributed programs left the native path")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    try:
        sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
        from repro.compile_cache import enable_compile_cache
        import benchmarks.programs  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the repo is not next to this script ({e})",
              file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devices[0].platform}); "
              "this check runs on the chip only", file=sys.stderr)
        return 3
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 3
    log(f"device: {device_summary(devices)} compile cache: {cache}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    peak = max(d.memory_stats().get("peak_bytes_in_use", 0) for d in devices)
    log(f"device peak_bytes_in_use={peak} wall_s={time.perf_counter() - t0}")
    print(json.dumps({"ok": True, "device": device_summary(devices)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
