"""One run of one cell: build the tables from the seed, warm up every
program of the mix, run the mix in a closed loop for the window, then check
every result the window produced against the plain reference.

A run's record (:class:`Run`) is what the metric readers under
``bench/metrics`` read.
"""
from __future__ import annotations

import contextlib
import dataclasses
import shutil
import sys
import tempfile
import time
import traceback

import numpy as np

from .check import REFERENCE, Tally, to_host
from .compile_clock import CompileClock
from .registry import Registry
from . import trace as T

BREAKDOWN_ENTRIES = 10


class NoAccelerator(RuntimeError):
    pass


@dataclasses.dataclass
class Call:
    program: str
    start: float
    end: float
    rows: int
    result: object = None
    error: str | None = None

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Run:
    """What one run measured: host-clock times are ``time.perf_counter``."""

    workload: str
    process_start: float
    window_start: float
    window_end: float             # the last completion in the window
    calls: list[Call]
    peak_bytes: list[int | None]  # each device's peak after the window
    device_kind: str
    clock: CompileClock
    spans: list                   # repro.obs spans of the window's calls
    trace: T.TraceView | None     # the window's profiler trace
    programs: dict                # the mix's program modules, by name
    tables: dict                  # the host tables the programs read

    @property
    def setup_s(self) -> float:
        return self.window_start - self.process_start


def accelerators(chips: int) -> list:
    """The first ``chips`` accelerator devices JAX finds; no CPU fallback."""
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoAccelerator("JAX finds no accelerator, only the CPU")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX finds "
                            f"{len(devices)}")
    return devices[:chips]


VALUE_DRAW = 0     # the one draw of every table's values, whatever the seed


def build_tables(reg: Registry, cfg: dict, seed: int) -> dict[str, dict]:
    """The configuration's tables on the host, as its datasets define them.
    The values are one fixed draw; ``seed`` draws the order of each table's
    rows.  So every seed gives the same sizes, filtered counts and groups
    (the same compiled programs) in another order."""
    tables: dict[str, dict] = {}
    for name, rows in cfg["datasets"].items():
        tables.update(reg.dataset(name).build(
            rows, np.random.default_rng(VALUE_DRAW)))
    rng = np.random.default_rng(seed)
    for name in sorted(tables):
        cols = tables[name]
        order = rng.permutation(len(next(iter(cols.values()))))
        tables[name] = {c: v[order] for c, v in cols.items()}
    return tables


def make_sources(tables: dict, cfg: dict) -> dict:
    """The engine's in-memory source of each table: categoricals become
    int32 codes with their vocabulary, ``datetime64[s]`` columns epoch
    seconds marked as datetimes."""
    import pandas as pd
    import repro.core as core
    sources = {}
    for name, cols in tables.items():
        arrays, dicts, datetimes = {}, {}, []
        for c, v in cols.items():
            if isinstance(v, pd.Categorical):
                arrays[c] = np.asarray(v.codes, np.int32)
                dicts[c] = [str(x) for x in v.categories]
            elif v.dtype.kind == "M":
                arrays[c] = v.astype("datetime64[s]").astype(np.int64)
                datetimes.append(c)
            else:
                arrays[c] = v
        sources[name] = core.InMemorySource(
            arrays, cfg["partition_rows"][name], dicts, datetimes, name=name)
    return sources


def call(program, name: str, sources: dict, engine: str, rows: int,
         spans: list | None) -> Call:
    """One program in a fresh session, timed from the call to its result in
    host memory; with ``spans`` given, the session's spans are added to it."""
    import jax
    import repro.core as core
    from repro.obs import profile

    start = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(T.PREFIX + name), \
                core.session(engine, name=name) as ctx, \
                (profile(ctx) if spans is not None
                 else contextlib.nullcontext()) as prof:
            with jax.profiler.TraceAnnotation(f"{T.PREFIX}{name}/compute"):
                value = program.run(sources)
            with jax.profiler.TraceAnnotation(f"{T.PREFIX}{name}/to_host"):
                result = to_host(value)
    except Exception:  # noqa: BLE001 — a failed call is counted, not fatal
        return Call(name, start, time.perf_counter(), rows,
                    error=traceback.format_exc())
    end = time.perf_counter()
    if prof is not None:
        spans.extend(prof.spans)
    return Call(name, start, end, rows, result)


def window(programs: dict, order: list[str], sources: dict, engine: str,
           rows: dict, seconds: float, spans: list | None) -> list[Call]:
    """The closed loop: one client sends the mix's programs in order, each
    after the last completes, until ``seconds`` have passed; the window
    then ends with the pass of the mix it is in, so that it holds whole
    passes only."""
    import jax
    calls: list[Call] = []
    deadline = time.perf_counter() + seconds
    with jax.profiler.TraceAnnotation(T.WINDOW):
        while len(calls) % len(order) or not calls or \
                time.perf_counter() < deadline:
            name = order[len(calls) % len(order)]
            calls.append(call(programs[name], name, sources, engine,
                              rows[name], spans))
    return calls


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def run(reg: Registry, workload: str, seed: int, seconds: float,
        traced: bool, devices: list, process_start: float) -> dict:
    """One run of ``workload``; returns the result line's object."""
    import jax

    clock = CompileClock()
    cell = reg.workload(workload)
    cfg = reg.config(cell["config"])
    order = reg.mix(cell["traffic"])["programs"]
    limits = reg.limits(workload)
    programs = {name: reg.program(name) for name in order}

    tables = build_tables(reg, cfg, seed)
    sources = make_sources(tables, cfg)
    table_rows = {t: len(next(iter(c.values()))) for t, c in tables.items()}
    rows = {name: sum(table_rows[t] for t in p.TABLES)
            for name, p in programs.items()}
    log(f"cell {workload}: seed {seed}, engine {cfg['engine']}, tables "
        f"{table_rows}, built in {time.perf_counter() - process_start:.3f} s "
        f"from process start")

    for name in order:                                  # warm-up pass
        c = call(programs[name], name, sources, cfg["engine"], rows[name],
                 None)
        log(f"warm-up {name}: {c.seconds:.6f} s"
            + (f" FAILED\n{c.error}" if c.error else ""))

    spans: list | None = [] if traced else None
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    try:
        if traced:
            jax.profiler.start_trace(log_dir)
        window_start = time.perf_counter()
        calls = window(programs, order, sources, cfg["engine"], rows, seconds,
                       spans)
        if traced:
            jax.profiler.stop_trace()
        peak = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in devices]
        view = T.load(log_dir) if traced else None
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)

    record = Run(workload, process_start, window_start,
                 max(c.end for c in calls), calls, peak,
                 devices[0].device_kind, clock, spans or [], view, programs,
                 tables)
    tally = check(programs, order, tables, calls)
    return result_line(reg, record, tally, limits, devices, traced)


def check(programs: dict, order: list[str], tables: dict,
          calls: list[Call]) -> Tally:
    """Every result of the window against the plain reference."""
    tally = Tally()
    for name in order:
        want = programs[name].reference(tables, REFERENCE)
        for c in calls:
            if c.program != name or c.error is not None:
                continue
            try:
                programs[name].check(c.result, want, tally)
            except Exception:  # noqa: BLE001 — a malformed answer is wrong
                tally.mismatches += 1
                tally.fault(name, c.result, traceback.format_exc())
    return tally


def result_line(reg: Registry, run: Run, tally: Tally, limits: dict,
                devices: list, traced: bool) -> dict:
    failed = [c for c in run.calls if c.error is not None]
    for c in failed[:3]:
        log(f"{c.program} failed in the window:\n{c.error}")
    for name in dict.fromkeys(c.program for c in run.calls):
        secs = sorted(c.seconds for c in run.calls if c.program == name)
        log(f"program {name}: {len(secs)} calls, median "
            f"{secs[len(secs) // 2]:.6f} s, max {secs[-1]:.6f} s")
    log(f"window: {len(run.calls)} calls in "
        f"{run.window_end - run.window_start:.6f} s; backend compiles in it: "
        f"{run.clock.backend_compiles(run.window_start, run.window_end)}")

    metrics = {}
    for m in reg.metrics_for(run.workload, traced):
        value = reg.metric(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": run.device_kind,
              "count": len(devices),
              "memory_peak_bytes": max((p or 0) for p in run.peak_bytes)}
    line = {"correct": False, "attempted": len(run.calls),
            "failed": len(failed), "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s()
        ops = sorted(run.trace.op_seconds().items(), key=lambda kv: -kv[1])
        line["breakdown"] = {
            "device_ops": [list(kv) for kv in ops[:BREAKDOWN_ENTRIES]],
            "idle_gaps": [list(g) for g in
                          run.trace.idle_gaps()[:BREAKDOWN_ENTRIES]]}

    numbers = dict(tally.numbers(), failed=len(failed))
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    line["correct"] = all(c["value"] <= c["limit"] for c in checks.values())
    line["checks"] = checks
    if tally.first_fault:
        log(f"first fault: {tally.first_fault}")
    log(f"compared {tally.compared} answers of {len(run.calls) - len(failed)}"
        " calls with the plain reference")
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} limit {c['limit']!r}")
    return line
