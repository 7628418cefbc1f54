"""The engine's spans in a traced run: transfer and sync readers, the idle
split by engine span on a synthetic trace, and a trace recorded on the CPU
in which the engine's ``repro:`` annotations and its spans placed by the
harness's calls land in the same place."""
import types

import numpy as np
import pytest

from bench import engine_spans as E
from bench import trace as T
from bench.cell import Call, Run

from .test_metrics import make_run, read
from .test_trace import ev, synthetic

HOST0 = 50.0     # the host clock's reading at the trace's 0, in seconds


def span(name, lo, hi, **attrs):
    """An engine span from ``lo`` to ``hi`` ns of the trace's clock."""
    return types.SimpleNamespace(name=name, attrs=attrs,
                                 t0=HOST0 + lo / 1e9, t1=HOST0 + hi / 1e9,
                                 duration=(hi - lo) / 1e9)


def split_run():
    """Two calls: ``a`` scans and aggregates on the device, ``b`` joins on
    the host; the device idles in [100, 450) and [520, 900) of a window of
    [0, 1000) ns."""
    marks = [ev("bench:window", 0, 1000), ev("bench:a", 0, 600),
             ev("bench:a/compute", 0, 500), ev("bench:a/to_host", 500, 100),
             ev("bench:b", 610, 390), ev("bench:b/compute", 615, 385)]
    view = T.TraceView(
        devices=[[T.Event("fusion", 0, 100, {}),
                  T.Event("fusion", 450, 520, {}),
                  T.Event("fusion", 900, 1000, {})]],
        annotations=[T.Event(e.name, e.start_ns, e.start_ns + e.duration_ns,
                             {}) for e in marks])
    spans = [span("execute", 10, 490),
             span("segment", 20, 480, engine="eager"),
             span("io", 100, 200, op="load_partition"),
             span("transfer", 200, 250, site="scan", dir="h2d", bytes=4e9),
             span("operator", 250, 470, op="groupby_agg"),
             span("sync", 300, 350, site="factorize"),
             span("execute", 620, 990),
             span("segment", 630, 980, engine="eager"),
             span("operator", 650, 850, op="join"),
             span("transfer", 700, 750, site="join", dir="d2h", bytes=1e9)]
    calls = [Call("a", HOST0, HOST0 + 600e-9, 10),
             Call("b", HOST0 + 610e-9, HOST0 + 1000e-9, 10)]
    return Run("w", process_start=0.0, window_start=HOST0,
               window_end=HOST0 + 1e-6, calls=calls, peak_bytes=[None],
               device_kind="k", clock=None, spans=spans, trace=view,
               programs={}, tables={})


def test_idle_pieces_go_to_the_innermost_engine_span():
    split = E.split(split_run())
    assert split.layers == pytest.approx(
        {"scan": 150e-9, "engine": 280e-9, "host_ops": 200e-9,
         "untraced": 5e-9, "to_host": 80e-9, "harness": 15e-9}, abs=1e-15)
    assert split.holders == pytest.approx(
        {"a/io:load_partition": 100e-9, "a/transfer:h2d:scan": 50e-9,
         "a/operator:groupby_agg": 150e-9, "a/sync:factorize": 50e-9,
         "a/to_host": 80e-9, "window": 10e-9, "b": 5e-9,
         "b/compute/untraced": 5e-9, "b/execute": 10e-9,
         "b/segment:eager": 70e-9, "b/operator:join": 150e-9,
         "b/transfer:d2h:join": 50e-9}, abs=1e-15)


def test_the_scan_operators_own_time_is_the_scan():
    run = split_run()
    run.spans[2] = span("io", 100, 150, op="load_partition")
    run.spans.append(span("operator", 100, 250, op="scan"))
    split = E.split(run)
    assert split.layers["scan"] == pytest.approx(150e-9, abs=1e-15)
    assert split.holders["a/operator:scan"] == pytest.approx(50e-9,
                                                              abs=1e-15)
    assert split.holders["a/io:load_partition"] == pytest.approx(50e-9,
                                                                 abs=1e-15)


def test_idle_layers_add_up_to_the_idle_share():
    run = split_run()
    assert read("idle_in_scan_pct", run) == pytest.approx(15.0)
    assert read("idle_in_host_ops_pct", run) == pytest.approx(20.0)
    assert read("idle_untraced_pct", run) == pytest.approx(0.5)
    split = E.split(run)
    parts = (read("idle_in_scan_pct", run) + read("idle_in_host_ops_pct", run)
             + read("idle_untraced_pct", run) + split.pct("engine")
             + split.pct("to_host") + split.pct("harness"))
    assert parts == pytest.approx(read("device_idle_share", run))
    assert split.pct("harness") == pytest.approx(1.5)


def test_transfer_and_sync_readers_per_program():
    run = split_run()
    assert read("h2d_gb_per_program", run) == pytest.approx(4.0 / 2)
    assert read("d2h_gb_per_program", run) == pytest.approx(1.0 / 2)
    assert read("host_syncs_per_program", run) == 0.5


def test_an_engine_without_transfer_spans_reads_nothing():
    run = split_run()
    run.spans = [s for s in run.spans if s.name not in ("transfer", "sync")]
    for name in ("h2d_gb_per_program", "d2h_gb_per_program",
                 "host_syncs_per_program"):
        assert read(name, run) is None
    assert read("idle_in_scan_pct", run) == pytest.approx(10.0)
    untraced = make_run([1.0])
    for name in ("h2d_gb_per_program", "d2h_gb_per_program",
                 "host_syncs_per_program", "idle_in_scan_pct",
                 "idle_in_host_ops_pct", "idle_untraced_pct"):
        assert read(name, untraced) is None


def test_calls_the_trace_does_not_hold_place_nothing():
    run = split_run()
    run.calls = run.calls[:1]
    assert E.split(run) is None
    run = split_run()
    run.calls[1].program = "c"
    assert E.split(run) is None


def test_engine_annotations_leave_the_harness_readings_alone(monkeypatch):
    seen = []
    real = T.from_planes
    monkeypatch.setattr(T, "from_planes",
                        lambda planes: seen.append(planes) or real(planes))
    plain = synthetic()
    planes = seen[0]
    host = planes[0].lines[0]
    host.events = host.events + [
        ev("repro:execute", 110, 280), ev("repro:segment:eager", 120, 260),
        ev("repro:io:load_partition", 130, 40),
        ev("repro:transfer:h2d", 200, 50), ev("repro:op:join", 620, 300),
        ev("repro:sync:factorize", 700, 90)]
    traced = real(planes)
    assert traced.annotations == plain.annotations
    assert traced.idle_gaps() == plain.idle_gaps()
    assert traced.program_calls() == plain.program_calls()
    assert traced.busy_s() == plain.busy_s()
    assert traced.op_seconds() == plain.op_seconds()
    programs = {"a": types.SimpleNamespace(
        groupby_sums=lambda t: [(10**5, 1, 7, 2)]),
        "b": types.SimpleNamespace()}
    readings = []
    for view in (plain, traced):
        run = make_run([1.0])
        run.trace, run.programs, run.tables = view, programs, {}
        run.device_kind = "TPU v5 lite"
        readings.append([read(m, run) for m in ("device_idle_share",
                                                "groupby_sum_roofline")])
    assert readings[0] == readings[1]


def test_recorded_trace_holds_engine_spans_inside_their_call(tmp_path):
    import glob
    import os

    import jax
    import repro.core as core
    from jax.profiler import ProfileData

    from bench import cell
    from repro.obs.spans import ANNOTATION_PREFIX, display_name

    def run_program(S):
        df = core.read_source(S["t"])
        df = df[df["x"] > 3.0]
        return df.groupby(["k"])["x"].sum().compute()

    prog = types.SimpleNamespace(run=run_program)
    sources = {"t": core.InMemorySource(
        {"k": np.arange(4000) % 7, "x": np.arange(4000.0)}, 1000, name="t")}
    assert cell.call(prog, "p", sources, "eager", 4000, None).error is None
    spans: list = []
    jax.profiler.start_trace(str(tmp_path))
    calls = cell.window({"p": prog}, ["p"], sources, "eager", {"p": 4000},
                        0.0, spans)
    jax.profiler.stop_trace()
    assert [c.error for c in calls] == [None]
    path = sorted(glob.glob(os.path.join(
        tmp_path, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    data = ProfileData.from_file(path)
    view = T.from_planes(data.planes)
    repro = sorted((e for plane in data.planes
                    if plane.name.startswith("/host:")
                    for line in plane.lines for e in line.events
                    if e.name.startswith(ANNOTATION_PREFIX)),
                   key=lambda e: e.start_ns)
    assert {"repro:execute", "repro:io:load_partition",
            "repro:transfer:h2d", "repro:sync:factorize"} <= {
        e.name for e in repro}
    (compute,) = [a for a in view.annotations if a.name == "bench:p/compute"]
    for e in repro:
        assert compute.start_ns <= e.start_ns
        assert e.start_ns + e.duration_ns <= compute.end_ns
    # the spans placed by the harness's calls land on their annotations
    run = Run("w", 0.0, calls[0].start, calls[-1].end, calls, [None], "cpu",
              None, spans, view, {}, {})
    placed = E.engine_intervals(run)
    assert len(placed) == len(repro) == len(spans)
    errors = []
    for name in {e.name for e in repro}:
        got = sorted(iv.lo for iv in placed
                     if ANNOTATION_PREFIX + display_name(iv.span) == name)
        want = sorted(e.start_ns for e in repro if e.name == name)
        assert len(got) == len(want)
        errors += [abs(g - w) for g, w in zip(got, want)]
    assert max(errors) < 5e6 and float(np.median(errors)) < 2e5
