"""The readers' arithmetic on fixed timings, spans and compile events."""
import types

import pytest

from bench.cell import Call, Run
from bench.registry import Registry

from .tiny import REPO


def read(name, run):
    return Registry(REPO).metric(name).read(run)


class Clock:
    def __init__(self, events):
        self.events = events

    from bench.compile_clock import CompileClock
    seconds = CompileClock.seconds
    backend_compiles = CompileClock.backend_compiles


def make_run(seconds, rows=100, spans=(), events=(), peaks=(None,)):
    calls, t = [], 10.0
    for s in seconds:
        calls.append(Call("p", t, t + s, rows))
        t += s
    return Run("w", process_start=1.0, window_start=10.0, window_end=t,
               calls=calls, peak_bytes=list(peaks), device_kind="k",
               clock=Clock(list(events)), spans=list(spans), trace=None,
               programs={}, tables={})


def test_rows_per_s_is_all_rows_over_all_window_time():
    run = make_run([1.0, 2.0, 1.0], rows=1000)
    assert read("rows_per_s", run) == pytest.approx(3000 / 4.0)


@pytest.mark.parametrize("n, rank", [(1, 1), (19, 19), (20, 19), (21, 20),
                                     (22, 21), (40, 38), (100, 95)])
def test_program_s_p95_is_the_nearest_rank(n, rank):
    seconds = [float(i) for i in range(n, 0, -1)]      # 1..n, unsorted
    assert read("program_s_p95", make_run(seconds)) == float(rank)


def test_setup_and_peak():
    run = make_run([1.0], peaks=(3_000_000_000, None, 5_500_000_000))
    assert read("setup_s", run) == 9.0
    assert read("device_peak_gb", run) == 5.5
    assert read("device_peak_gb", make_run([1.0])) is None


def span(name, sid, parent, duration, **attrs):
    return types.SimpleNamespace(name=name, id=sid, parent_id=parent,
                                 duration=duration, attrs=attrs)


def test_span_readers_per_program():
    spans = [span("execute", 1, None, 1.0),
             span("segment", 2, 1, 0.6), span("segment", 3, 1, 0.3),
             span("operator", 4, 2, 0.2, op="join"),
             span("operator", 5, 2, 0.1, op="top_k"),
             span("operator", 6, 2, 0.25, op="groupby_agg"),
             span("io", 7, 2, 0.05, op="load_partition"),
             span("io", 8, 2, 0.15, op="load_partition"),
             span("execute", 9, None, 0.5)]
    run = make_run([1.0, 1.0], spans=spans)
    assert read("plan_s_per_program", run) == pytest.approx((0.1 + 0.5) / 2)
    assert read("host_op_s_per_program", run) == pytest.approx(0.3 / 2)
    assert read("scan_s_per_program", run) == pytest.approx(0.2 / 2)
    empty = make_run([1.0])
    for name in ("plan_s_per_program", "host_op_s_per_program",
                 "scan_s_per_program", "device_idle_share",
                 "groupby_sum_roofline"):
        assert read(name, empty) is None


def test_compile_readers_split_set_up_from_window():
    from bench.compile_clock import BACKEND, LOWER, TRACE
    events = [(2.0, TRACE, 0.5, "f"), (3.0, BACKEND, 4.0, "f"),
              (9.5, LOWER, 0.25, "g"), (10.5, TRACE, 0.1, "h"),
              (11.0, BACKEND, 1.0, "h"), (50.0, BACKEND, 1.0, "late")]
    run = make_run([2.0], events=events)
    assert read("setup_compile_s", run) == pytest.approx(4.75)
    assert read("window_compiles", run) == 1


def test_groupby_sum_roofline_from_the_trace():
    from bench import trace as T
    from bench.peaks import groupby_sum_bytes

    def ev(name, start, end, **stats):
        return T.Event(name, start, end, stats)

    custom = 'custom-call(...), custom_call_target="tpu_custom_call"'
    view = T.TraceView(
        devices=[[ev("_groupby_sum.1", 110, 1_000_110,   # in a's call
                     long_name=custom),
                  ev("fusion.3", 1_000_200, 1_500_000, long_name="fusion"),
                  ev("_groupby_sum", 3_000_000, 5_000_000,  # in b's call
                     long_name=custom),
                  ev("_groupby_sum.2", 9_000_000, 9_500_000,  # c: no work
                     long_name=custom)]],
        annotations=[ev("bench:window", 0, 10_000_000),
                     ev("bench:a", 100, 2_000_000),
                     ev("bench:a/compute", 100, 1_000_000),
                     ev("bench:b", 2_000_000, 6_000_000),
                     ev("bench:c", 8_000_000, 10_000_000)])
    programs = {"a": types.SimpleNamespace(
                    groupby_sums=lambda t: [(t["rows"], 1, 7, 2)]),
                "b": types.SimpleNamespace(
                    groupby_sums=lambda t: [(t["rows"], 2, 4, 1)]),
                "c": types.SimpleNamespace()}
    run = make_run([1.0])
    run.trace, run.programs, run.tables = view, programs, {"rows": 10**5}
    run.device_kind = "TPU v5 lite"
    least = (groupby_sum_bytes(10**5, 1, 7, 2)
             + groupby_sum_bytes(10**5, 2, 4, 1)) / 819e9
    spent = (1_000_000 + 2_000_000 + 500_000) / 1e9
    assert read("groupby_sum_roofline", run) == pytest.approx(
        100 * least / spent)
    run.device_kind = "an unknown chip"
    with pytest.raises(KeyError):
        read("groupby_sum_roofline", run)
