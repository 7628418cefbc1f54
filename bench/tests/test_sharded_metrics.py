"""The readers of the four-chip cell's metrics on a hand-built traced run:
exchange seconds and idle, the sharded scan's idle, gathered bytes and
stage traces."""
import pytest

from bench import trace as T
from bench.cell import Call, Run

from .test_engine_spans import HOST0, span
from .test_metrics import read

NEW = ("exchange_s_per_program", "idle_in_exchange_pct",
       "stage_traces_per_program")


def sharded_run():
    """Two calls on the distributed engine in a window of [0, 1000) ns: ``a``
    scans and groups, ``b`` runs a scan on the eager engine, then a distinct
    whose exchange pulls and pushes back its shards, then gathers its
    result.  The device idles in [100, 450) and [520, 900)."""
    marks = [("bench:window", 0, 1000), ("bench:a", 0, 480),
             ("bench:a/compute", 0, 470), ("bench:b", 500, 1000),
             ("bench:b/compute", 505, 990)]
    view = T.TraceView(
        devices=[[T.Event("fusion", 0, 100, {}),
                  T.Event("fusion", 450, 520, {}),
                  T.Event("fusion", 900, 1000, {})]],
        annotations=[T.Event(n, lo, hi, {}) for n, lo, hi in marks])
    spans = [span("segment", 20, 450, engine="distributed"),
             span("operator", 100, 300, op="scan"),
             span("io", 100, 150, op="load_partition"),
             span("operator", 200, 300, op="shard_host_table"),
             span("transfer", 220, 300, site="shard", dir="h2d", bytes=4e9),
             span("operator", 300, 440, op="sharded_groupby"),
             span("trace", 310, 310, op="groupby"),
             span("trace", 320, 320, op="filter"),
             span("segment", 515, 590, engine="eager"),
             span("operator", 520, 580, op="scan"),
             span("segment", 595, 980, engine="distributed"),
             span("operator", 600, 900, op="sharded_distinct"),
             span("exchange", 600, 850, op="distinct", shards=4),
             span("transfer", 600, 650, site="exchange", dir="d2h",
                  bytes=1e9),
             span("transfer", 800, 850, site="exchange", dir="h2d",
                  bytes=2e9),
             span("trace", 860, 860, op="assign"),
             span("transfer", 900, 950, site="gather", dir="d2h",
                  bytes=3e9)]
    calls = [Call("a", HOST0, HOST0 + 480e-9, 10),
             Call("b", HOST0 + 500e-9, HOST0 + 1000e-9, 10)]
    return Run("w", process_start=0.0, window_start=HOST0,
               window_end=HOST0 + 1e-6, calls=calls, peak_bytes=[None],
               device_kind="k", clock=None, spans=spans, trace=view,
               programs={}, tables={})


def test_exchange_transfers_are_exchange_idle_not_scan_idle():
    run = sharded_run()
    # the exchange holds [600, 850) of the idle [520, 900), its pull and
    # push included; the idle split gives the push to the scan, beside the
    # load, the scan's own time, the shard put and the eager scan
    assert read("idle_in_exchange_pct", run) == pytest.approx(25.0)
    assert read("idle_in_scan_pct", run) == pytest.approx(
        (50 + 50 + 80 + 60 + 50) / 10)
    # the distributed scan holds [100, 300); the eager one is not it
    assert read("idle_in_sharded_scan_pct", run) == pytest.approx(20.0)


def test_exchange_seconds_per_program():
    assert read("exchange_s_per_program", sharded_run()) == pytest.approx(
        250e-9 / 2)


def test_gather_counts_and_exchange_does_not():
    run = sharded_run()
    assert read("gather_gb_per_program", run) == pytest.approx(3.0 / 2)
    assert read("d2h_gb_per_program", run) == pytest.approx((1 + 3) / 2)


def test_trace_events_per_call():
    run = sharded_run()
    assert read("stage_traces_per_program", run) == pytest.approx(3 / 2)
    run.spans = [s for s in run.spans if s.name != "trace"]
    assert read("stage_traces_per_program", run) == 0


def test_an_engine_without_the_new_spans_reads_nothing_new():
    run = sharded_run()
    run.spans = [s for s in run.spans
                 if s.name not in ("exchange", "trace")
                 and s.attrs.get("op") != "sharded_groupby"]
    for name in NEW:
        assert read(name, run) is None
    assert read("idle_in_sharded_scan_pct", run) == pytest.approx(20.0)
    run.spans = [s for s in run.spans if s.name != "transfer"]
    assert read("gather_gb_per_program", run) is None
    run.trace = None
    assert read("idle_in_sharded_scan_pct", run) is None
