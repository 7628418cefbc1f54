"""Telemetry subsystem (repro.obs): span trees from pd.profile(), the
no-op fast path, counters, bounded trace logs, structured planner events,
Chrome-trace/JSONL export, and the explain() span linkage."""
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import repro.pandas as pd
from repro.core import get_context
from repro.obs import (NOOP_SPAN, PlannerEvent, Profile, TraceLog, Tracer,
                       profile, tracing_active, validate_chrome_trace)


def _corpus_program():
    """api_corpus-style plain-pandas program: filter → assign → groupby,
    a join, and a fallback op."""
    df = pd.from_arrays({"fare": np.arange(200.0),
                         "vendor": np.arange(200) % 5})
    df = df[df["fare"] > 10.0]
    df["tip"] = df["fare"] * 0.2
    by_vendor = df.groupby("vendor")["tip"].sum().compute()
    std = df["fare"].std()                          # measured fallback
    return by_vendor, std


# ---------------------------------------------------------------------------
# The acceptance scenario: profile a program, get the full span tree.


def test_profile_span_tree_covers_plan_segments_operators():
    with pd.session(engine="auto", name="tree"):
        with profile() as prof:
            _corpus_program()
    names = prof.span_names()
    assert {"execute", "plan", "segment", "operator"} <= names
    # every executed segment span has a nonzero duration and an engine attr
    segs = prof.find("segment")
    assert segs
    for s in segs:
        assert s.duration > 0
        assert s.attrs.get("engine")
    # the leading filter is pushed into the scan (scan_pushdown), so the
    # rowwise chain reduces to the single assign; the pushdown row
    # accounting replaces the old fused-operator row attrs
    ops = {s.attrs.get("op") for s in prof.find("operator")}
    assert "assign" in ops and "groupby_agg" in ops
    assert prof.counters.get("io.pushdown_rows_in", 0) >= 200
    assert prof.counters.get("io.pushdown_rows_out", 0) >= 189
    assert prof.counters.get("io.pushdown_rows_out", 0) < \
        prof.counters.get("io.pushdown_rows_in", 0)
    # spans nest: plan and segment are children of an execute span
    exec_ids = {s.id for s in prof.find("execute")}
    assert all(s.parent_id in exec_ids for s in prof.find("plan"))
    assert all(s.parent_id in exec_ids for s in segs)
    # the fallback op surfaced as both an event span and a counter
    assert prof.find("fallback")
    assert prof.counters.get("fallback.served", 0) >= 1
    assert prof.counters.get("calibration.runtime_samples", 0) >= 1


def test_profile_render_is_indented_tree_with_counters():
    with pd.session(engine="auto", name="rendered"):
        with profile() as prof:
            _corpus_program()
    text = prof.render()
    assert text.splitlines()[0].startswith("profile session=rendered")
    assert "  execute " in text
    assert "    segment " in text            # child of execute: deeper indent
    assert "op=assign" in text               # the filter was pushed into the scan
    assert "counters:" in text


def test_explain_segments_link_to_measured_spans():
    with pd.session(engine="auto", name="linked"):
        with profile() as prof:
            _corpus_program()
        report = pd.explain()
    span_ids = {s.id for s in prof.find("segment")}
    executed = [seg for run in report.runs for seg in run.segments]
    assert executed
    assert all(seg.span_id in span_ids for seg in executed)
    assert any(f"span=#{seg.span_id}" in report.render() for seg in executed)
    # plan-only explain has no measured spans to link
    df = pd.from_arrays({"x": np.arange(8.0)})
    plan_only = pd.explain(df[df["x"] > 3])
    assert all(seg.span_id is None
               for run in plan_only.runs for seg in run.segments)


# ---------------------------------------------------------------------------
# No-op fast path.


def test_tracing_disabled_by_default_and_spans_are_noop():
    ctx = get_context()
    assert not tracing_active()
    assert ctx.tracer.span("anything") is NOOP_SPAN
    assert not NOOP_SPAN                    # falsy: cheap "if sp:" guards
    with profile():
        assert tracing_active()
        assert ctx.tracer.span("real") is not NOOP_SPAN
        ctx.tracer.span("real").finish()
    assert not tracing_active()
    assert ctx.tracer.span("after") is NOOP_SPAN


def test_traced_op_passes_through_untouched_when_disabled():
    from repro.core import physical as X
    assert not tracing_active()
    table = {"v": np.arange(10.0)}
    out = X.apply_head(table, 3)
    assert len(out["v"]) == 3
    # the original is preserved for the uninstrumented benchmark baseline
    assert X.apply_head.__wrapped__ is not X.apply_head
    np.testing.assert_array_equal(
        X.apply_head.__wrapped__(table, 3)["v"], out["v"])


def test_timed_span_is_real_without_profile_and_feeds_calibration():
    """Spans are the single timing source: calibration samples land in the
    stats store with no profile attached."""
    with pd.session(engine="eager", name="cal") as ctx:
        sp = ctx.tracer.timed_span("segment", engine="eager")
        assert sp is not NOOP_SPAN
        sp.finish()
        assert sp.duration > 0
        df = pd.from_arrays({"x": np.arange(32.0)})
        df[df["x"] > 1].compute()
        assert len(ctx.stats_store.runtime_samples.get("eager", ())) >= 1
        assert ctx.metrics.snapshot().get("calibration.runtime_samples",
                                          0) >= 1


def test_profiles_nest_and_detach_cleanly():
    ctx = get_context()
    with profile() as outer:
        ctx.tracer.span("a").finish()
        with profile() as inner:
            ctx.tracer.span("b").finish()
        ctx.tracer.span("c").finish()
    assert {s.name for s in outer.spans} == {"a", "b", "c"}
    assert {s.name for s in inner.spans} == {"b"}


# ---------------------------------------------------------------------------
# Bounded trace logs + structured events.


def test_trace_log_ring_buffer_bounds_and_counts_drops():
    log = TraceLog(limit=3)
    for i in range(10):
        log.append(i)
    assert list(log) == [7, 8, 9]
    assert log.dropped == 7
    unbounded = TraceLog(limit=None)
    unbounded.extend(range(100))
    assert len(unbounded) == 100 and unbounded.dropped == 0


def test_session_trace_limit_bounds_planner_trace():
    with pd.session(engine="auto", trace_limit=5) as ctx:
        df = pd.from_arrays({"x": np.arange(16.0)})
        for _ in range(8):
            df[df["x"] > 1].compute()
        assert len(ctx.planner_trace) <= 5
        assert ctx.planner_trace.dropped > 0
        assert len(ctx.force_log) <= 5


def test_planner_events_are_strings_with_structure():
    with pd.session(engine="auto", name="ev") as ctx:
        df = pd.from_arrays({"x": np.arange(64.0)})
        df[df["x"] > 1].compute()
        seg_lines = [e for e in ctx.planner_trace
                     if getattr(e, "kind", None) == "segment"]
        assert seg_lines
        ev = seg_lines[0]
        assert isinstance(ev, str)              # legacy consumers unbroken
        assert ev.startswith("auto: seg0")
        assert ev.fields["engine"] in ("eager", "streaming", "distributed")
        assert ev.to_dict()["kind"] == "segment"
    ev2 = PlannerEvent("hello", kind="note", n=1)
    assert ev2 == "hello" and ev2.fields == {"n": 1}


def test_fallback_events_counted_per_status():
    from repro.pandas.fallback import record_fallback
    with pd.session(name="fb") as ctx:
        record_fallback("DataFrame.x", (3, 2), "materialize-input")
        record_fallback("DataFrame.y", None, "no-registered-kernel",
                        status="failed")
        snap = ctx.metrics.snapshot()
        assert snap["fallback.served"] == 1
        assert snap["fallback.failed"] == 1
        assert len(ctx.fallback_trace) == 2


# ---------------------------------------------------------------------------
# Exporters.


def test_chrome_trace_export_validates_and_has_complete_events(tmp_path):
    with pd.session(engine="auto", name="chrome"):
        with profile() as prof:
            _corpus_program()
    trace = prof.to_chrome_trace()
    validate_chrome_trace(trace)
    events = trace["traceEvents"]
    x_events = [e for e in events if e["ph"] == "X"]
    assert x_events
    for e in x_events:
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert "span_id" in e["args"]
    assert any(e["ph"] == "M" for e in events)       # process metadata
    assert any(e["ph"] == "C" for e in events)       # counter samples
    path = prof.save_chrome_trace(str(tmp_path / "trace.json"))
    reloaded = json.load(open(path))
    validate_chrome_trace(reloaded)


def test_chrome_trace_validation_rejects_malformed():
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": []})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"name": "x", "ph": "X",
                                               "pid": 1}]})  # no ts/dur
    with pytest.raises(ValueError):
        validate_chrome_trace({})


def test_jsonl_export_round_trips_span_fields(tmp_path):
    with pd.session(engine="auto", name="jsonl"):
        with profile() as prof:
            _corpus_program()
    path = tmp_path / "spans.jsonl"
    n = prof.to_jsonl(str(path))
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    assert n == len(lines) == len(prof.spans)
    by_id = {s.id: s for s in prof.spans}
    for rec in lines:
        assert rec["name"] == by_id[rec["id"]].name
        assert rec["duration"] >= 0


def test_profile_ring_bounds_span_count():
    ctx = get_context()
    with profile(max_spans=4) as prof:
        for i in range(10):
            ctx.tracer.span(f"s{i}").finish()
    assert len(prof.spans) == 4
    assert prof.dropped == 6
    assert prof.counters.get("spans.dropped") == 6
    assert [s.name for s in prof.spans] == ["s6", "s7", "s8", "s9"]


def test_profile_counts_persist_cache_hits():
    from repro.core import from_arrays
    with pd.session(engine="streaming", name="persist"):
        with profile() as prof:
            df = from_arrays({"x": np.arange(2048.0)}, partition_rows=256)
            df = df[df["x"] > 1]
            df["x"].sum().compute(live_df=[df])    # df live → persisted
            df["x"].mean().compute(live_df=[])     # reuses the cache
    assert prof.counters.get("persist.misses", 0) >= 1
    assert prof.counters.get("persist.hits", 0) >= 1


# ---------------------------------------------------------------------------
# The jit_analyze rename.


def test_core_tracer_shim_warns_and_reexports():
    import importlib
    import sys
    sys.modules.pop("repro.core.tracer", None)
    with pytest.warns(DeprecationWarning, match="repro.core.tracer"):
        mod = importlib.import_module("repro.core.tracer")
    from repro.core import jit_analyze
    assert mod.analyze is jit_analyze.analyze
    assert mod.usecols_hint is jit_analyze.usecols_hint


@pd.analyze
def _analyzed_prog():
    return 1


def test_analyze_emits_span_when_profiled():
    with pd.session(name="an") as ctx:
        with profile() as prof:
            _analyzed_prog()
        spans = prof.find("analyze", mode="function")
        assert spans and "jit_seconds" in spans[0].attrs
        assert ctx.analysis.get("jit_seconds") is not None


# ---------------------------------------------------------------------------
# Spans on the profiler's clock, transfers and syncs.


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each
    annotation built, and the thread it opened and closed on."""

    def __init__(self):
        self.built, self.open = [], set()
        rec = self

        class Annotation:
            def __init__(self, name):
                self.name = name
                rec.built.append(self)

            def __enter__(self):
                self.entered = threading.get_ident()
                rec.open.add(self)
                return self

            def __exit__(self, *exc):
                self.exited = threading.get_ident()
                rec.open.remove(self)

        self.cls = Annotation

    def names(self) -> set[str]:
        return {a.name for a in self.built}


@pytest.fixture
def annotations(monkeypatch):
    import jax.profiler
    rec = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec.cls)
    return rec


def test_spans_annotate_the_profiler_only_while_profiled(annotations):
    ctx = get_context()
    ctx.tracer.span("a").finish()
    ctx.tracer.timed_span("segment", engine="eager").finish()
    assert annotations.built == []
    with profile():
        with ctx.tracer.span("execute"):
            assert annotations.open
        ctx.tracer.timed_span("segment", engine="eager").finish()
    assert annotations.names() == {"repro:execute", "repro:segment:eager"}
    assert not annotations.open
    ctx.tracer.timed_span("segment", engine="eager").finish()
    assert len(annotations.built) == 2


def test_out_of_order_finish_closes_every_annotation(annotations):
    ctx = get_context()
    with profile():
        outer = ctx.tracer.span("outer")
        inner = ctx.tracer.span("inner")
        outer.finish()
        assert len(annotations.open) == 1
        inner.finish()
    assert not annotations.open
    assert ctx.tracer.current_span() is None


def test_worker_thread_spans_annotate_on_their_own_thread(annotations):
    from repro.obs import engine_span
    ctx = get_context()
    with profile():
        worker = threading.Thread(target=lambda: engine_span(
            "io", "load_partition", tracer=ctx.tracer).finish())
        worker.start()
        worker.join()
    (a,) = annotations.built
    assert a.name == "repro:io:load_partition"
    assert a.entered == a.exited == worker.ident


def _device_program():
    """Eager, device-resident: a filter compacted on the device, an int
    group-by sum, a join (the device probe) and a TopK."""
    df = pd.from_arrays({"k": np.arange(400) % 7, "v": np.arange(400),
                         "x": np.arange(400.0)})
    df = df[df["x"] > 10.0]
    df["y"] = df["x"] * 2.0
    df = df[df["y"] < 700.0]
    df.groupby("k")["v"].sum().compute()
    right = pd.from_arrays({"k": np.arange(7), "w": np.arange(7.0)})
    df.merge(right, on="k").compute()
    df.nlargest(3, "x").compute()


def test_transfer_and_sync_spans_match_their_counters(annotations):
    with pd.session(engine="eager", name="moves"):
        with profile() as prof:
            _device_program()
    transfers = prof.find("transfer")
    for direction in ("h2d", "d2h"):
        moved = [s.attrs["bytes"] for s in transfers
                 if s.attrs["dir"] == direction]
        assert moved and sum(moved) > 0
        assert sum(moved) == prof.counters[f"transfer.{direction}_bytes"]
    syncs = prof.find("sync")
    assert syncs and len(syncs) == prof.counters["device.syncs"]
    sites = {s.attrs["site"] for s in transfers + syncs}
    assert {"scan", "join", "top_k", "factorize"} <= sites
    assert {"repro:execute", "repro:segment:eager", "repro:op:join",
            "repro:io:load_partition", "repro:op:scan",
            "repro:transfer:h2d", "repro:transfer:d2h",
            "repro:sync:factorize"} <= annotations.names()
    assert len(annotations.built) == len(prof.spans)


def test_transfers_and_syncs_count_without_a_profile(annotations):
    with pd.session(engine="eager", name="counted") as ctx:
        before = ctx.metrics.snapshot()
        _device_program()
        counted = ctx.metrics.delta(before, ctx.metrics.snapshot())
    assert counted["transfer.h2d_bytes"] > 0
    assert counted["transfer.d2h_bytes"] > 0
    assert counted["device.syncs"] > 0
    assert annotations.built == []


_SHARDED_SYNCS = """
import sys
import numpy as np
sys.path.insert(0, "src")
import jax
import repro.pandas as pd
from repro.obs import profile

def program():
    df = pd.from_arrays({"k": np.arange(4000) % 7, "v": np.arange(4000),
                         "x": np.arange(4000.0)})
    df = df[df["x"] > 10.0]
    right = pd.from_arrays({"k": np.arange(7), "w": np.arange(7.0)})
    df.merge(right, on="k").compute()
    df.drop_duplicates(subset=("k",)).compute()
    df.groupby("k")["v"].sum().compute()
    len(df)

counts = []
for profiled in (False, True, False):
    with pd.session(engine="distributed") as ctx:
        before = ctx.metrics.snapshot()
        if profiled:
            with profile():
                program()
        else:
            program()
        counts.append(ctx.metrics.delta(
            before, ctx.metrics.snapshot()).get("device.syncs", 0))
print(jax.device_count(), *counts)
"""


def test_tracing_adds_no_syncs_to_a_sharded_program():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", _SHARDED_SYNCS], env=env,
                         capture_output=True, text=True, check=True, cwd=".")
    devices, plain, profiled, again = map(int, out.stdout.split()[-4:])
    assert devices == 4
    assert plain > 0
    assert profiled == plain == again


def _sharded_program():
    """Distributed: an assign and a filter on it (rowwise stages), a
    group-by, a reduction and a distinct (the host exchange)."""
    df = pd.from_arrays({"k": np.arange(400) % 7, "v": np.arange(400),
                         "x": np.arange(400.0)})
    df["y"] = df["x"] * 2.0
    df = df[df["y"] < 700.0]
    df.groupby("k")["y"].sum().compute()
    df["y"].max().compute()
    df.drop_duplicates(subset=("k",)).compute()


def test_sharded_stages_and_exchanges_span_only_while_profiled(annotations):
    import jax
    with pd.session(engine="distributed"):
        _sharded_program()
    assert annotations.built == []
    with pd.session(engine="distributed"):
        with profile() as prof:
            _sharded_program()
    ops = {s.attrs["op"] for s in prof.find("operator")}
    assert {"sharded_rowwise", "sharded_groupby", "sharded_reduce"} <= ops
    # one event per trace of a stage's jitted body, at the stage's name
    traced = {s.attrs["op"] for s in prof.find("trace")}
    assert traced == {"assign", "filter", "groupby", "reduce"}
    (exchange,) = prof.find("exchange")
    assert exchange.attrs == {"op": "distinct",
                              "shards": jax.device_count()}
    moves = [s for s in prof.find("transfer") if s.parent_id == exchange.id]
    assert {(s.attrs["site"], s.attrs["dir"]) for s in moves} == {
        ("exchange", "d2h"), ("exchange", "h2d")}
    assert {"repro:op:sharded_rowwise", "repro:op:sharded_groupby",
            "repro:exchange:distinct", "repro:trace:filter",
            "repro:trace:groupby"} <= annotations.names()
