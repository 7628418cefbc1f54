"""The reduction from a profiler trace to busy time, idle share, device
time per operation and named idle gaps, on a synthetic trace and on one
recorded on the CPU."""
import types

import pytest

from bench import trace as T


def ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def plane(name, **lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=k.replace("_", " "), events=v)
        for k, v in lines.items()])


def synthetic():
    host = plane("/host:CPU", python3=[
        ev("bench:window", 100, 1000),
        ev("bench:a", 100, 500), ev("bench:a/compute", 100, 300),
        ev("bench:a/to_host", 400, 200), ev("bench:b", 600, 500),
        ev("unrelated", 0, 2000)])
    tpu = plane("/device:TPU:0",
                XLA_Ops=[ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 50,
                            150),
                         ev("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p)", 150,
                            100),
                         ev("%_groupby_sum.1 = f32[1,8,128] custom-call(...),"
                            ' custom_call_target="tpu_custom_call"', 700, 100),
                         ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 1050,
                            200)],
                XLA_Modules=[ev("jit_f(123)", 0, 600),
                             ev("jit_g(456)", 650, 250),
                             ev("jit_f(123)", 1000, 400)])
    return T.from_planes([host, tpu, plane("/host:metadata")])


def test_busy_union_and_idle_share_inside_the_window():
    view = synthetic()
    assert view.window == (100, 1100)
    assert view.window_s() == pytest.approx(1e-6)
    # [100, 250) merged from two overlapping ops, [700, 800), [1050, 1100)
    assert view.busy_s() == pytest.approx(300e-9)


def test_device_seconds_per_operation():
    ops = synthetic().op_seconds()
    assert ops == pytest.approx({"jit_f/fusion.1": 150e-9,
                                 "jit_f/fusion.2": 100e-9,
                                 "jit_g/_groupby_sum.1": 100e-9})


def test_operations_keep_their_instruction_text():
    kernel = synthetic().devices[0][2]
    assert kernel.name == "_groupby_sum.1"
    assert "tpu_custom_call" in kernel.stats["long_name"]
    assert kernel.stats["hlo_module"] == "jit_g"


def test_gaps_are_named_by_the_innermost_annotation():
    gaps = synthetic().idle_gaps()
    assert [g[0] for g in gaps] == ["a/to_host", "b"]
    assert [g[1] for g in gaps] == pytest.approx([450e-9, 250e-9])


def test_devices_are_averaged():
    view = synthetic()
    view.devices.append([T.Event("x", 100, 1100, {})])
    assert view.busy_s() == pytest.approx((300e-9 + 1000e-9) / 2)


def test_a_trace_without_its_window_is_refused():
    with pytest.raises(ValueError):
        T.from_planes([plane("/host:CPU", python3=[ev("bench:a", 0, 1)])]
                      ).window


def test_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(T.WINDOW):
        with jax.profiler.TraceAnnotation("bench:prog"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    view = T.load(str(tmp_path))
    names = [a.name for a in view.annotations]
    assert names == [T.WINDOW, "bench:prog"]
    lo, hi = view.window
    assert hi > lo
    assert view.devices == []        # the CPU has no device plane
