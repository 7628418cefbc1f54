"""Rowwise-fusion pass tests: chain collapse + safety guards, the
session(fusion=False) escape hatch, trace/metric/explain surfacing, the
plan-cache environment fingerprint, and fused-vs-sequential execution
through the shared physical operator."""
from __future__ import annotations

import numpy as np
import pytest

import repro.pandas as rpd
from repro.core import get_context
from repro.core import graph as G
from repro.core import physical as X
from repro.core.fuse import fuse_rowwise_chains
from repro.core.optimizer import optimize
from repro.core.planner.plancache import plan_fingerprint


def _frame(rng, n=400):
    return rpd.from_arrays({
        "a": rng.integers(0, 8, n).astype(np.float64),
        "b": rng.random(n),
        "c": rng.integers(0, 3, n).astype(np.float64),
    })


def _chain(df):
    r = df[df["a"] > 2.0]
    r = r.assign(x=r["b"] * 2.0)
    # "aa" makes pandas column order (a, b, aa, x) differ from sorted
    # order — catches the jitted path's dict-pytree key sorting
    r = r.rename(columns={"c": "aa"})
    return r.fillna(0.0)


def _fused_nodes(roots):
    return [n for n in G.walk(roots) if n.op == "fused_rowwise"]


# ---------------------------------------------------------------------------
# Chain collapse + guards


def test_chain_collapses_to_single_fused_node(rng):
    node = _chain(_frame(rng))._node
    roots, _ = fuse_rowwise_chains([node])
    (fused,) = _fused_nodes(roots)
    # members are innermost-first: the filter executes before the assign
    assert [m.op for m in fused.ops] == ["filter", "assign", "rename",
                                        "fillna"]
    assert fused.inputs[0].op == "scan"


def test_single_rowwise_op_is_not_wrapped(rng):
    df = _frame(rng)
    node = df[df["a"] > 2.0]._node
    roots, idmap = fuse_rowwise_chains([node])
    assert not _fused_nodes(roots) and not idmap


def test_persist_mark_breaks_the_chain(rng):
    # a persisted interior node is a planned §3.5 materialization point —
    # absorbing it would make its cached value unaddressable
    df = _frame(rng)
    r = df[df["a"] > 2.0]
    r._node.persist = True
    node = r.assign(x=r["b"] * 2.0).fillna(0.0)._node
    roots, _ = fuse_rowwise_chains([node])
    (fused,) = _fused_nodes(roots)
    assert [m.op for m in fused.ops] == ["assign", "fillna"]
    assert fused.inputs[0].op == "filter" and fused.inputs[0].persist


def test_shared_interior_node_is_not_absorbed(rng):
    # the filter feeds two consumers: only the single-consumer suffix fuses
    df = _frame(rng)
    shared = df[df["a"] > 2.0]
    left = shared.assign(x=shared["b"] * 2.0).fillna(0.0)._node
    right = shared.rename(columns={"c": "cc"})._node
    roots, _ = fuse_rowwise_chains([left, right])
    for fused in _fused_nodes(roots):
        assert "filter" not in [m.op for m in fused.ops]


def test_session_fusion_false_disables_the_pass(rng):
    ctx = get_context()
    ctx.backend_options["fusion"] = False
    roots, _ = optimize([_chain(_frame(rng))._node], ctx)
    assert not _fused_nodes(roots)
    ctx.backend_options["fusion"] = True
    roots, _ = optimize([_chain(_frame(rng))._node], ctx)
    assert _fused_nodes(roots)


# ---------------------------------------------------------------------------
# Surfacing: trace event, metric, explain label, plan-cache fingerprint


def test_fuse_emits_event_and_metric(rng):
    ctx = get_context()
    before = ctx.metrics.counter("fuse.applied")
    optimize([_chain(_frame(rng))._node], ctx)
    events = [ev for ev in ctx.planner_trace
              if getattr(ev, "kind", None) == "fuse"]
    # the leading filter is absorbed into the scan by scan_pushdown, so
    # the fused chain starts at the assign
    assert events and events[-1].fields["ops"][0] == "assign"
    assert ctx.metrics.counter("fuse.applied") == before + 1


def test_explain_renders_fused_label(rng):
    out = _chain(_frame(rng)).compute()
    assert len(out["a"]) > 0
    report = rpd.explain()
    ops = [op for run in report.runs for seg in run.segments
           for op in seg.ops]
    # the filter is pushed into the scan; the remaining rowwise chain fuses
    assert any(op.startswith("fused[assign") for op in ops), ops


def test_fingerprint_covers_fusion_flag_and_kernel_impl(rng):
    ctx = get_context()
    node = _chain(_frame(rng))._node
    base = plan_fingerprint([node], ctx)
    ctx.backend_options["fusion"] = False
    off = plan_fingerprint([node], ctx)
    ctx.backend_options["fusion"] = True
    ctx.backend_options["kernel_impl"] = "pallas"
    pallas = plan_fingerprint([node], ctx)
    assert len({base, off, pallas}) == 3


# ---------------------------------------------------------------------------
# Execution: the fused pass must equal the op-at-a-time members


@pytest.mark.parametrize("xp_name", ("numpy", "jnp", "jnp-pallas"))
def test_fused_execution_matches_sequential(rng, xp_name):
    # "jnp-pallas": the TPU kernel impl, whose compaction stays on device
    node = _chain(_frame(rng))._node
    roots, _ = fuse_rowwise_chains([node])
    (fused,) = _fused_nodes(roots)
    cols = {
        "a": rng.integers(0, 8, 300).astype(np.float64),
        "b": rng.random(300),
        "c": rng.integers(0, 3, 300).astype(np.float64),
    }
    cols["b"][::7] = np.nan
    if xp_name != "numpy":
        import jax.numpy as jnp
        table = {k: jnp.asarray(v) for k, v in cols.items()}
    else:
        table = dict(cols)
    got = X.apply_fused_rowwise(
        table, fused.ops, impl="pallas" if xp_name == "jnp-pallas" else None)
    ref = dict(table)
    for m in fused.ops:
        ref = X.rowwise._apply_member(ref, m)
    assert list(got) == list(ref)     # pandas column ORDER, not just set
    for k in ref:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(ref[k]),
                                   rtol=1e-6)
