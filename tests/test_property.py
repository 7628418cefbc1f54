"""Property-based tests (hypothesis) for the system's invariants:

1. optimizer soundness — random pipelines produce identical results with and
   without every optimization rule;
2. predicate-pushdown safety over random predicates and op orders;
3. streaming/eager equivalence under random partition sizes;
4. kernel compaction/aggregation laws.
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

import repro.core as core
from repro.core import BackendEngines, get_context
from repro.core.optimizer import optimize

COLS = ["a", "b", "c"]


@st.composite
def small_table(draw):
    n = draw(st.integers(8, 200))
    seed = draw(st.integers(0, 2 ** 16))
    rng = np.random.default_rng(seed)
    return {
        "a": rng.integers(-10, 10, n).astype(np.int64),
        "b": rng.normal(size=n),
        "c": rng.integers(0, 5, n).astype(np.int64),
    }


@st.composite
def pipeline_ops(draw):
    """A random sequence of frame ops as (kind, args) tuples."""
    ops = []
    for _ in range(draw(st.integers(1, 5))):
        kind = draw(st.sampled_from(
            ["filter_gt", "filter_lt", "assign", "sort", "head", "rename"]))
        col = draw(st.sampled_from(COLS))
        val = draw(st.integers(-5, 5))
        ops.append((kind, col, val))
    return ops


def _apply_ops(df, ops, renamed):
    for kind, name, val in ops:
        col = renamed.get(name, name)     # ops name the original columns
        if kind == "filter_gt":
            df = df[df[col] > val]
        elif kind == "filter_lt":
            df = df[df[col] < val]
        elif kind == "assign":
            df[f"x_{col}"] = df[col] * 2 + val
        elif kind == "sort":
            df = df.sort_values(col)
        elif kind == "head":
            df = df.head(max(1, abs(val)) * 5)
        elif kind == "rename":
            new = f"{col}_r"
            df = df.rename({col: new})
            renamed[name] = new
    return df


def _values(res):
    return {k: np.asarray(v) for k, v in res.columns.items()}


@settings(max_examples=25, deadline=None)
@given(table=small_table(), ops=pipeline_ops())
def test_optimizer_soundness_random_pipelines(table, ops):
    """optimized(pipeline) == unoptimized(pipeline) for random programs."""
    get_context().reset()
    ctx = get_context()
    from repro.core.backends import get_backend
    be = get_backend(BackendEngines.EAGER)

    def build():
        df = core.from_arrays(table, partition_rows=32)
        return _apply_ops(df, ops, {})

    node = build()._node
    plain_roots, _ = optimize([node], ctx, enable=())
    opt_roots, _ = optimize([node], ctx)
    pv = be.execute(plain_roots, ctx)[plain_roots[0].id]
    ov = be.execute(opt_roots, ctx)[opt_roots[0].id]
    assert set(pv.keys()) == set(ov.keys())
    for k in pv:
        np.testing.assert_allclose(np.asarray(pv[k], dtype=np.float64),
                                   np.asarray(ov[k], dtype=np.float64),
                                   rtol=1e-5, atol=1e-8)


@st.composite
def rewrite_idiom_ops(draw):
    """Pipelines dense in the idioms the rewrite engine targets: sorted
    heads, sort+dedup, vectorizable row-UDFs, filtered self-concats."""
    ops = []
    for _ in range(draw(st.integers(1, 4))):
        kind = draw(st.sampled_from(
            ["sort_head", "sort_head_desc", "sort_dedup", "udf",
             "concat_filter", "filter_gt"]))
        col = draw(st.sampled_from(COLS))
        val = draw(st.integers(-5, 5))
        ops.append((kind, col, val))
    return ops


def _apply_idioms(pd_mod, df, ops):
    for kind, col, val in ops:
        if kind == "sort_head":
            df = df.sort_values(col).head(max(1, abs(val)) * 4)
        elif kind == "sort_head_desc":
            df = df.sort_values(col, ascending=False).head(max(1, abs(val)) * 4)
        elif kind == "sort_dedup":
            df = df.sort_values(col).drop_duplicates()
        elif kind == "udf":
            df = df.apply_rows(
                lambda t, c=col, v=val: dict(t, **{f"u_{c}": t[c] * 2 + v}))
        elif kind == "concat_filter":
            cat = pd_mod.concat([df, df.head(20)])
            df = cat[cat[col] > val]
        elif kind == "filter_gt":
            df = df[df[col] > val]
    return df


@settings(max_examples=25, deadline=None)
@given(table=small_table(), ops=rewrite_idiom_ops())
def test_rewritten_plans_equal_unrewritten(table, ops):
    """Plan-rewrite soundness: for idiom-dense random pipelines, the
    rewritten plan's result equals the plan with the rewrite pass disabled
    (the ``session(rewrites=False)`` escape hatch), row order included."""
    import repro.pandas as rpd
    res = {}
    for flag in (True, False):
        with rpd.session(engine="eager", rewrites=flag) as ctx:
            ctx.print_fn = lambda *a: None
            df = rpd.from_arrays(table, partition_rows=32)
            res[flag] = _values(_apply_idioms(rpd, df, ops).compute())
    assert set(res[True]) == set(res[False])
    for k in res[True]:
        np.testing.assert_array_equal(np.asarray(res[True][k]),
                                      np.asarray(res[False][k]),
                                      err_msg=f"column {k!r}")


@settings(max_examples=15, deadline=None)
@given(table=small_table(), ops=pipeline_ops(),
       part=st.sampled_from([7, 32, 1000]))
def test_streaming_matches_eager(table, ops, part):
    get_context().reset()
    ctx = get_context()

    def run(backend):
        ctx.backend = backend
        df = core.from_arrays(table, partition_rows=part)
        return _values(_apply_ops(df, ops, {}).compute())

    ev = run(BackendEngines.EAGER)
    sv = run(BackendEngines.STREAMING)
    assert set(ev.keys()) == set(sv.keys())
    for k in ev:
        # eager runs f32 (jax x32), streaming f64 — compare at f32 precision
        np.testing.assert_allclose(np.asarray(ev[k], np.float64),
                                   np.asarray(sv[k], np.float64),
                                   rtol=5e-4, atol=1e-6)


@settings(max_examples=20, deadline=None)
@given(table=small_table(),
       keycol=st.sampled_from(["a", "c"]),
       fn=st.sampled_from(["sum", "mean", "min", "max", "count"]))
def test_groupby_partial_combine_law(table, keycol, fn):
    """Streaming partial+combine group-by == whole-table group-by."""
    get_context().reset()
    ctx = get_context()
    res = {}
    for backend, part in ((BackendEngines.EAGER, 10 ** 6),
                          (BackendEngines.STREAMING, 16)):
        ctx.backend = backend
        df = core.from_arrays(table, partition_rows=part)
        g = getattr(df.groupby([keycol])["b"], fn)()
        res[backend] = _values(g.sort_values(keycol).compute())
    e, s = res[BackendEngines.EAGER], res[BackendEngines.STREAMING]
    np.testing.assert_array_equal(e[keycol], s[keycol])
    # f32 (eager/jax) vs f64 (streaming/np) accumulation
    np.testing.assert_allclose(np.asarray(e["b"], np.float64),
                               np.asarray(s["b"], np.float64), rtol=5e-4,
                               atol=1e-6)


@st.composite
def calibration_scales(draw):
    """Randomized measured sec/work scales spanning orders of magnitude —
    skewed calibrations push the operator-granular planner into different
    (possibly split) placements."""
    return {name: draw(st.sampled_from([1e-9, 1e-6, 1e-3, 1.0]))
            for name in ("eager", "streaming", "distributed")}


@settings(max_examples=15, deadline=None)
@given(table=small_table(), ops=pipeline_ops(), scales=calibration_scales())
def test_operator_granular_auto_matches_fixed_backend(table, ops, scales):
    """Whatever segments the operator-granular planner picks (under any
    runtime calibration), the hybrid result equals forcing one backend."""
    from repro.core.planner.feedback import MIN_RUNTIME_SAMPLES
    get_context().reset()
    ctx = get_context()

    ctx.backend = BackendEngines.EAGER
    df = core.from_arrays(table, partition_rows=32)
    ref = _values(_apply_ops(df, ops, {}).compute())

    ctx.reset()
    ctx.backend = BackendEngines.AUTO
    for name, s in scales.items():
        for _ in range(MIN_RUNTIME_SAMPLES):
            ctx.stats_store.record_runtime(name, 1.0, s)
    df = core.from_arrays(table, partition_rows=32)
    av = _values(_apply_ops(df, ops, {}).compute())

    assert set(ref.keys()) == set(av.keys())
    for k in ref:
        # engines differ in float width (eager f32, streaming f64)
        np.testing.assert_allclose(np.asarray(ref[k], np.float64),
                                   np.asarray(av[k], np.float64),
                                   rtol=5e-4, atol=1e-6)


@settings(max_examples=15, deadline=None)
@given(table=small_table(), ops=pipeline_ops(),
       budget=st.sampled_from([1 << 10, 1 << 14, 1 << 20, None]))
def test_planner_segments_respect_memory_budget(table, ops, budget):
    """Every segment the planner emits either fits ``ctx.memory_budget``
    (estimated peak) or is explicitly marked infeasible with every
    alternative rejected for the budget too."""
    from repro.core.optimizer import optimize as opt
    from repro.core.planner.select import plan_placement
    get_context().reset()
    ctx = get_context()
    ctx.backend = BackendEngines.AUTO
    ctx.memory_budget = budget
    df = core.from_arrays(table, partition_rows=32)
    node = _apply_ops(df, ops, {})._node
    roots, _ = opt([node], ctx)
    decisions = plan_placement(roots, ctx)
    seen: set[int] = set()
    for d in decisions:
        if budget is not None and d.feasible:
            assert d.cost.peak_bytes <= budget
        elif budget is not None:
            assert all("budget!" in r or "pricing-failed" in r
                       for r in d.rejected.values())
        # segments partition the plan: no operator is assigned twice
        ids = {n.id for n in d.nodes}
        assert not (ids & seen)
        seen |= ids


@settings(max_examples=20, deadline=None)
@given(st.lists(st.booleans(), min_size=1, max_size=300),
       st.integers(0, 2 ** 16))
def test_filter_compact_properties(mask, seed):
    """Kernel law: packed prefix == input[mask]; tail is zero."""
    import jax.numpy as jnp
    from repro.kernels.filter_compact import filter_compact
    rng = np.random.default_rng(seed)
    mask = np.asarray(mask)
    vals = rng.normal(size=mask.shape[0]).astype(np.float32)
    packed, count = filter_compact(jnp.asarray(vals), jnp.asarray(mask),
                                   block_rows=1024)
    packed = np.asarray(packed)
    assert int(count) == int(mask.sum())
    np.testing.assert_allclose(packed[: int(count)], vals[mask], rtol=1e-6)
    assert not packed[int(count):].any()


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 40), st.integers(1, 500), st.integers(0, 2 ** 16))
def test_groupby_sum_kernel_total_preserved(groups, n, seed):
    """Σ_g out[g] == Σ values (mass conservation)."""
    import jax.numpy as jnp
    from repro.kernels.groupby_sum import groupby_sum
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, groups, n).astype(np.int32)
    vals = rng.normal(size=n).astype(np.float32)
    out = np.asarray(groupby_sum(jnp.asarray(codes), jnp.asarray(vals),
                                 groups, block_rows=1024))
    np.testing.assert_allclose(out.sum(), vals.sum(), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Native distributed join ≡ eager join on random dict-coded keys


@st.composite
def _dist_join_case(draw):
    seed = draw(st.integers(0, 2 ** 16))
    n = draw(st.integers(1, 300))
    b = draw(st.integers(1, 64))
    domain = draw(st.integers(1, 30))
    how = draw(st.sampled_from(["inner", "left"]))
    rng = np.random.default_rng(seed)
    probe = {"k": rng.integers(0, domain, n).astype(np.int64),
             "v": rng.integers(-100, 100, n).astype(np.int64)}
    build = {"k": rng.integers(0, domain, b).astype(np.int64),
             "w": rng.integers(-100, 100, b).astype(np.int64)}
    return probe, build, how


@settings(max_examples=40, deadline=None)
@given(case=_dist_join_case())
def test_native_distributed_join_equals_eager_join(case):
    """Whatever native path fires (broadcast-hash for unique small builds,
    shuffle-by-dict-code otherwise), the device-resident result equals the
    eager host hash join exactly — values AND probe-order row order."""
    from repro.core import physical as X
    from repro.core.backends.distributed import _default_mesh
    from repro.core.physical.sharded import ShardedTable
    probe, build, how = case
    mesh = _default_mesh()
    t = X.shard_host_table(probe, mesh, "data")
    out = X.sharded_join(t, build, ["k"], how, ("_x", "_y"), mesh, "data")
    ref = X.apply_join(probe, build, ["k"], how)
    assert isinstance(out, ShardedTable)
    got = out.gather()
    assert set(got) == set(ref)
    for c in ref:   # integer payloads: equality is exact, order included
        np.testing.assert_array_equal(np.asarray(got[c], np.int64),
                                      np.asarray(ref[c], np.int64),
                                      err_msg=f"{how}:{c}")


# ---------------------------------------------------------------------------
# 5. plan-cache fingerprint laws (planner/plancache.py): structural identity
#    collides, any op/param/schema mutation separates, and the stats epoch
#    reacts to exactly the feedback a plan can see.


@st.composite
def fp_pipeline(draw):
    ops = []
    for _ in range(draw(st.integers(1, 6))):
        kind = draw(st.sampled_from(
            ["filter_gt", "filter_lt", "assign", "sort", "head", "project"]))
        col = draw(st.sampled_from(COLS))
        val = draw(st.integers(-5, 5))
        ops.append((kind, col, val))
    return ops


def _fp_source(seed=0, n=500):
    rng = np.random.default_rng(seed)
    return core.InMemorySource({
        "a": rng.integers(-10, 10, n).astype(np.int64),
        "b": rng.normal(size=n),
        "c": rng.integers(0, 5, n).astype(np.int64),
    }, 128)


def _fp_build(src, ops):
    from repro.core import expr as E
    from repro.core import graph as G
    node = G.Scan(src)
    for kind, col, val in ops:
        if kind == "filter_gt":
            node = G.Filter(node, E.BinOp("gt", E.Col(col), E.Lit(val)))
        elif kind == "filter_lt":
            node = G.Filter(node, E.BinOp("lt", E.Col(col), E.Lit(val)))
        elif kind == "assign":
            node = G.Assign(node, f"x_{col}", E.BinOp(
                "add", E.BinOp("mul", E.Col(col), E.Lit(2)), E.Lit(val)))
        elif kind == "sort":
            node = G.SortValues(node, [col])
        elif kind == "head":
            node = G.Head(node, max(1, abs(val)) * 5)
        elif kind == "project":
            node = G.Project(node, COLS)
    return [node]


@settings(max_examples=60, deadline=None)
@given(ops=fp_pipeline(), seed_a=st.integers(0, 99), seed_b=st.integers(0, 99))
def test_fingerprint_structural_identity_collides(ops, seed_a, seed_b):
    """Identical shapes collide — including over *different* data (the
    source cache_token is deliberately not part of the fingerprint)."""
    from repro.core.context import LaFPContext
    from repro.core.planner.plancache import plan_fingerprint
    ctx = LaFPContext(name="prop")
    fp_a = plan_fingerprint(_fp_build(_fp_source(seed_a), ops), ctx)
    fp_b = plan_fingerprint(_fp_build(_fp_source(seed_b), ops), ctx)
    assert fp_a == fp_b


@settings(max_examples=60, deadline=None)
@given(ops=fp_pipeline(),
       extra=st.sampled_from(["filter_gt", "assign", "head"]),
       col=st.sampled_from(COLS))
def test_fingerprint_shape_mutation_separates(ops, extra, col):
    from repro.core.context import LaFPContext
    from repro.core.planner.plancache import plan_fingerprint
    ctx = LaFPContext(name="prop")
    src = _fp_source()
    base = plan_fingerprint(_fp_build(src, ops), ctx)
    longer = plan_fingerprint(_fp_build(src, ops + [(extra, col, 7)]), ctx)
    assert base != longer


@settings(max_examples=30, deadline=None)
@given(ops=fp_pipeline(), val=st.integers(6, 20))
def test_fingerprint_param_mutation_separates(ops, val):
    """Changing one op parameter (a filter constant) separates."""
    from repro.core.context import LaFPContext
    from repro.core.planner.plancache import plan_fingerprint
    ctx = LaFPContext(name="prop")
    src = _fp_source()
    probe = [("filter_gt", "a", 0)] + ops
    mutated = [("filter_gt", "a", val)] + ops
    assert (plan_fingerprint(_fp_build(src, probe), ctx)
            != plan_fingerprint(_fp_build(src, mutated), ctx))


@settings(max_examples=30, deadline=None)
@given(ops=fp_pipeline(), rows=st.integers(1, 10 ** 6))
def test_stats_epoch_sees_own_plan_only(ops, rows):
    """Recording a cardinality for a node of THIS plan moves the epoch;
    feedback about unrelated plans leaves it alone."""
    from repro.core.context import LaFPContext
    from repro.core.planner.plancache import stats_epoch
    ctx = LaFPContext(name="prop")
    roots = _fp_build(_fp_source(), ops)
    e0 = stats_epoch(roots, ctx)
    ctx.stats_store.record(("unrelated", "key"), rows=rows, nbytes=8 * rows)
    assert stats_epoch(roots, ctx) == e0
    ctx.stats_store.record(roots[0].key(), rows=rows, nbytes=8 * rows)
    assert stats_epoch(roots, ctx) != e0
