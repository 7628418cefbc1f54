"""Plan-rewrite engine tests: rule matching, safety guards, fixpoint
termination, differential equivalence (rewritten ≡ unrewritten), explain
records, and the pre-execution linter."""
from __future__ import annotations

import numpy as np
import pytest

import repro.pandas as rpd
from repro.core import get_context
from repro.core import graph as G
from repro.core.optimizer import optimize
from repro.core.rewrite import (DEFAULT_RULES, apply_rewrites,
                                default_rules)
from repro.lint import lint_source


def _frame(rng, n=500):
    return rpd.from_arrays({
        "a": rng.integers(0, 8, n).astype(np.float64),
        "b": rng.random(n),
        "c": rng.integers(0, 3, n).astype(np.float64),
    })


def _ops(roots):
    return [n.op for n in G.walk(roots)]


# ---------------------------------------------------------------------------
# Rule matching / guards


def test_sort_head_collapses_to_top_k(rng):
    df = _frame(rng)
    node = df.sort_values("b").head(7)._node
    roots, _, events = apply_rewrites([node])
    ops = _ops(roots)
    assert "top_k" in ops and "sort_values" not in ops and "head" not in ops
    (ev,) = events
    assert ev.rule == "sort_head_to_top_k"
    top = next(n for n in G.walk(roots) if n.op == "top_k")
    assert top.n == 7 and top.by == ("b",) and top.mode == "sort"


def test_nlargest_lowers_to_top_k_directly(rng):
    # nlargest doesn't need the rewrite: the facade lowers it natively
    df = _frame(rng)
    node = df.nlargest(5, "b")._node
    assert node.op == "top_k" and node.mode == "select"


def test_dedup_reorders_before_ascending_sort(rng):
    df = _frame(rng)
    node = df.sort_values("a").drop_duplicates()._node
    roots, _, events = apply_rewrites([node])
    assert [ev.rule for ev in events] == ["dedup_before_sort"]
    root = roots[0]
    assert root.op == "sort_values" and root.inputs[0].op == "drop_duplicates"


@pytest.mark.parametrize("case", ("descending", "subset"))
def test_dedup_guard_blocks_unsafe_commutes(rng, case):
    df = _frame(rng)
    if case == "descending":
        node = df.sort_values("a", ascending=False).drop_duplicates()._node
    else:
        node = df.sort_values("a").drop_duplicates(subset=("a",))._node
    _, _, events = apply_rewrites([node])
    assert not [ev for ev in events if ev.rule == "dedup_before_sort"]


def test_multi_parent_sort_is_not_absorbed(rng):
    # the sorted frame is used twice: collapsing it into TopK would steal
    # the other consumer's input
    df = _frame(rng).sort_values("b")
    head = df.head(3)._node
    full = df._node                              # second consumer of the sort
    _, _, events = apply_rewrites([head, full])
    assert not events


def test_persist_mark_blocks_rewrite(rng):
    df = _frame(rng)
    node = df.sort_values("b").head(3)._node
    node.inputs[0].persist = True                 # planned reuse point
    _, _, events = apply_rewrites([node])
    assert not events


def test_filter_pushes_through_concat(rng):
    df = _frame(rng)
    cat = rpd.concat([df, df])
    node = cat[cat["a"] > 3]._node
    roots, _, events = apply_rewrites([node])
    assert [ev.rule for ev in events] == ["filter_through_concat"]
    root = roots[0]
    assert root.op == "concat"
    assert all(c.op == "filter" for c in root.inputs)


def test_filter_through_concat_keeps_shared_scan_whole(rng):
    # after the push, the scan feeds both the filter and a head: zone maps
    # must not prune partitions that the head still reads
    b = np.zeros(64)
    b[50] = 3.0                      # the only match, in the second partition
    table = {"a": np.arange(64), "b": b}
    res = {}
    for flag in (True, False):
        with rpd.session(engine="eager", rewrites=flag):
            df = rpd.from_arrays(table, partition_rows=32)
            cat = rpd.concat([df, df.head(20)])
            res[flag] = np.asarray(cat[cat["b"] > 2].compute().columns["a"])
    np.testing.assert_array_equal(res[True], [50])
    np.testing.assert_array_equal(res[False], [50])


def test_map_rows_vectorizes_to_native_exprs(rng):
    df = _frame(rng)
    node = df.apply_rows(lambda t: {"a": t["a"], "s": t["a"] + 2 * t["b"]},
                         name="lin")._node
    roots, _, events = apply_rewrites([node])
    assert [ev.rule for ev in events] == ["map_rows_vectorize"]
    ops = _ops(roots)
    assert "map_rows" not in ops and "assign" in ops and "project" in ops


def test_map_rows_with_control_flow_stays_opaque(rng):
    df = _frame(rng)

    def udf(t):
        if t["a"] is not None and t["a"]:          # truthiness aborts trace
            return {"a": t["a"]}
        return {"a": t["b"]}

    node = df.apply_rows(udf)._node
    _, _, events = apply_rewrites([node])
    assert not events


def test_fixpoint_terminates_and_chains_rules(rng):
    # dedup-before-sort leaves a SortValues on top; a Head above it must
    # then collapse with *that* sort into TopK on the deduped input —
    # two different rules firing across fixpoint iterations
    df = _frame(rng)
    node = df.sort_values("a").drop_duplicates().head(4)._node
    roots, _, events = apply_rewrites([node])
    rules = sorted(ev.rule for ev in events)
    assert rules == ["dedup_before_sort", "sort_head_to_top_k"]
    ops = _ops(roots)
    assert ops.count("top_k") == 1 and "sort_values" not in ops


def test_default_rules_have_linter_metadata():
    assert default_rules() is DEFAULT_RULES
    for rule in DEFAULT_RULES:
        assert rule.name and rule.summary


# ---------------------------------------------------------------------------
# Differential equivalence: rewritten ≡ unrewritten


def _run_idioms(engine, rewrites, seed):
    with rpd.session(engine=engine, rewrites=rewrites) as ctx:
        ctx.print_fn = lambda *a: None
        rng = np.random.default_rng(seed)
        df = _frame(rng, n=1_000)
        outs = []
        outs.append(df.sort_values("b", ascending=False).head(13)
                    .to_numpy_table())
        outs.append(df.sort_values("b").head(2_000).to_numpy_table())  # k>rows
        outs.append(df.sort_values("a").drop_duplicates().to_numpy_table())
        outs.append(df.apply_rows(
            lambda t: {"b": t["a"], "a": t["b"], "z": t["a"] * t["c"] + 1})
            .to_numpy_table())                     # column-swapping UDF
        cat = rpd.concat([df, df.head(200)])
        outs.append(cat[cat["c"] >= 1].to_numpy_table())
        outs.append(df.nlargest(9, "b").to_numpy_table())
        outs.append(df.nsmallest(9, "b").to_numpy_table())
    return outs


@pytest.mark.parametrize("engine", ("eager", "streaming"))
def test_rewritten_plans_match_unrewritten(engine):
    for seed in (0, 1, 2):
        on = _run_idioms(engine, True, seed)
        off = _run_idioms(engine, False, seed)
        for i, (x, y) in enumerate(zip(on, off)):
            assert list(x) == list(y), f"idiom {i}: column mismatch"
            for k in x:
                np.testing.assert_array_equal(
                    np.asarray(x[k]), np.asarray(y[k]),
                    err_msg=f"idiom {i} col {k!r} (seed {seed})")


def test_session_rewrites_false_disables_pass(rng):
    with rpd.session(engine="eager", rewrites=False) as ctx:
        df = _frame(rng)
        node = df.sort_values("b").head(3)._node
        roots, _ = optimize([node], ctx)
        assert "top_k" not in _ops(roots)
        assert not getattr(ctx, "_pending_rewrites", None)
        assert not ctx.metrics.snapshot().get("rewrite.applied")


# ---------------------------------------------------------------------------
# Observability: trace, metric, explain records


def test_rewrite_emits_trace_metric_and_explain_record(rng):
    with rpd.session(engine="eager") as ctx:
        ctx.print_fn = lambda *a: None
        df = _frame(rng)
        _ = df.sort_values("b").head(3).to_numpy_table()
        assert ctx.metrics.snapshot().get("rewrite.applied") == 1
        kinds = [getattr(t, "kind", None) for t in ctx.optimizer_trace]
        assert "rewrite" in kinds
        rep = rpd.explain()
        recs = rep.runs[-1].rewrites
        assert len(recs) == 1
        (rec,) = recs
        assert rec.rule == "sort_head_to_top_k"
        assert rec.before_op == "head" and rec.after_op == "top_k"
        assert rec.cost_delta is not None and rec.cost_delta < 0
        assert "rewrite sort_head_to_top_k" in rep.render()
        # drained: a second report must not repeat the records
        assert not getattr(ctx, "_pending_rewrites", None)


def test_plan_only_explain_reports_rewrites(rng):
    with rpd.session(engine="eager") as ctx:
        ctx.print_fn = lambda *a: None
        df = _frame(rng)
        rep = rpd.explain(df.sort_values("b").head(3))
        assert rep.runs[0].rewrites
        assert rep.runs[0].rewrites[0].rule == "sort_head_to_top_k"


# ---------------------------------------------------------------------------
# Pre-execution linter


_LINT_PROGRAM = '''
import repro.pandas as pd
df = pd.read_csv("rides.csv")
top = df.sort_values("fare").head(10)
uniq = df.sort_values("fare").drop_duplicates()
skip = df.sort_values("fare", ascending=False).drop_duplicates()
big = df.nlargest(5, "fare")
dev = df["fare"].std()
boom = df.pivot_table(index="fare")
vec = df.apply_rows(lambda t: {"x": t["fare"] * 2})
'''


def test_linter_classifies_idioms_and_gaps():
    diags = lint_source(_LINT_PROGRAM)
    by_kind = {}
    for d in diags:
        by_kind.setdefault(d.kind, []).append(d)
    assert [d.line for d in by_kind["rewrite.top_k"]] == [4]
    assert [d.line for d in by_kind["rewrite.dedup_before_sort"]] == [5]
    assert [d.line for d in by_kind["native.top_k"]] == [7]
    assert [d.line for d in by_kind["fallback.materialize"]] == [8]
    assert [d.line for d in by_kind["fallback.failed"]] == [9]
    assert [d.line for d in by_kind["rewrite.vectorize"]] == [10]
    # the guarded-out descending dedup (line 6) must NOT be advertised
    assert 6 not in [d.line for d in diags]
    failed = by_kind["fallback.failed"][0]
    assert failed.level == "warn" and "pivot_table" in failed.message


def test_linter_cli_exit_codes(tmp_path):
    from repro.lint import main
    bad = tmp_path / "bad.py"
    bad.write_text(_LINT_PROGRAM)
    good = tmp_path / "good.py"
    good.write_text('import repro.pandas as pd\n'
                    'df = pd.read_csv("r.csv")\n'
                    'print(df.sort_values("a").head(3))\n')
    assert main([str(good)]) == 0
    assert main([str(bad)]) == 1           # fallback.failed → regression
    assert main([]) == 2


def test_analyze_attaches_diagnostics_and_explain_surfaces_them(tmp_path):
    prog = tmp_path / "prog.py"
    prog.write_text(
        "import numpy as np\n"
        "import repro.pandas as rpd\n"
        "from repro.core import get_context\n"
        "def run():\n"
        "    df = rpd.from_arrays({'a': np.arange(20.0)})\n"
        "    return df.sort_values('a').head(3)\n")
    import importlib.util
    spec = importlib.util.spec_from_file_location("lint_prog", prog)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with rpd.session(engine="eager") as ctx:
        ctx.print_fn = lambda *a: None
        decorated = rpd.analyze(mod.run)
        _ = decorated()
        diags = ctx.analysis.get("diagnostics")
        assert diags and diags[0].kind == "rewrite.top_k"
        assert diags[0].line == 6          # absolute file line of the idiom
        rep = rpd.explain()
        assert rep.diagnostics and rep.diagnostics[0].kind == "rewrite.top_k"
        assert "[rewrite.top_k]" in rep.render()
