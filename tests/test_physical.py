"""Tests for the unified physical-operator layer (`repro.core.physical`):

1. layer surface — the package exposes the operator set and the
   ``exec_common`` shim still re-exports it;
2. native distributed join/sort/distinct — broadcast-hash and
   shuffle-by-dict-code paths agree with the host kernels at every shard
   count, including a hypothesis property (native join ≡ eager join on
   random dict-coded keys);
3. the distributed backend really runs these ops natively (no eager
   fallback) and keeps results pandas-shaped;
4. device-resident handoffs — a distributed→distributed segment chain
   passes a ``ShardedTable`` payload with no intermediate host gather;
5. stats-store persistence and peak-estimate calibration (satellites).
"""
import json
import os

import numpy as np
import pytest

import repro.core as core
from repro.core import BackendEngines, get_context
from repro.core import expr as E
from repro.core import graph as G
from repro.core import physical as X
from repro.core.backends.distributed import DistributedBackend, _default_mesh
from repro.core.physical.sharded import ShardedTable


def _mesh():
    return _default_mesh()


def _probe_arrays(rng, n=3000):
    return {
        "k": rng.integers(0, 40, n).astype(np.int64),
        "zone": rng.integers(0, 12, n).astype(np.int32),
        "val": rng.integers(-50, 50, n).astype(np.int64),
        "f": rng.uniform(0, 100, n),
    }


# ---------------------------------------------------------------------------
# Layer surface


def test_exec_common_shim_reexports_physical_layer():
    from repro.core import exec_common as XC
    for name in ("apply_join", "apply_groupby_agg", "apply_sort",
                 "apply_drop_duplicates", "to_host_value", "handoff_value",
                 "ShardedTable", "sharded_join", "sharded_sort",
                 "sharded_distinct", "shard_host_table"):
        assert getattr(XC, name) is getattr(X, name), name


def test_backends_bind_the_shared_physical_layer():
    import repro.core.backends.eager as eb
    import repro.core.backends.streaming as sb
    import repro.core.backends.distributed as db
    assert eb.X is X and sb.X is X and db.X is X


# ---------------------------------------------------------------------------
# Native distributed operators ≡ host kernels


def _assert_tables_equal(actual: dict, expected: dict, rtol=1e-6):
    assert set(actual) == set(expected)
    for c in expected:
        a = np.asarray(actual[c], np.float64)
        e = np.asarray(expected[c], np.float64)
        np.testing.assert_allclose(a, e, rtol=rtol, err_msg=f"column {c!r}")


@pytest.mark.parametrize("how", ["inner", "left"])
def test_broadcast_hash_join_matches_host_kernel(how, rng):
    probe = _probe_arrays(rng)
    build = {"k": np.arange(40, dtype=np.int64),
             "fee": rng.uniform(0, 1, 40),
             "f": rng.uniform(0, 1, 40)}          # overlap column → suffixes
    mesh = _mesh()
    t = X.shard_host_table(probe, mesh, "data")
    out = X.sharded_join(t, build, ["k"], how, ("_x", "_y"), mesh, "data")
    assert isinstance(out, ShardedTable), "broadcast path not taken"
    _assert_tables_equal(out.gather(), X.apply_join(probe, build, ["k"], how))


@pytest.mark.parametrize("how", ["inner", "left"])
def test_shuffle_join_matches_host_kernel(how, rng):
    probe = _probe_arrays(rng)
    # duplicate build keys force the shuffle-by-dict-code path
    build = {"k": rng.integers(0, 25, 400).astype(np.int64),
             "fee": rng.uniform(0, 1, 400)}
    mesh = _mesh()
    t = X.shard_host_table(probe, mesh, "data")
    out = X.sharded_join(t, build, ["k"], how, ("_x", "_y"), mesh, "data")
    assert isinstance(out, ShardedTable)
    _assert_tables_equal(out.gather(), X.apply_join(probe, build, ["k"], how))


def test_multi_key_join_matches_host_kernel(rng):
    probe = _probe_arrays(rng)
    build = {"k": rng.integers(0, 40, 60).astype(np.int64),
             "zone": rng.integers(0, 12, 60).astype(np.int32),
             "fee": rng.uniform(0, 1, 60)}
    mesh = _mesh()
    t = X.shard_host_table(probe, mesh, "data")
    out = X.sharded_join(t, build, ["k", "zone"], "inner", ("_x", "_y"),
                         mesh, "data")
    assert isinstance(out, ShardedTable)
    _assert_tables_equal(out.gather(),
                         X.apply_join(probe, build, ["k", "zone"], "inner"))


def test_join_with_empty_build_side(rng):
    """Empty build tables must not crash the host kernel — the distributed
    shuffle join feeds it per-shard key buckets that can be empty."""
    probe = _probe_arrays(rng, 50)
    empty = {"k": np.zeros(0, np.int64), "fee": np.zeros(0)}
    lj = X.apply_join(probe, empty, ["k"], "left")
    assert X.table_rows(lj) == 50
    assert np.isnan(np.asarray(lj["fee"])).all()
    assert X.table_rows(X.apply_join(probe, empty, ["k"], "inner")) == 0


def test_shuffle_join_skewed_keys_leave_empty_buckets(rng):
    """All build rows share one key: with n_shards > 1 every other shard's
    build bucket is empty (the multishard CI job exercises this for real;
    at one shard it degenerates gracefully)."""
    probe = {"k": np.arange(8, dtype=np.int64).repeat(10),
             "v": np.arange(80, dtype=np.int64)}
    build = {"k": np.full(64, 2, dtype=np.int64),
             "fee": rng.uniform(0, 1, 64)}
    mesh = _mesh()
    t = X.shard_host_table(probe, mesh, "data")
    for how in ("inner", "left"):
        out = X.sharded_join(t, build, ["k"], how, ("_x", "_y"),
                             mesh, "data")
        assert isinstance(out, ShardedTable)
        ref = X.apply_join(probe, build, ["k"], how)
        got = out.gather()
        for c in ref:
            a = np.asarray(got[c], np.float64)
            e = np.asarray(ref[c], np.float64)
            np.testing.assert_array_equal(np.isnan(a), np.isnan(e))
            m = ~np.isnan(e)
            np.testing.assert_allclose(a[m], e[m], rtol=1e-6,
                                       err_msg=f"{how}:{c}")


def test_non_integer_keys_fall_back(rng):
    probe = _probe_arrays(rng)
    build = {"f": rng.uniform(0, 100, 10), "fee": rng.uniform(0, 1, 10)}
    mesh = _mesh()
    t = X.shard_host_table(probe, mesh, "data")
    assert X.sharded_join(t, build, ["f"], "inner", ("_x", "_y"),
                          mesh, "data") is None


@pytest.mark.parametrize("ascending", [True, False])
def test_sharded_sort_matches_host_kernel(ascending, rng):
    probe = _probe_arrays(rng)
    mesh = _mesh()
    t = X.shard_host_table(probe, mesh, "data")
    out = X.sharded_sort(t, ["k", "val"], ascending, mesh, "data")
    assert isinstance(out, ShardedTable)
    _assert_tables_equal(out.gather(),
                         X.apply_sort(probe, ["k", "val"], ascending))


def test_sharded_distinct_matches_host_kernel(rng):
    probe = _probe_arrays(rng)
    mesh = _mesh()
    t = X.shard_host_table(probe, mesh, "data")
    out = X.sharded_distinct(t, ("k", "zone"), mesh, "data")
    assert isinstance(out, ShardedTable)
    _assert_tables_equal(out.gather(),
                         X.apply_drop_duplicates(probe, ["k", "zone"]))


# ---------------------------------------------------------------------------
# The distributed backend runs join/sort/distinct natively


def _dist_src(rng, n=4000, partition_rows=512):
    return core.InMemorySource(_probe_arrays(rng, n), partition_rows)


def test_distributed_backend_join_sort_distinct_native(rng, monkeypatch):
    """No eager fallback fires for join/sort/distinct on dict-coded keys."""
    src = _dist_src(rng)
    fee = core.InMemorySource(
        {"k": np.arange(40, dtype=np.int64),
         "fee": rng.uniform(0, 1, 40)}, 64)
    backend = DistributedBackend()

    banned = {"join", "sort_values", "drop_duplicates"}

    def no_fallback(n, vals):
        assert n.op not in banned, f"{n.op} fell back to eager"
        return DistributedBackend._fallback_node(backend, n, vals)

    monkeypatch.setattr(backend, "_fallback_node", no_fallback)
    ctx = get_context()
    scan, feescan = G.Scan(src), G.Scan(fee)
    join = G.Join(scan, feescan, ["k"], "inner")
    srt = G.SortValues(join, ["k", "val"])
    dd = G.DropDuplicates(srt, ("k",))
    res = backend.execute([dd], ctx)[dd.id]
    # ground truth through the shared host kernels
    full = {k: np.asarray(v) for k, v in src._arrays.items()}
    feet = {k: np.asarray(v) for k, v in fee._arrays.items()}
    ref = X.apply_drop_duplicates(
        X.apply_sort(X.apply_join(full, feet, ["k"], "inner"),
                     ["k", "val"]), ["k"])
    _assert_tables_equal(res, ref)


@pytest.mark.parametrize("engine", ["eager", "distributed"])
def test_int_group_sums_past_int32_are_exact(rng, engine):
    """Group totals of an int column above 2^31 come back exact, as the
    int64 pandas gives, though device ints are int32."""
    from repro.core.backends import get_backend
    n = 3000
    arrays = {"k": rng.integers(0, 4, n).astype(np.int64),
              "cents": rng.integers(1 << 29, 1 << 31, n).astype(np.int64)}
    arrays["cents"][arrays["k"] == 3] = 7          # one group fits int32
    node = G.GroupByAgg(G.Scan(core.InMemorySource(arrays, 512)), ["k"],
                        {"total": ("cents", "sum"), "avg": ("cents", "mean")})
    res = get_backend(engine).execute([node], get_context())[node.id]
    want = np.zeros(4, np.int64)
    np.add.at(want, arrays["k"], arrays["cents"])
    assert want.max() > 1 << 31
    np.testing.assert_array_equal(np.asarray(res["k"]), np.arange(4))
    np.testing.assert_array_equal(np.asarray(res["total"]), want)
    assert np.asarray(res["total"]).dtype == np.int64
    np.testing.assert_allclose(np.asarray(res["avg"], np.float64),
                               want / np.bincount(arrays["k"]), rtol=1e-4)  # f32 sums


# ---------------------------------------------------------------------------
# Device-resident handoff: distributed→distributed chain, no host gather


def test_distributed_chain_handoff_stays_device_resident(rng, monkeypatch):
    from repro.core.planner.cost import CostEstimate
    from repro.core.planner.select import Decision
    from repro.core.runtime import execute_segments

    src = _dist_src(rng)
    scan = G.Scan(src)
    filt = G.Filter(scan, E.BinOp("gt", E.Col("f"), E.Lit(25.0)))
    srt = G.SortValues(filt, ["k", "val"])

    def dec(roots, nodes, boundary=()):
        return Decision(roots=list(roots), backend=BackendEngines.DISTRIBUTED,
                        cost=CostEstimate("distributed", 1.0, 0.0, {}),
                        rejected={}, nodes=list(nodes),
                        boundary=list(boundary))

    gathers = {"n": 0}
    orig_gather = ShardedTable.gather

    def counting_gather(self):
        gathers["n"] += 1
        return orig_gather(self)

    monkeypatch.setattr(ShardedTable, "gather", counting_gather)
    ctx = get_context()
    decisions = [dec([filt], [scan, filt]), dec([srt], [srt], boundary=[filt])]
    results, names = execute_segments(decisions, ctx,
                                      final_root_ids={srt.id})
    assert names == "distributed"
    # the boundary payload crossed as a ShardedTable: exactly one gather —
    # the final root materialization — and the trace records the payload type
    assert gathers["n"] == 1
    assert any("payload=ShardedTable" in line and "device-resident" in line
               for line in ctx.planner_trace), ctx.planner_trace
    full = {k: np.asarray(v) for k, v in src._arrays.items()}
    ref = X.apply_sort({k: v[full["f"] > 25.0] for k, v in full.items()},
                       ["k", "val"])
    _assert_tables_equal(results[srt.id], ref)


def test_handoff_sharded_payload_usable_by_every_backend(rng):
    """A ShardedTable handoff payload is consumed in place by distributed
    and gathered defensively by host engines."""
    from repro.core.backends import get_backend
    probe = _probe_arrays(rng, 200)
    t = X.shard_host_table(probe, _mesh(), "data")
    ctx = get_context()
    for kind in (BackendEngines.EAGER, BackendEngines.STREAMING,
                 BackendEngines.DISTRIBUTED):
        h = G.Handoff(t, ("sharded-handoff-test",), producer="filter")
        f = G.Filter(h, E.BinOp("ge", E.Col("zone"), E.Lit(6)))
        res = get_backend(kind).execute([f], ctx)[f.id]
        assert isinstance(res, dict), kind
        ref = {k: v[probe["zone"] >= 6] for k, v in probe.items()}
        _assert_tables_equal(res, ref)


# ---------------------------------------------------------------------------
# Stats-store persistence (satellite)


def test_stats_store_roundtrips_through_json(tmp_path):
    from repro.core.planner.feedback import MIN_RUNTIME_SAMPLES, StatsStore
    store = StatsStore()
    store.record(("scan", ("npz", "/data/taxi"), None), 1234, 99_000)
    for _ in range(MIN_RUNTIME_SAMPLES):
        store.record_runtime("eager", 1e5, 0.2)
        store.record_peak("streaming", 5_000_000, est_peak=10_000_000)
    path = str(tmp_path / "stats.json")
    store.save(path)
    fresh = StatsStore()
    assert fresh.load(path)
    assert fresh.lookup(("scan", ("npz", "/data/taxi"), None))["rows"] == 1234
    assert fresh.cost_scale("eager") == pytest.approx(2e-6)
    assert fresh.peak_scale("streaming") == pytest.approx(0.5)
    assert fresh.backend_peaks["streaming"] == 5_000_000


def test_session_stats_path_persists_calibration_across_sessions(tmp_path):
    from repro.core.context import session
    from repro.core.planner.feedback import MIN_RUNTIME_SAMPLES
    path = str(tmp_path / "cal.json")
    src_arrays = {"x": np.arange(500, dtype=np.int64)}
    with session(engine="eager", stats_path=path) as ctx:
        for _ in range(MIN_RUNTIME_SAMPLES):
            ctx.stats_store.record_runtime("streaming", 1e4, 0.05)
        df = core.from_arrays(dict(src_arrays), partition_rows=128)
        df[df["x"] > 100].compute()      # any execute saves the store
    assert os.path.exists(path)
    with session(engine="eager", stats_path=path) as ctx2:
        # reloaded on startup: calibration survives the "restart"
        assert ctx2.stats_store.cost_scale("streaming") == pytest.approx(5e-6)
        assert len(ctx2.stats_store) >= 1   # cardinalities reloaded too


def test_stats_cache_dir_env_enables_context_persistence(tmp_path, monkeypatch):
    from repro.core.context import LaFPContext
    monkeypatch.setenv("REPRO_STATS_CACHE_DIR", str(tmp_path))
    ctx = LaFPContext(name="envtest")
    assert ctx.stats_path == str(tmp_path / "envtest.json")
    ctx.stats_store.record_runtime("eager", 1.0, 1.0)
    ctx.stats_store.save(ctx.stats_path)
    ctx2 = LaFPContext(name="envtest")
    assert ctx2.stats_store.runtime_samples["eager"]


# ---------------------------------------------------------------------------
# Peak calibration (satellite): observed peaks recalibrate estimates


def test_streaming_runs_record_peak_samples(rng):
    ctx = get_context()
    ctx.backend = BackendEngines.STREAMING
    src = _dist_src(rng, n=5000)
    df = core.read_source(src)
    df[df["f"] > 10.0].compute()
    samples = ctx.stats_store.peak_samples.get("streaming")
    assert samples, "streaming run recorded no (est, observed) peak sample"
    est, obs = samples[-1]
    assert est > 0 and obs > 0


def test_npz_cache_token_tracks_directory_content(tmp_path):
    """Same path + same content → same token (stats feedback survives
    restarts); rewritten content → fresh token (persist cache can never
    serve stale results for structurally-identical plans)."""
    from repro.core.source import NpzDirectorySource, write_npz_source
    p = str(tmp_path / "src")
    t1 = write_npz_source(p, {"x": np.arange(10)}).cache_token()
    assert NpzDirectorySource(p).cache_token() == t1
    t2 = write_npz_source(p, {"x": np.arange(10) * 2}).cache_token()
    assert t2 != t1


def test_peak_samples_record_raw_not_calibrated_estimates(rng):
    """Calibration samples must pair the *pre-scale* model estimate with
    the observed peak — recording the calibrated value would drag the
    regressed scale back toward 1 on every subsequent run."""
    from repro.core.planner.cost import CostEstimate
    from repro.core.planner.select import Decision
    from repro.core.runtime import execute_segments
    src = _dist_src(rng, n=2000)
    scan = G.Scan(src)
    f = G.Filter(scan, E.BinOp("gt", E.Col("f"), E.Lit(10.0)))
    cost = CostEstimate("streaming", 1.0, 2e6, {}, raw_peak_bytes=1e6)
    d = Decision(roots=[f], backend=BackendEngines.STREAMING, cost=cost,
                 rejected={}, nodes=[scan, f])
    ctx = get_context()
    execute_segments([d], ctx, final_root_ids={f.id})
    est, obs = ctx.stats_store.peak_samples["streaming"][-1]
    assert est == 1e6      # the raw estimate, not the calibrated 2e6
    assert obs > 0


def test_distributed_rowwise_fallback_is_traced(rng):
    """A native row-wise path failure falls back AND records why."""
    import repro.core.expr as E2

    def host_udf(a):
        return np.asarray(a) + 1.0     # forces __array__ on the tracer

    src = _dist_src(rng, 500)
    scan = G.Scan(src)
    a = G.Assign(scan, "g", E2.UDF(host_udf, (E2.Col("f"),)))
    ctx = get_context()
    res = DistributedBackend().execute([a], ctx)[a.id]
    assert X.table_rows(res) == 500
    assert any("native path failed" in line and "assign" in line
               for line in ctx.planner_trace), ctx.planner_trace


def test_peak_scale_recalibrates_budget_feasibility(rng):
    """A measured observed/estimated peak ratio ≫ 1 makes the planner
    distrust an engine's optimistic peak estimate: a candidate whose raw
    estimate fits the budget is rejected once calibration scales it over."""
    from repro.core.planner.feedback import MIN_PEAK_SAMPLES
    from repro.core.planner.select import plan_placement
    ctx = get_context()
    ctx.backend = BackendEngines.AUTO
    src = _dist_src(rng, n=20_000)
    scan = G.Scan(src)
    f = G.Filter(scan, E.BinOp("gt", E.Col("f"), E.Lit(10.0)))
    base = plan_placement([f], ctx)
    raw_peaks = {d.cost.backend: d.cost.peak_bytes for d in base}
    # every engine's real peak is measured at 100× its estimate
    for name in ("eager", "streaming", "distributed"):
        for _ in range(MIN_PEAK_SAMPLES):
            ctx.stats_store.record_peak(name, int(1e12), est_peak=1e10)
    decisions = plan_placement([f], ctx)
    for d in decisions:
        assert d.cost.peak_bytes == pytest.approx(
            raw_peaks[d.cost.backend] * 100.0, rel=1e-6)
    assert any(line.startswith("auto: peak-calibration")
               for line in ctx.planner_trace)
