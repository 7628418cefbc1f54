"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (ref.py).
The Pallas kernels run in interpret mode on the CPU and compiled on a
TPU (the mode follows the platform)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.filter_compact import filter_compact
from repro.kernels.groupby_sum import groupby_sum
from repro.kernels.zonemap import zonemap


@pytest.mark.parametrize("n", [17, 256, 1000, 4096])
@pytest.mark.parametrize("g", [1, 7, 100])
@pytest.mark.parametrize("vdim", [0, 1, 5])
def test_groupby_sum_sweep(rng, n, g, vdim):
    codes = rng.integers(0, g, n).astype(np.int32)
    if vdim == 0:
        vals = rng.normal(size=n).astype(np.float32)
    else:
        vals = rng.normal(size=(n, vdim)).astype(np.float32)
    got = groupby_sum(jnp.asarray(codes), jnp.asarray(vals), g,
                      block_rows=1024)
    want = ref.groupby_sum_ref(jnp.asarray(codes), jnp.asarray(vals), g)
    # blocked vs flat accumulation order → f32 rounding differences
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=1e-3)


@pytest.mark.parametrize("dtype", [np.int32, np.int64, np.float32])
def test_groupby_sum_dtypes(rng, dtype):
    codes = rng.integers(0, 9, 500).astype(np.int32)
    vals = rng.integers(0, 100, 500).astype(dtype) if dtype != np.float32 \
        else rng.normal(size=500).astype(dtype)
    got = groupby_sum(jnp.asarray(codes), jnp.asarray(vals), 9)
    want = ref.groupby_sum_ref(jnp.asarray(codes),
                               jnp.asarray(vals).astype(jnp.float32), 9)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-3)


def test_groupby_sum_out_of_range_codes(rng):
    codes = np.array([0, 5, 99, 2, -1, 5], np.int32)   # 99/-1 out of range
    vals = np.ones(6, np.float32)
    got = np.asarray(groupby_sum(jnp.asarray(codes), jnp.asarray(vals), 6))
    assert got.sum() == 4.0          # only in-range rows contribute
    assert got[5] == 2.0


@pytest.mark.parametrize("n", [1, 63, 512, 1537, 8192])
@pytest.mark.parametrize("p", [0.0, 0.3, 1.0])
def test_filter_compact_sweep(rng, n, p):
    vals = rng.normal(size=n).astype(np.float32)
    mask = rng.random(n) < p
    got, cnt = filter_compact(jnp.asarray(vals), jnp.asarray(mask),
                              block_rows=1024)
    want, wcnt = ref.filter_compact_ref(jnp.asarray(vals), jnp.asarray(mask))
    assert int(cnt) == int(wcnt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("n,block", [(100, 64), (4096, 512), (10000, 1024)])
def test_zonemap_sweep(rng, n, block):
    vals = rng.normal(size=n).astype(np.float32)
    mn, mx = zonemap(jnp.asarray(vals), block_rows=block)
    rmn, rmx = ref.zonemap_ref(jnp.asarray(vals), block)
    np.testing.assert_allclose(np.asarray(mn), np.asarray(rmn))
    np.testing.assert_allclose(np.asarray(mx), np.asarray(rmx))


def test_filter_compact_large_whole_column(rng):
    # many grid steps and many row tiles in one call: the row stitch must
    # carry survivors across block boundaries in order
    vals = rng.normal(size=100_000).astype(np.float32)
    mask = rng.random(100_000) < 0.2
    got, cnt = filter_compact(jnp.asarray(vals), jnp.asarray(mask),
                              block_rows=8192)
    assert int(cnt) == int(mask.sum())
    np.testing.assert_array_equal(np.asarray(got)[: int(cnt)], vals[mask])
    assert not np.asarray(got)[int(cnt):].any()


def test_kernel_config_dispatch():
    cfg_x = ops.KernelConfig(impl="xla")
    cfg_p = ops.KernelConfig(impl="pallas")
    codes = jnp.asarray(np.array([0, 1, 1], np.int32))
    vals = jnp.asarray(np.array([1.0, 2.0, 3.0], np.float32))
    x = np.asarray(ops.groupby_sum(codes, vals, 2, cfg_x))
    p = np.asarray(ops.groupby_sum(codes, vals, 2, cfg_p))
    np.testing.assert_allclose(x, p, rtol=1e-6)
    # "auto" picks the Pallas kernels exactly on a TPU host
    from repro.kernels.platform import on_tpu
    assert ops.KernelConfig(impl="auto").resolved() == (
        "pallas" if on_tpu() else "xla")


# ---------------------------------------------------------------------------
# Boundary shapes, differential against ref.py on both impls — the fused
# physical path leans on these exact edges (empty partitions, filters that
# kill every row, row counts that don't fill a block, NaN-bearing columns).

_IMPLS = [ops.KernelConfig(impl="xla"), ops.KernelConfig(impl="pallas")]
_IMPL_IDS = ["xla", "pallas"]


@pytest.mark.parametrize("cfg", _IMPLS, ids=_IMPL_IDS)
def test_filter_compact_empty_input(cfg):
    vals = jnp.zeros((0,), jnp.float32)
    mask = jnp.zeros((0,), bool)
    got, cnt = ops.filter_compact(vals, mask, cfg)
    want, wcnt = ref.filter_compact_ref(vals, mask)
    assert int(cnt) == int(wcnt) == 0
    assert got.shape == want.shape == (0,)


@pytest.mark.parametrize("cfg", _IMPLS, ids=_IMPL_IDS)
@pytest.mark.parametrize("n", [1, 127, 1000])
def test_filter_compact_all_false_mask(rng, cfg, n):
    vals = jnp.asarray(rng.normal(size=n).astype(np.float32))
    mask = jnp.zeros((n,), bool)
    got, cnt = ops.filter_compact(vals, mask, cfg)
    want, wcnt = ref.filter_compact_ref(vals, mask)
    assert int(cnt) == int(wcnt) == 0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("cfg", _IMPLS, ids=_IMPL_IDS)
@pytest.mark.parametrize("n", [1, 65, 129, 1023])   # never a block multiple
def test_filter_compact_non_block_multiple(rng, cfg, n):
    vals = jnp.asarray(rng.normal(size=n).astype(np.float32))
    mask = jnp.asarray(rng.random(n) < 0.5)
    got, cnt = ops.filter_compact(vals, mask, cfg)
    want, wcnt = ref.filter_compact_ref(vals, mask)
    assert int(cnt) == int(wcnt)
    np.testing.assert_allclose(np.asarray(got)[: int(cnt)],
                               np.asarray(want)[: int(wcnt)], rtol=1e-6)


@pytest.mark.parametrize("cfg", _IMPLS, ids=_IMPL_IDS)
def test_filter_compact_nan_values_survive(rng, cfg):
    vals = rng.normal(size=257).astype(np.float32)
    vals[::5] = np.nan
    mask = rng.random(257) < 0.4
    got, cnt = ops.filter_compact(jnp.asarray(vals), jnp.asarray(mask), cfg)
    packed = np.asarray(got)[: int(cnt)]
    expect = vals[mask]
    assert int(cnt) == int(mask.sum())
    np.testing.assert_array_equal(np.isnan(packed), np.isnan(expect))
    np.testing.assert_allclose(packed[~np.isnan(expect)],
                               expect[~np.isnan(expect)], rtol=1e-6)


@pytest.mark.parametrize("cfg", _IMPLS, ids=_IMPL_IDS)
def test_groupby_sum_empty_input(cfg):
    got = ops.groupby_sum(jnp.zeros((0,), jnp.int32),
                          jnp.zeros((0,), jnp.float32), 4, cfg)
    np.testing.assert_array_equal(np.asarray(got), np.zeros(4, np.float32))


@pytest.mark.parametrize("cfg", _IMPLS, ids=_IMPL_IDS)
@pytest.mark.parametrize("n", [1, 130, 999])
def test_groupby_sum_non_block_multiple(rng, cfg, n):
    codes = jnp.asarray(rng.integers(0, 5, n).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=n).astype(np.float32))
    got = ops.groupby_sum(codes, vals, 5, cfg)
    want = ref.groupby_sum_ref(codes, vals, 5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=1e-3)


@pytest.mark.parametrize("cfg", _IMPLS, ids=_IMPL_IDS)
def test_zonemap_empty_input(cfg):
    mn, mx = ops.zonemap(jnp.zeros((0,), jnp.float32), 64, cfg)
    assert mn.shape == mx.shape == (0,)


@pytest.mark.parametrize("cfg", _IMPLS, ids=_IMPL_IDS)
@pytest.mark.parametrize("n", [1, 63, 4097])
def test_zonemap_non_block_multiple(rng, cfg, n):
    vals = jnp.asarray(rng.normal(size=n).astype(np.float32))
    mn, mx = ops.zonemap(vals, 64, cfg)
    rmn, rmx = ref.zonemap_ref(vals, 64)
    np.testing.assert_allclose(np.asarray(mn), np.asarray(rmn))
    np.testing.assert_allclose(np.asarray(mx), np.asarray(rmx))
    # global reduction matches the raw column (partition-skip contract)
    assert np.asarray(mn).min() == np.asarray(vals).min()
    assert np.asarray(mx).max() == np.asarray(vals).max()


# ---------------------------------------------------------------------------
# Int columns above 2^24 (epoch seconds, ids): f32 holds them only to within
# 128, so every kernel must keep ints as ints — values, bounds and sums
# exact, dtype unchanged — on both impls.

_EPOCH_LO, _EPOCH_HI = 1_577_836_800, 1_609_459_200


@pytest.mark.parametrize("cfg", _IMPLS, ids=_IMPL_IDS)
@pytest.mark.parametrize("dtype", [np.int32, np.uint32])
def test_filter_compact_int_above_2_24(rng, cfg, dtype):
    vals = rng.integers(_EPOCH_LO, _EPOCH_HI, 3000).astype(dtype)
    mask = rng.random(3000) < 0.5
    got, cnt = ops.filter_compact(jnp.asarray(vals), jnp.asarray(mask), cfg)
    assert got.dtype == dtype
    assert int(cnt) == int(mask.sum())
    np.testing.assert_array_equal(np.asarray(got)[: int(cnt)], vals[mask])


@pytest.mark.parametrize("cfg", _IMPLS, ids=_IMPL_IDS)
def test_zonemap_int_above_2_24(rng, cfg):
    vals = rng.integers(_EPOCH_LO, _EPOCH_HI, 5000).astype(np.int32)
    mn, mx = ops.zonemap(jnp.asarray(vals), 1024, cfg)
    rmn, rmx = ref.zonemap_ref(jnp.asarray(vals), 1024)
    assert mn.dtype == mx.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(mn), np.asarray(rmn))
    np.testing.assert_array_equal(np.asarray(mx), np.asarray(rmx))
    assert np.asarray(mn).min() == vals.min()
    assert np.asarray(mx).max() == vals.max()


@pytest.mark.parametrize("cfg", _IMPLS, ids=_IMPL_IDS)
@pytest.mark.parametrize("n", [150, 200_000])   # one grid step, four
def test_groupby_sum_words_above_2_31(rng, cfg, n):
    # group totals far outside int32, of both signs: the (hi, lo) words
    # must carry every step's low-word overflow and give them exactly
    codes = rng.integers(0, 3, n).astype(np.int32)
    vals = rng.integers(1 << 30, (1 << 31) - 1, n).astype(np.int32)
    vals[codes == 1] = -vals[codes == 1]
    got = ops.words_to_int64(*ops.groupby_sum_words(
        jnp.asarray(codes), jnp.asarray(vals), 3, cfg))
    want = np.zeros(3, np.int64)
    np.add.at(want, codes, vals.astype(np.int64))
    assert abs(want).min() > 1 << 31
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("cfg", _IMPLS, ids=_IMPL_IDS)
def test_groupby_sum_int_above_2_24(rng, cfg):
    # per-group sums near 2^30: exact only if the kernel sums in int32
    codes = rng.integers(0, 3, 150).astype(np.int32)
    vals = rng.integers(0, 1 << 20, 150).astype(np.int32) + (1 << 24) + 1
    got = ops.groupby_sum(jnp.asarray(codes), jnp.asarray(vals), 3, cfg)
    want = np.bincount(codes, weights=vals.astype(np.float64), minlength=3)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got).astype(np.int64),
                                  want.astype(np.int64))
