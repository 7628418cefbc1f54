"""Compile the main path's kernels for a described (not attached) TPU v5e
chip at the sizes the chip smoke check runs: 2e7-row group-by sums and
compactions (the paper's 1.4 GB taxi set), zone maps of its 1.25M-row
partitions, and the eager fused-chain body of the ``taxi_agg`` program.

A compile here raises what the TPU's compiler would raise — misaligned
blocks, unsupported Mosaic primitives, a program that does not fit HBM —
at no chip time.  Nothing runs, so nothing here says anything about results
or speed.  The topology is described inside a fixture, never at import: one
process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.filter_compact import filter_compact, filter_compact_columns
from repro.kernels.groupby_sum import groupby_sum
from repro.kernels.zonemap import zonemap

ROWS = 20_000_000                 # taxi rows of the paper's 1.4 GB set
PARTITION_ROWS = ROWS // 16       # its zone-map partitions
HBM_BYTES = 16 * 10**9            # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)`` → an abstract array on one described chip."""
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


@pytest.fixture(autouse=True)
def as_on_tpu(monkeypatch):
    # the kernels pick Mosaic lowering (not the interpreter) on a TPU host;
    # this process has none, so tell them they are on one
    monkeypatch.setattr("repro.kernels.platform.on_tpu", lambda: True)


@pytest.fixture(autouse=True, scope="module")
def no_compile_cache():
    # described-chip executables cannot be read back without a chip
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used
    return compiled


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_groupby_sum_compiles_at_taxi_scale(spec, dtype):
    c = _compile(lambda codes, v: groupby_sum(codes, v, 7),
                 spec((ROWS,), jnp.int32), spec((ROWS,), dtype))
    assert _has_kernel(c)


def test_groupby_sum_compiles_for_ratings_movies(spec):
    # ratings_top: 2000 movie groups over the 5e6-row ratings table
    c = _compile(lambda codes, v: groupby_sum(codes, v, 2000),
                 spec((ROWS // 4,), jnp.int32), spec((ROWS // 4,), jnp.float32))
    assert _has_kernel(c)


@pytest.mark.parametrize("dtype,groups", [(jnp.float32, 8192),
                                          (jnp.int32, 4096)])
def test_groupby_sum_kernel_up_to_its_vmem_bound(spec, dtype, groups):
    # the largest code domain whose accumulators fit GROUPBY_VMEM_BYTES
    # (ints take two words) still compiles as the kernel; one group more
    # goes to XLA's segment sum
    from repro.kernels import ops
    cfg = ops.KernelConfig(impl="pallas")
    args = spec((ROWS,), jnp.int32), spec((ROWS,), dtype)
    for g, kernel in ((groups, True), (groups + 1, False)):
        c = _compile(lambda codes, v, g=g: ops.groupby_sum(codes, v, g, cfg),
                     *args)
        assert _has_kernel(c) is kernel, g


def test_filter_compact_compiles_whole_column(spec):
    c = _compile(filter_compact, spec((ROWS,), jnp.float32),
                 spec((ROWS,), jnp.bool_))
    assert _has_kernel(c)


def test_filter_compact_compiles_a_table(spec):
    # taxi_feature's two surviving columns under one mask
    c = _compile(lambda m, a, b: filter_compact_columns((a, b), m),
                 spec((ROWS,), jnp.bool_), spec((ROWS,), jnp.float32),
                 spec((ROWS,), jnp.int32))
    assert _has_kernel(c)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_zonemap_compiles_per_partition(spec, dtype):
    c = _compile(zonemap, spec((PARTITION_ROWS,), dtype))
    assert _has_kernel(c)


def test_taxi_agg_fused_chain_compiles(spec):
    import repro.core as core
    from repro.core.physical.rowwise import _fused_jax_fn
    from repro.kernels.ops import KernelConfig

    # taxi_agg's rowwise chain, built through the user API
    src = core.InMemorySource({
        "fare_amount": np.zeros(8, np.float32),
        "pickup_datetime": np.zeros(8, np.int32),
        "passenger_count": np.zeros(8, np.int32)}, 8)
    df = core.read_source(src)
    df = df[df["fare_amount"] > 0]
    df["day"] = (df["pickup_datetime"] // 86400 + 3) % 7
    assign = df._node
    chain = (assign.inputs[0], assign)                  # filter, assign
    body = _fused_jax_fn(chain, KernelConfig(impl="pallas"))
    cols = {"fare_amount": spec((ROWS,), jnp.float32),
            "pickup_datetime": spec((ROWS,), jnp.int32),
            "passenger_count": spec((ROWS,), jnp.int32)}
    compiled = body.lower(cols).compile()
    out_cols, mask = compiled.out_info
    assert out_cols["day"].shape == mask.shape == (ROWS,)
