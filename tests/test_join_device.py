"""The join's device probe (`physical/join.py`): a unique-key join of two
device tables on one integer key runs on the device and gives the host
join's answer, column for column and dtype for dtype, and pandas.merge's
rows; every other join takes the host path.  Also what the probe records:
the ``join.device``/``join.host`` counters, the ``path`` of the ``join``
operator span, one ``sync`` and no d2h copy of the probe side."""
import jax.numpy as jnp
import numpy as np
import pandas
import pytest

from repro.core import get_context
from repro.core import physical as X
from repro.obs import profile


def _build(rng, n, keys, dtype=np.int32):
    return {"k": rng.choice(keys, n, replace=False).astype(dtype),
            "w": rng.uniform(0, 1, n).astype(np.float32),
            "g": rng.integers(0, 9, n).astype(np.int32)}


def _probe(rng, n, lo, hi, dtype=np.int32):
    return {"k": rng.integers(lo, hi, n).astype(dtype),
            "v": rng.uniform(0, 1, n).astype(np.float32),
            "r": np.arange(n, dtype=np.int32)}


def _case_absent_on_both_sides(rng):
    # build keys 0..59 step 2 of 0..79; probe 0..99: misses both ways
    return _probe(rng, 500, 0, 100), _build(rng, 30, np.arange(0, 80, 2))


def _case_negative_and_past_range(rng):
    return (_probe(rng, 500, -300, 300),
            _build(rng, 40, np.arange(-100, 100)))


def _case_empty_probe(rng):
    return _probe(rng, 0, 0, 10), _build(rng, 10, np.arange(20))


def _case_empty_build(rng):
    return _probe(rng, 50, 0, 10), _build(rng, 0, np.arange(20))


def _case_mixed_int_widths(rng):
    return (_probe(rng, 400, -120, 120, np.int8),
            _build(rng, 60, np.arange(-200, 200), np.int16))


def _case_unsigned_and_signed(rng):
    return (_probe(rng, 400, 0, 250, np.uint8),
            _build(rng, 60, np.arange(-100, 300), np.int32))


def _case_overlap_suffixes(rng):
    probe = _probe(rng, 300, 0, 50)
    probe["g"] = rng.integers(100, 200, 300).astype(np.int32)
    probe["w"] = rng.uniform(5, 6, 300).astype(np.float32)
    return probe, _build(rng, 40, np.arange(60))


def _case_probe_order_kept(rng):
    probe = _probe(rng, 300, 0, 40)
    probe["k"] = np.sort(probe["k"])[::-1].copy()      # descending keys
    return probe, _build(rng, 25, np.arange(40))


def _case_narrow_payload(rng):
    build = _build(rng, 40, np.arange(60))
    build.update(b=rng.random(40) < 0.5,
                 i8=rng.integers(-128, 128, 40).astype(np.int8),
                 u16=rng.integers(0, 1 << 16, 40).astype(np.uint16),
                 h=rng.uniform(-4, 4, 40).astype(np.float16))
    return _probe(rng, 300, 0, 70), build


def _case_all_matched(rng):
    build = _build(rng, 50, np.arange(1000, 2000))
    probe = _probe(rng, 600, 0, 1)
    probe["k"] = rng.choice(build["k"], 600)
    return probe, build


def _case_duplicate_build_keys(rng):
    build = _build(rng, 30, np.arange(60))
    build["k"][:10] = build["k"][10:20]
    return _probe(rng, 300, 0, 60), build


def _case_two_keys(rng):
    probe = _probe(rng, 300, 0, 8)
    probe["z"] = rng.integers(0, 3, 300).astype(np.int32)
    build = _build(rng, 24, np.arange(8).repeat(3))
    build["z"] = np.tile(np.arange(3, dtype=np.int32), 8)
    build["k"] = np.arange(8, dtype=np.int32).repeat(3)
    return probe, build


def _case_float_key(rng):
    probe, build = _case_absent_on_both_sides(rng)
    probe["k"] = probe["k"].astype(np.float32)
    build["k"] = build["k"].astype(np.float32)
    return probe, build


# (case, key columns, the path a device table takes)
CASES = [
    (_case_absent_on_both_sides, ["k"], "device"),
    (_case_negative_and_past_range, ["k"], "device"),
    (_case_empty_probe, ["k"], "device"),
    (_case_empty_build, ["k"], "device"),
    (_case_mixed_int_widths, ["k"], "device"),
    (_case_unsigned_and_signed, ["k"], "device"),
    (_case_overlap_suffixes, ["k"], "device"),
    (_case_probe_order_kept, ["k"], "device"),
    (_case_narrow_payload, ["k"], "device"),
    (_case_all_matched, ["k"], "device"),
    (_case_duplicate_build_keys, ["k"], "host"),
    (_case_two_keys, ["k", "z"], "host"),
    (_case_float_key, ["k"], "host"),
]


def _on_device(table):
    return {k: jnp.asarray(v) for k, v in table.items()}


def _counted(fn):
    metrics = get_context().metrics
    before = metrics.snapshot()
    out = fn()
    delta = metrics.delta(before, metrics.snapshot())
    return out, {p: delta.get(f"join.{p}", 0) for p in ("device", "host")}


def _pandas_merge(probe, build, on, how):
    """pandas.merge's answer under the engine's names; right int columns of
    unmatched left-join rows read build row 0, as both engine paths do."""
    rf = pandas.DataFrame(build).assign(_row=np.arange(len(build["k"])))
    want = pandas.DataFrame(probe).merge(rf, on=on, how=how,
                                         suffixes=("_x", "_y"))
    unmatched = want["_row"].isna().to_numpy()
    for c, v in build.items():
        if c in on or v.dtype.kind == "f":
            continue
        name = c + "_y" if c in probe else c
        col = np.array(want[name], np.float64)
        col[unmatched] = v[0] if len(v) else 0
        want[name] = col
    return want.drop(columns="_row")


@pytest.mark.parametrize("how", ["inner", "left"])
@pytest.mark.parametrize("case,on,path", CASES,
                         ids=[c[0].__name__[len("_case_"):] for c in CASES])
def test_device_join_matches_host_join_and_pandas(case, on, path, how, rng,
                                                  monkeypatch):
    probe, build = case(rng)
    host, host_paths = _counted(lambda: X.apply_join(probe, build, on, how))
    assert host_paths == {"device": 0, "host": 1}
    if case is _case_all_matched and how == "inner":
        from repro.kernels import ops as K

        def no_compaction(*a, **k):
            raise AssertionError("every row matched: nothing to compact")
        monkeypatch.setattr(K, "filter_compact_columns", no_compaction)
    got, paths = _counted(lambda: X.apply_join(
        _on_device(probe), _on_device(build), on, how))
    assert paths == {"device": int(path == "device"),
                     "host": int(path == "host")}
    # the host join's answer, column for column and dtype for dtype
    assert list(got) == list(host)
    for c in host:
        a, e = np.asarray(got[c]), np.asarray(host[c])
        assert a.dtype == e.dtype, c
        np.testing.assert_array_equal(a, e, err_msg=c)
    # pandas.merge's rows, in the probe's order
    want = _pandas_merge(probe, build, on, how)
    assert sorted(want.columns) == sorted(got)
    for c in want.columns:
        np.testing.assert_array_equal(
            np.asarray(got[c], np.float64), want[c].to_numpy(np.float64),
            err_msg=c)


def _unique_join():
    rng = np.random.default_rng(7)
    return _on_device(_probe(rng, 200, 0, 40)), \
        _on_device(_build(rng, 30, np.arange(40)))


@pytest.mark.parametrize("how", ["inner", "left"])
def test_device_join_records_its_path_one_sync_and_no_probe_copy(how):
    probe, build = _unique_join()
    with profile() as prof:
        X.apply_join(probe, build, ["k"], how)
    (op,) = prof.find("operator")
    assert op.attrs["op"] == "join" and op.attrs["path"] == "device"
    assert prof.counters["join.device"] == 1
    assert "join.host" not in prof.counters
    assert not [s for s in prof.find("transfer")
                if s.attrs["dir"] == "d2h"]
    syncs = prof.find("sync")
    assert len(syncs) <= 1
    assert all(s.attrs["site"] == "join" for s in syncs)


@pytest.mark.parametrize("case,on", [(c, on) for c, on, p in CASES
                                     if p == "host"],
                         ids=["duplicate_build_keys", "two_keys", "float_key"])
def test_host_join_records_its_path(case, on, rng):
    probe, build = case(rng)
    with profile() as prof:
        X.apply_join(_on_device(probe), _on_device(build), on, "inner")
    (op,) = prof.find("operator")
    assert op.attrs["op"] == "join" and op.attrs["path"] == "host"
    assert prof.counters["join.host"] == 1
    assert "join.device" not in prof.counters
    assert {s.attrs["dir"] for s in prof.find("transfer")} == {"d2h", "h2d"}


def test_numpy_tables_join_on_the_host():
    rng = np.random.default_rng(3)
    probe, build = _probe(rng, 100, 0, 20), _build(rng, 20, np.arange(20))
    with profile() as prof:
        X.apply_join(probe, build, ["k"], "inner")
    (op,) = prof.find("operator")
    assert op.attrs["path"] == "host"
    assert prof.counters["join.host"] == 1
    assert not prof.find("transfer") and not prof.find("sync")
