"""A whole run on the CPU, past the look for a chip, with the timed path
broken underneath: ``correct`` comes out false for each fault a cell can
have, and true with nothing broken."""
import json

import numpy as np
import pytest

from .tiny import REPO, run_tiny

WORKLOADS = [w["name"] for w in
             json.loads((REPO / "BENCHMARK.json").read_text())["workloads"]]


def half_of_each_partition(monkeypatch):
    """The scan leaves out half of the rows it reads."""
    from repro.core.source import InMemorySource
    load = InMemorySource.load_partition

    def half(self, i, columns=None):
        part = load(self, i, columns)
        return {c: v[: len(v) // 2] for c, v in part.items()}
    monkeypatch.setattr(InMemorySource, "load_partition", half)


def compaction_drops_rows(monkeypatch):
    """The fused rowwise chain's compaction leaves out one surviving row in
    32, about 3 % of them."""
    import jax.numpy as jnp
    from repro.core.physical import rowwise
    fused = rowwise._fused_jax_fn

    def lossy(ops, cfg):
        fn = fused(ops, cfg)

        def run(table):
            cols, mask = fn(table)
            if mask is not None:
                mask = mask & (jnp.arange(mask.shape[0]) % 32 != 0)
            return cols, mask
        return run
    monkeypatch.setattr(rowwise, "_fused_jax_fn", lossy)


def answer_altered(monkeypatch):
    """Each frame the engine produces has one value changed."""
    from repro.core.lazyframe import Result
    init = Result.__init__

    def altered(self, columns, vocab=None):
        init(self, columns, vocab)
        last = list(self.columns)[-1]
        col = np.array(self.columns[last])
        if col.size:
            col[0] += 1
            self.columns[last] = col
    monkeypatch.setattr(Result, "__init__", altered)


# each fault in the cells that can have it: only the taxi mix filters on a
# derived column, which the fused chain compacts
CASES = [(f, w) for f in (None, half_of_each_partition, answer_altered)
         for w in WORKLOADS] + [(compaction_drops_rows,
                                 "taxi-1.4gb.device_agg")]


@pytest.mark.parametrize("fault, workload", CASES)
def test_faults_are_not_correct(tiny_root, monkeypatch, workload, fault):
    if fault is not None:
        fault(monkeypatch)
    line = run_tiny(tiny_root, workload)
    assert line["correct"] is (fault is None), line["checks"]
    assert list(line)[-1] == "checks"
