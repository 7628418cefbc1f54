"""Each program of every mix agrees with its plain reference on the CPU at
a small size, and its bfloat16 control does not."""
import json

import pytest

from bench import cell
from bench.check import BFLOAT16, REFERENCE, Tally
from bench.registry import Registry

from .tiny import REPO, copy_tiny

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
PROGRAMS = {p: w["name"] for w in SPEC["workloads"]
            for p in json.loads((REPO / "bench" / "mixes" /
                                 f"{w['traffic']}.json").read_text())
            ["programs"]}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    reg = Registry(copy_tiny(tmp_path_factory.mktemp("tiny")))
    built = {}

    def tables(workload):
        if workload not in built:
            cfg = reg.config(reg.workload(workload)["config"])
            tables = cell.build_tables(reg, cfg, 2**31 + 5)
            built[workload] = (cfg, tables, cell.make_sources(tables, cfg))
        return built[workload]
    return reg, tables


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_program_matches_its_reference(tiny, name):
    reg, tables = tiny
    workload = PROGRAMS[name]
    cfg, host, sources = tables(workload)
    program = reg.program(name)
    got = cell.call(program, name, sources, cfg["engine"], 0, None)
    assert got.error is None, got.error
    tally = Tally()
    program.check(got.result, program.reference(host, REFERENCE), tally)
    limits = reg.limits(workload)
    assert tally.compared and tally.first_fault is None
    assert tally.mismatches == 0 and tally.rel_err <= limits["rel_err"]


@pytest.mark.parametrize("workload", sorted(set(PROGRAMS.values())))
def test_the_control_fails_the_cell(tiny, workload):
    """The reference in bfloat16, in the engine's place, fails the cell's
    limits: by the worst relative error and by exact mismatches."""
    reg, tables = tiny
    _, host, _ = tables(workload)
    tally = Tally()
    for name in reg.mix(reg.workload(workload)["traffic"])["programs"]:
        program = reg.program(name)
        program.check(program.reference(host, BFLOAT16),
                      program.reference(host, REFERENCE), tally)
    limits = reg.limits(workload)
    assert tally.rel_err > 3 * limits["rel_err"]
    assert tally.mismatches > limits["mismatches"]
