"""Each program of the one-chip cells agrees with its plain reference on
the CPU at a small size, run on its own cell's engine, and each one-chip
cell's bfloat16 control fails it (``test_programs`` checks each program
on the last cell whose mix lists it, and the control of that cell)."""
import json

import pytest

from bench import cell
from bench.check import BFLOAT16, REFERENCE, Tally

from .test_programs import tiny  # noqa: F401 — the module's fixture
from .tiny import REPO

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
ONE_CHIP = [w for w in SPEC["workloads"] if w["chips"] == 1]
CASES = [(w["name"], p) for w in ONE_CHIP
         for p in json.loads((REPO / "bench" / "mixes" /
                              f"{w['traffic']}.json").read_text())["programs"]]


@pytest.mark.parametrize("workload, name", CASES)
def test_program_matches_its_reference_on_its_cells_engine(
        tiny, workload, name):  # noqa: F811
    reg, tables = tiny
    cfg, host, sources = tables(workload)
    program = reg.program(name)
    got = cell.call(program, name, sources, cfg["engine"], 0, None)
    assert got.error is None, got.error
    tally = Tally()
    program.check(got.result, program.reference(host, REFERENCE), tally)
    limits = reg.limits(workload)
    assert tally.compared and tally.first_fault is None
    assert tally.mismatches == 0 and tally.rel_err <= limits["rel_err"]


@pytest.mark.parametrize("workload", [w["name"] for w in ONE_CHIP])
def test_the_control_fails_the_cell(tiny, workload):  # noqa: F811
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
