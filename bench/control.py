#!/usr/bin/env python3
"""The lower-precision control of a cell: its plain reference computed in
bfloat16 (every float held, computed and returned in bfloat16; sums
accumulate wider, as XLA's reductions of bfloat16 do) put in the engine's
place, compared with the reference by the cell's own checks and limits.

    python3 bench/control.py --workload <cell> --seeds 1,2,3

A sound control comes out not correct: for each seed it prints the numbers
compared beside their limits, and one JSON line
``{"workload", "seeds", "numbers", "correct"}`` at the end.  It runs on the
host alone, at the cell's own sizes.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def control(reg, workload: str, seed: int) -> dict:
    """The control's numbers on one seed."""
    from bench.cell import build_tables
    from bench.check import BFLOAT16, REFERENCE, Tally

    cell = reg.workload(workload)
    cfg = reg.config(cell["config"])
    tables = build_tables(reg, cfg, seed)
    tally = Tally()
    for name in reg.mix(cell["traffic"])["programs"]:
        program = reg.program(name)
        program.check(program.reference(tables, BFLOAT16),
                      program.reference(tables, REFERENCE), tally)
    return tally.numbers()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.registry import Registry
    reg = Registry(ROOT)
    limits = reg.limits(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    numbers, correct = [], []
    for seed in seeds:
        got = control(reg, args.workload, seed)
        ok = all(got[k] <= limits[k] for k in got)
        for k, v in got.items():
            print(f"seed {seed} control {k}: {v!r} limit {limits[k]!r}",
                  flush=True)
        numbers.append(got)
        correct.append(ok)
    print(json.dumps({"workload": args.workload, "seeds": seeds,
                      "numbers": numbers, "correct": correct}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
