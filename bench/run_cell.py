#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run_cell.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is a ``workloads`` entry of ``BENCHMARK.json`` beside this
directory.  The run builds its tables from the seed, warms up every program
of its mix (set-up), runs the mix in a closed loop for ``--seconds``, then
checks every result of the window against the plain reference.  The last
line of standard output is one JSON object; with ``--trace 0`` its metrics
are the cell's end-to-end metrics, with ``--trace 1`` its per-layer ones,
read from the window's spans and profiler trace.  The numbers compared,
each beside its limit, close standard error and the result line.

It exits non-zero and prints no result where JAX finds no accelerator or
fewer chips than the cell asks for (there is no CPU fallback), or where the
engine (``src/repro``) is not beside this directory.  JAX's compile cache is
kept where ``JAX_COMPILATION_CACHE_DIR`` says, else at ``<root>/.jax_cache``.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    try:
        from repro.compile_cache import enable_compile_cache
        from bench import cell
        from bench.registry import Registry
    except ImportError as e:
        print(f"run_cell: the engine is not beside the benchmark ({e})",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    import jax
    # every program of a run goes to the cache, however fast it compiles,
    # so that a cell's later runs compile nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    reg = Registry(ROOT)
    try:
        cfg = reg.config(reg.workload(args.workload)["config"])
        devices = cell.accelerators(cfg["chips"])
    except (KeyError, cell.NoAccelerator) as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return 3
    line = cell.run(reg, args.workload, args.seed, args.seconds,
                    bool(args.trace), devices, PROCESS_START)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
