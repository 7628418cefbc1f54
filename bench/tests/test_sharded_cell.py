"""The four-chip cell at a tiny size on four virtual CPU devices: every
answer agrees with the reference, and the traced run holds the sharded
engine's stage, join and exchange spans and its trace events.  The
suite's own process sees one CPU device, so the run is a child process
of its own."""
import json
import os
import subprocess
import sys

from .tiny import REPO

WORKLOAD = "lafp-4.2gb-x4.sharded"

CHILD = """
import json, sys, time
from pathlib import Path

import jax

from bench import cell
from bench.engine_spans import holder
from bench.registry import Registry
from bench.tests.tiny import copy_tiny

records = []
result_line = cell.result_line


def keep(reg, run, *rest):
    records.append(run)
    return result_line(reg, run, *rest)


cell.result_line = keep
root = copy_tiny(Path(sys.argv[1]))
line = cell.run(Registry(root), sys.argv[2], 2**31 + 13, 0.2, True,
                jax.devices(), time.perf_counter())
print(json.dumps({"line": line, "devices": len(jax.devices()),
                  "holders": sorted({holder(s) for s in records[0].spans})}))
"""


def test_sharded_cell_on_four_devices(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(REPO / "src"), str(REPO)]))
    done = subprocess.run([sys.executable, "-c", CHILD, str(tmp_path),
                           WORKLOAD], cwd=tmp_path, env=env,
                          capture_output=True, text=True, timeout=240)
    assert done.returncode == 0, done.stderr[-4000:]
    got = json.loads(done.stdout.strip().splitlines()[-1])
    line = got["line"]
    assert got["devices"] == 4 and line["device"]["count"] == 4
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 9
    holders = set(got["holders"])
    assert {"operator:sharded_groupby", "operator:sharded_join",
            "exchange:distinct"} <= holders
    assert any(h.startswith("trace:") for h in holders)
    metrics = line["metrics"]
    assert metrics["exchange_s_per_program"]["value"] > 0
    assert metrics["stage_traces_per_program"]["value"] > 0
    assert metrics["gather_gb_per_program"]["value"] > 0
