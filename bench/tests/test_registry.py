"""Every piece of a cell is found by its name, and new cells, mixes and
configurations need new files and entries only."""
import json
import re

import numpy as np
import pytest

from bench.registry import Registry

from .tiny import REPO, run_tiny

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
WORKLOADS = [w["name"] for w in SPEC["workloads"]]
METRICS = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_pieces_found_by_name(workload):
    reg = Registry(REPO)
    cell = reg.workload(workload)
    cfg = reg.config(cell["config"])
    assert cfg["name"] == cell["config"] and cfg["chips"] == cell["chips"]
    assert set(reg.limits(workload)) == {"rel_err", "mismatches", "failed"}
    for name in reg.mix(cell["traffic"])["programs"]:
        program = reg.program(name)
        assert callable(program.run) and callable(program.reference)
        assert callable(program.check) and program.TABLES
    for dataset, rows in cfg["datasets"].items():
        tables = reg.dataset(dataset).build(
            {t: n // 100_000 or n for t, n in rows.items()},
            np.random.default_rng(0))
        assert set(tables) <= set(rows)
        assert set(tables) == set(cfg["partition_rows"])


@pytest.mark.parametrize("metric", METRICS)
def test_metric_reader_found_by_name(metric):
    assert callable(Registry(REPO).metric(metric).read)


def test_benchmark_json_follows_its_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"] and 1 <= SPEC["run_seconds"] <= 51
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in SPEC[k]]
    assert len(names) == len(set(names)) and all(map(NAME.match, names))
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/configs/")
        assert (REPO / c["file"]).is_file()
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layered = set()
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and m["unit"] and m["layer"]
        assert set(m["workloads"]) <= set(WORKLOADS)
        layered |= set(m["workloads"])
    assert layered == set(WORKLOADS)
    for w in SPEC["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200


def test_new_mix_and_config_are_files_alone(tiny_root):
    """A configuration, a mix and their cell, added as files and entries in
    a copy of the benchmark, run with no edit to any code."""
    bench = tiny_root / "bench"
    cfg = json.loads((bench / "configs" / "taxi-1.4gb.json").read_text())
    cfg.update(name="taxi-small", partition_rows={"taxi": 3000})
    assert "value_seed" not in cfg
    (bench / "configs" / "taxi-small.json").write_text(json.dumps(cfg))
    (bench / "mixes" / "two_taxi.json").write_text(
        json.dumps({"programs": ["wide_projection", "taxi_filter"]}))
    (bench / "limits" / "taxi-small.two_taxi.json").write_text(
        json.dumps({"rel_err": 1e-4, "mismatches": 0, "failed": 0}))
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(spec["configs"][0], name="taxi-small",
                                file="bench/configs/taxi-small.json"))
    spec["workloads"].append({"name": "taxi-small.two_taxi",
                              "config": "taxi-small", "traffic": "two_taxi",
                              "chips": 1, "why": "a test"})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))

    line = run_tiny(tiny_root, "taxi-small.two_taxi")
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 2 and line["failed"] == 0
    assert {"rows_per_s", "program_s_p95", "setup_s"} <= set(line["metrics"])
    assert list(line)[-1] == "checks"
