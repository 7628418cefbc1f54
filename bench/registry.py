"""Find each piece of a cell by its name: the workload, metric and
configuration entries in ``<root>/BENCHMARK.json``, and the files under
``<root>/bench`` that they name."""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path


class Registry:
    def __init__(self, root):
        self.root = Path(root)
        self.bench = self.root / "bench"
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def _entry(self, key: str, name: str) -> dict:
        for e in self.spec[key]:
            if e["name"] == name:
                return e
        raise KeyError(f"no {key} entry named {name!r} in BENCHMARK.json; "
                       f"known: {[e['name'] for e in self.spec[key]]}")

    def _json(self, kind: str, name: str) -> dict:
        return json.loads((self.bench / kind / f"{name}.json").read_text())

    def _module(self, kind: str, name: str):
        path = self.bench / kind / f"{name}.py"
        key = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
        mod = sys.modules.get(key)
        if mod is not None and Path(mod.__file__) == path:
            return mod
        spec = importlib.util.spec_from_file_location(key, path)
        if spec is None or not path.is_file():
            raise FileNotFoundError(f"no {kind} module {path}")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
        return mod

    def workload(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        return json.loads((self.root / self._entry("configs", name)["file"])
                          .read_text())

    def mix(self, name: str) -> dict:
        return self._json("mixes", name)

    def limits(self, workload: str) -> dict:
        return self._json("limits", workload)

    def dataset(self, name: str):
        return self._module("datasets", name)

    def program(self, name: str):
        return self._module("programs", name)

    def metric(self, name: str):
        return self._module("metrics", name)

    def metrics_for(self, workload: str, trace: bool) -> list[dict]:
        """The cell's end-to-end metrics, or with ``trace`` its per-layer
        ones: every entry with no ``workloads`` key, and those that list
        the cell."""
        return [m for m in self.spec["per_layer" if trace else "end_to_end"]
                if workload in m.get("workloads", (workload,))]
