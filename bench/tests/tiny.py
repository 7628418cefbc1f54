"""A copy of the benchmark cut to a size the CPU runs in seconds."""
import json
import shutil
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
TINY_ROWS = 1000     # a cell's tables cut by this factor on the CPU


def shrink(root: Path) -> None:
    """Cut every configuration under ``root`` to a size the CPU runs in
    seconds: big tables by ``TINY_ROWS``, dimension tables kept whole."""
    for f in (root / "bench" / "configs").glob("*.json"):
        cfg = json.loads(f.read_text())

        def cut(n):
            return n if n <= 10_000 else n // TINY_ROWS

        cfg["datasets"] = {d: {t: cut(n) for t, n in rows.items()}
                           for d, rows in cfg["datasets"].items()}
        cfg["partition_rows"] = {t: max(cut(n), 1) for t, n
                                 in cfg["partition_rows"].items()}
        f.write_text(json.dumps(cfg))


def copy_tiny(dst: Path) -> Path:
    """A copy of the benchmark (``BENCHMARK.json`` and ``bench/``) at a
    tiny size under ``dst``."""
    shutil.copytree(REPO / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", dst)
    shrink(dst)
    return dst


def run_tiny(root: Path, workload: str, seed: int = 2**31 + 11,
             seconds: float = 0.2, traced: bool = False) -> dict:
    """One run of a cell on the CPU, past the look for a chip."""
    import jax
    from bench import cell
    from bench.registry import Registry
    return cell.run(Registry(root), workload, seed, seconds, traced,
                    jax.devices()[:1], time.perf_counter())
