"""In-tree execution engines (paper §2.6), registered with the open
engine registry (``repro.core.engines``):

* eager       — whole-table, device-resident jnp (the Pandas analogue)
* streaming   — partition-at-a-time host execution, bounded memory, out-of-
                core (the Dask analogue)
* distributed — shard_map over the mesh data axis (the Modin/cluster
                analogue); unsupported ops fall back to eager, mirroring the
                paper's convert-to-Pandas fallback.

Importing this package registers all three under their string names; the
planner derives its candidate set, capabilities, cost constants, and
calibration namespaces from the registry, so out-of-tree engines added via
``repro.register_engine`` (or the ``repro.engines`` entry-point group) are
planned exactly like these.
"""
from __future__ import annotations

from ..engines import (ALL_OPS as _ALL_OPS, BackendCapability,
                       default_registry, normalize_engine)


class MemoryBudgetExceeded(RuntimeError):
    def __init__(self, needed: int, budget: int, where: str):
        super().__init__(
            f"memory budget exceeded at {where}: needs {needed/1e6:.1f} MB, "
            f"budget {budget/1e6:.1f} MB")
        self.needed = needed
        self.budget = budget


class MemoryMeter:
    """Deterministic memory accounting for the streaming backend — lets the
    benchmark reproduce the paper's OOM behaviour (Fig. 12) without actually
    exhausting RAM."""

    def __init__(self, budget: int | None):
        self.budget = budget
        self.current = 0
        self.peak = 0

    def alloc(self, nbytes: int, where: str = "?"):
        self.current += int(nbytes)
        self.peak = max(self.peak, self.current)
        if self.budget is not None and self.current > self.budget:
            raise MemoryBudgetExceeded(self.current, self.budget, where)

    def free(self, nbytes: int):
        self.current -= int(nbytes)


# ---------------------------------------------------------------------------
# Registration.  The engine classes themselves are the factories — the
# registry filters construction options against their signatures.  (The
# imports sit below MemoryMeter on purpose: streaming imports it back from
# this partially-initialized package.)

from .eager import EagerBackend          # noqa: E402
from .streaming import StreamingBackend  # noqa: E402
from .distributed import DistributedBackend  # noqa: E402


def _device_count() -> int:
    import jax
    return len(jax.devices())


def _broadcast_build_bytes() -> float:
    from ..physical.sharded import BROADCAST_BUILD_BYTES
    return float(BROADCAST_BUILD_BYTES)


_REG = default_registry()

_REG.register("eager", EagerBackend, BackendCapability(
    name="eager", native_ops=_ALL_OPS,
    startup_cost=1e3, scan_cost_per_byte=1.0, row_cost=1.0,
    parallelism=4.0, transfer_cost_per_byte=0.5, fallback_penalty=1.0,
    peak_model="resident", scan_pushdown=True),
    source="builtin", replace=True)

_REG.register("streaming", StreamingBackend, BackendCapability(
    name="streaming", native_ops=_ALL_OPS,
    startup_cost=2e3, scan_cost_per_byte=1.5, row_cost=2.0,
    parallelism=1.0, transfer_cost_per_byte=0.0, fallback_penalty=1.0,
    peak_model="chunked", scan_pushdown=True),
    source="builtin", replace=True)

_REG.register("distributed", DistributedBackend, BackendCapability(
    name="distributed",
    native_ops=frozenset({"scan", "materialized", "filter", "project",
                          "assign", "rename", "astype", "fillna",
                          "fused_rowwise", "reduce", "length",
                          "groupby_agg", "join", "sort_values",
                          "drop_duplicates", "head", "sink_print"}),
    # scan models parallel partition ingest across shard workers (cheaper
    # per byte than eager's single-device load), paid for by the highest
    # fixed startup: distributed only wins once tables are large enough
    # to amortize mesh dispatch.  Runtime calibration corrects both.
    startup_cost=8e4, scan_cost_per_byte=0.6, row_cost=1.0,
    parallelism=8.0, transfer_cost_per_byte=2.0, fallback_penalty=3.0,
    peak_model="sharded",
    broadcast_join_bytes=_broadcast_build_bytes(),
    keeps_device_payloads=True, scan_pushdown=True,
    shard_count=_device_count), source="builtin", replace=True)


# ---------------------------------------------------------------------------
# Back-compat surface.  ``CAPABILITIES`` is the registry's live capability
# dict (string-keyed; ``BackendEngines`` members hash/compare equal to the
# names, so legacy enum-keyed lookups — and test monkeypatching — work
# unchanged).

CAPABILITIES = _REG.capabilities


def capabilities(kind) -> BackendCapability:
    return _REG.capability_of(kind)


def backend_class(kind):
    """Deprecated: engine factory lookup by name (kept for callers that
    expect a constructor)."""
    kind = normalize_engine(kind)
    if kind == "auto":
        _REG.create(kind)           # raises the explanatory ValueError
    return _REG.spec(kind).factory


def get_backend(kind, **options):
    return _REG.create(kind, options)
