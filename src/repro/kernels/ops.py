"""jit'd dispatch wrappers for the Pallas kernels.

``impl`` selects:
* ``"pallas"``   — TPU-target kernels: compiled by Mosaic on a TPU,
                   interpreted on any other host (``platform.py``)
* ``"xla"``      — the pure-jnp reference path (production fallback; also
                   the oracle used in tests)

The engine picks "xla" on CPU hosts and "pallas" on TPU; this mirrors the
paper's backend-capability fallback.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .filter_compact import filter_compact_columns as _compact_pallas
from .groupby_sum import WORD_BITS
from .groupby_sum import groupby_sum as _groupby_sum_pallas
from .groupby_sum import groupby_sum_words as _groupby_sum_words_pallas
from .platform import LANES, on_tpu
from .zonemap import zonemap as _zonemap_pallas

# VMEM the group-by kernel's resident accumulators may take: (V, G, 128)
# 32-bit words, twice that for exact int sums.  A capacity limit (larger
# code domains aggregate through the XLA segment sum), not a speed
# crossover: the kernel's cost grows as G · N and no run has timed either
# side of it.
GROUPBY_VMEM_BYTES = 4 << 20


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    impl: str = "auto"          # auto | pallas | xla

    def resolved(self) -> str:
        if self.impl != "auto":
            return self.impl
        return "pallas" if on_tpu() else "xla"


_CONFIG = KernelConfig()


def set_kernel_config(cfg: KernelConfig):
    global _CONFIG
    _CONFIG = cfg


def get_kernel_config() -> KernelConfig:
    return _CONFIG


def _use_groupby_kernel(cfg, values, num_groups: int, words: int) -> bool:
    width = 1 if values.ndim == 1 else values.shape[1]
    return ((cfg or _CONFIG).resolved() == "pallas" and
            words * width * max(num_groups, 1) * LANES * 4
            <= GROUPBY_VMEM_BYTES)


def groupby_sum(codes, values, num_groups: int, cfg: KernelConfig | None = None):
    """f32 sums of floats; int32 sums of ints and bools (wrapping past
    2^31: use ``groupby_sum_words`` where a total may not fit)."""
    if _use_groupby_kernel(cfg, values, num_groups,
                           1 if values.dtype.kind == "f" else 2):
        return _groupby_sum_pallas(codes, values, num_groups)
    return ref.groupby_sum_ref(codes, values, num_groups)


def groupby_sum_words(codes, values, num_groups: int,
                      cfg: KernelConfig | None = None):
    """Exact sums of int or bool values as int32 words ``(hi, lo)``;
    ``words_to_int64`` gives the totals."""
    if _use_groupby_kernel(cfg, values, num_groups, 2):
        return _groupby_sum_words_pallas(codes, values, num_groups)
    return ref.groupby_sum_words_ref(codes, values, num_groups)


def words_to_int64(hi, lo) -> np.ndarray:
    """Host int64 totals of ``groupby_sum_words`` words (``lo`` may exceed
    its 16 bits after words are added across shards)."""
    return (np.asarray(hi).astype(np.int64) << WORD_BITS) + np.asarray(lo)


def filter_compact(values, mask, cfg: KernelConfig | None = None):
    (out,), count = filter_compact_columns((values,), mask, cfg)
    return out, count


def filter_compact_columns(columns, mask, cfg: KernelConfig | None = None):
    """Stable compaction of each (N,) column under one mask → (packed
    columns, count); slots ≥ count are zeroed."""
    cfg = cfg or _CONFIG
    if cfg.resolved() == "pallas":
        return _compact_pallas(columns, mask)
    return _compact_xla(tuple(columns), mask)


@jax.jit
def _compact_xla(columns, mask):
    return (tuple(ref.filter_compact_ref(v, mask)[0] for v in columns),
            ref.filter_count_ref(mask))


def zonemap(values, block_rows: int = 4096, cfg: KernelConfig | None = None):
    cfg = cfg or _CONFIG
    if values.shape[0] == 0:
        # unified empty contract: no rows → no blocks (the Pallas kernel
        # would otherwise emit one identity-padded block)
        return (jnp.zeros((0,), values.dtype), jnp.zeros((0,), values.dtype))
    if cfg.resolved() == "pallas":
        return _zonemap_pallas(values, block_rows=block_rows)
    return ref.zonemap_ref(values, block_rows)
