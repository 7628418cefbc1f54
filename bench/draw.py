"""Fast draws of a large column from a small discrete distribution, shared
by the datasets under ``bench/datasets``."""
from __future__ import annotations

import numpy as np

RESOLUTION = 1 << 22     # shares are held to 1 part in 4 million


def categorical(rng: np.random.Generator, n: int, values, shares
                ) -> np.ndarray:
    """``n`` draws of ``values`` in proportion to ``shares``: one gather
    from a table that holds each value its share of ``RESOLUTION`` times,
    much faster than ``rng.choice`` with ``p`` for tens of millions."""
    shares = np.asarray(shares, np.float64)
    bounds = np.round(np.cumsum(shares) / shares.sum() * RESOLUTION)
    counts = np.diff(bounds, prepend=0).astype(np.int64)
    table = np.repeat(np.asarray(values), counts)
    return table[rng.integers(0, len(table), n)]
