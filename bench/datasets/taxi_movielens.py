"""The LaFP paper's two data sets side by side, for a configuration that
holds both: the NYC TLC yellow taxi records (``taxi``) and MovieLens 25M
(``ratings``, ``movies`` from ``users``), each drawn by its own generator
with a generator of values of its own, split from the one given."""
from __future__ import annotations

import numpy as np

from bench.datasets import movielens, taxi


def build(rows: dict[str, int], rng: np.random.Generator) -> dict[str, dict]:
    taxi_rng, movielens_rng = rng.spawn(2)
    tables = taxi.build({"taxi": rows["taxi"]}, taxi_rng)
    tables.update(movielens.build(
        {t: rows[t] for t in ("ratings", "movies", "users")}, movielens_rng))
    return tables
