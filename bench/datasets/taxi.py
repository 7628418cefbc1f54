"""NYC TLC yellow taxi trip records, with the 19 fields of the TLC's data
dictionary (nyc.gov, "Data Dictionary - Yellow Taxi Trip Records", 2024),
under their published names and types: codes as int64, amounts and
distances as float64, the two timestamps as ``datetime64[s]`` and the
store-and-forward flag as a categorical of ``N`` and ``Y``.

Each code is drawn from its documented domain.  The shares of the codes
and the amounts' distributions are not published with the dictionary: the
values below are assumed, and the configuration lists them under
``assumed``.  ``total_amount`` is the sum of its parts, as the dictionary
defines it (cash tips are not in it)."""
from __future__ import annotations

import numpy as np
import pandas as pd

from bench.draw import categorical

YEAR_2024 = (np.datetime64("2024-01-01T00:00:00", "s"),
             np.datetime64("2025-01-01T00:00:00", "s"))


def _codes(rng, n, codes, shares):
    return categorical(rng, n, np.asarray(codes, np.int64), shares)


def build(rows: dict[str, int], rng: np.random.Generator) -> dict[str, dict]:
    n = rows["taxi"]
    lo, hi = (t.astype(np.int64) for t in YEAR_2024)
    pickup = rng.integers(lo, hi, n).astype("datetime64[s]")
    distance = np.round(rng.lognormal(0.6, 0.9, n), 2)
    minutes = (2 + 3 * distance) * rng.lognormal(0, 0.3, n)
    dropoff = pickup + (60 * minutes).astype("timedelta64[s]")
    payment = _codes(rng, n, range(7), [3, 78, 16, 1, 1, 0.5, 0.5])
    fare = np.round((3 + 3.5 * distance) * rng.lognormal(0, 0.2, n), 2)
    fare[rng.random(n) < 0.01] *= -1                  # refunds and voids
    extra = _codes(rng, n, [0, 1, 2.5, 5], [55, 25, 15, 5]).astype(np.float64)
    mta_tax = np.where(rng.random(n) < 0.98, 0.5, 0.0)
    tip = np.where(payment == 1,
                   np.round(fare.clip(0) * rng.uniform(0.1, 0.3, n), 2), 0.0)
    tolls = np.where(rng.random(n) < 0.07, 6.94, 0.0)
    improvement = np.full(n, 1.0)
    congestion = np.where(rng.random(n) < 0.9, 2.5, 0.0)
    airport = np.where(rng.random(n) < 0.08, 1.75, 0.0)
    total = np.round(fare + extra + mta_tax + tip + tolls + improvement
                     + congestion + airport, 2)
    flag = pd.Categorical.from_codes(
        (rng.random(n) < 0.005).astype(np.int8), categories=["N", "Y"])
    return {"taxi": {
        "VendorID": _codes(rng, n, [1, 2, 6, 7], [27, 71, 1, 1]),
        "tpep_pickup_datetime": pickup,
        "tpep_dropoff_datetime": dropoff,
        "passenger_count": _codes(rng, n, range(7), [2, 75, 14, 4, 2, 2, 1]),
        "trip_distance": distance,
        "RatecodeID": _codes(rng, n, [1, 2, 3, 4, 5, 6, 99],
                             [93, 4, 0.5, 0.3, 1.2, 0.01, 1]),
        "store_and_fwd_flag": flag,
        "PULocationID": rng.integers(1, 266, n),
        "DOLocationID": rng.integers(1, 266, n),
        "payment_type": payment,
        "fare_amount": fare,
        "extra": extra,
        "mta_tax": mta_tax,
        "tip_amount": tip,
        "tolls_amount": tolls,
        "improvement_surcharge": improvement,
        "total_amount": total,
        "congestion_surcharge": congestion,
        "Airport_fee": airport,
    }}
