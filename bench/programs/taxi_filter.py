"""Mean tip of long, dear trips, and how many there are: a conjunction
pushed into the scan, a scalar mean and a count, computed together."""
from __future__ import annotations

import numpy as np

import repro.core as core
from repro.core.func import len as lazy_len

TABLES = ("taxi",)


def run(S):
    df = core.read_source(S["taxi"])
    df = df[(df["trip_distance"] > 10.0) & (df["fare_amount"] > 30.0)]
    mean, trips = df["tip_amount"].mean(), lazy_len(df)
    return tuple(core.execute([mean.node, trips.node]))


def reference(t, p):
    t = t["taxi"]
    m = (p.host(t["trip_distance"]) > 10.0) & \
        (p.host(t["fare_amount"]) > 30.0)
    tips = p.device(t["tip_amount"][m]).astype(np.float64)
    return p.out(float(np.mean(tips))), int(np.count_nonzero(m))


def check(got, want, tally):
    tally.close("taxi_filter mean", got[0], want[0])
    tally.exact("taxi_filter trips", got[1], want[1])
