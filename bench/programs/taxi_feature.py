"""Mean charge per vendor of the trips above 20 dollars, with their count:
a derived float column, a filter on it (the fused rowwise chain with
device compaction) and a float group-by mean and count."""
from __future__ import annotations

import numpy as np
import pandas as pd

import repro.core as core
from bench.check import grouped

TABLES = ("taxi",)


def run(S):
    df = core.read_source(S["taxi"])
    df["total"] = df["fare_amount"] + df["tip_amount"] + df["tolls_amount"]
    df = df[df["total"] > 20]
    return df.groupby(["VendorID"]).agg(
        {"total": ("total", "mean"), "trips": ("total", "count")}).compute()


def reference(t, p):
    t = t["taxi"]
    d = p.device
    total = d(d(d(t["fare_amount"]) + d(t["tip_amount"]))
              + d(t["tolls_amount"]))
    m = total > 20
    by = pd.Series(total[m].astype(np.float64)).groupby(t["VendorID"][m])
    want = by.mean()
    return {"VendorID": want.index.to_numpy(),
            "total": p.out(want.to_numpy()),
            "trips": by.count().to_numpy()}


def groupby_sums(t):
    """The group-by sums the answer needs: (rows, values, groups, 4-byte
    words written per group and value)."""
    t = t["taxi"]
    f32 = np.float32
    total = (t["fare_amount"].astype(f32) + t["tip_amount"].astype(f32)) \
        + t["tolls_amount"].astype(f32)
    return [(int((total > 20).sum()), 1, 4, 2)]     # a sum and a count


def check(got, want, tally):
    grouped(tally, "taxi_feature", got, want, "VendorID", "total",
            exact=False)
    tally.exact("taxi_feature trips", got["trips"], want["trips"])
