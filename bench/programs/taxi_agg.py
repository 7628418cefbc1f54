"""Passengers per weekday of the paid trips: a pushed-down filter, a
derived key from the pickup time and an int group-by sum (exact past
2^31)."""
from __future__ import annotations

import pandas as pd

import repro.core as core
from bench.check import grouped

TABLES = ("taxi",)


def run(S):
    df = core.read_source(S["taxi"])
    df = df[df["fare_amount"] > 0]
    df["day"] = df["tpep_pickup_datetime"].dt.dayofweek
    return df.groupby(["day"])["passenger_count"].sum().compute()


def reference(t, p):
    t = t["taxi"]
    m = p.host(t["fare_amount"]) > 0
    day = pd.DatetimeIndex(t["tpep_pickup_datetime"][m]).dayofweek
    want = pd.Series(t["passenger_count"][m]).groupby(day).sum()
    return {"day": want.index.to_numpy(),
            "passenger_count": want.to_numpy()}


def groupby_sums(t):
    """The group-by sums the answer needs: (rows, values, groups, 4-byte
    words written per group and value)."""
    rows = int((t["taxi"]["fare_amount"] > 0).sum())
    return [(rows, 1, 7, 2)]          # exact int sums as two words


def check(got, want, tally):
    grouped(tally, "taxi_agg", got, want, "day", "passenger_count",
            exact=True)
