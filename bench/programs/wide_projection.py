"""Largest fare per vendor: 2 of the 19 columns read, a group-by max."""
from __future__ import annotations

import pandas as pd

import repro.core as core
from bench.check import grouped

TABLES = ("taxi",)


def run(S):
    df = core.read_source(S["taxi"])
    return df.groupby(["VendorID"])["fare_amount"].max().compute()


def reference(t, p):
    t = t["taxi"]
    want = pd.Series(p.device(t["fare_amount"])).groupby(
        t["VendorID"]).max()
    return {"VendorID": want.index.to_numpy(),
            "fare_amount": want.to_numpy()}


def check(got, want, tally):
    grouped(tally, "wide_projection", got, want, "VendorID", "fare_amount",
            exact=True)
