"""Each user's first rating of 4.5 stars or more: a pushed-down filter and
a distinct on one key over some 160,000 users (a sort-based unique on the
device)."""
from __future__ import annotations

import pandas as pd

import repro.core as core
from bench.check import rows

TABLES = ("ratings",)


def run(S):
    r = core.read_source(S["ratings"])
    r = r[r["rating"] >= 4.5]
    return r.drop_duplicates(subset=("userId",)).compute()


def reference(t, p):
    r = t["ratings"]
    m = p.host(r["rating"]) >= 4.5
    frame = pd.DataFrame({c: p.device(v[m]) for c, v in r.items()})
    want = frame.drop_duplicates(subset=["userId"])
    return {c: want[c].to_numpy() for c in want.columns}


def check(got, want, tally):
    rows(tally, "ratings_first", got, want)
