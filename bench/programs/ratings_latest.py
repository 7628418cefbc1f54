"""The 50 latest five-star ratings: a pushed-down filter, then a sort and
head that the planner rewrites to a TopK (on the host today)."""
from __future__ import annotations

import pandas as pd

import repro.core as core
from bench.check import top_rows

TABLES = ("ratings",)


def run(S):
    r = core.read_source(S["ratings"])
    r = r[r["rating"] == 5.0]
    return r.sort_values("timestamp", ascending=False).head(50).compute()


def reference(t, p):
    r = t["ratings"]
    m = p.host(r["rating"]) == 5.0
    return pd.DataFrame({c: p.device(v[m]) for c, v in r.items()})


def check(got, want, tally):
    top_rows(tally, "ratings_latest", got, want, "timestamp", 50)
