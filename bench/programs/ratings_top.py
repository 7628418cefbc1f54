"""The ten best-rated movies among those with at least 1000 ratings: a
group-by mean and count over some 59,000 movies, a filter on the count,
then a sort and head that the planner rewrites to a TopK (on the host
today)."""
from __future__ import annotations

import numpy as np
import pandas as pd

import repro.core as core

TABLES = ("ratings",)
LEAST = 1000


def run(S):
    r = core.read_source(S["ratings"])
    g = r.groupby(["movieId"]).agg(
        {"rating": ("rating", "mean"), "ratings": ("rating", "count")})
    g = g[g["ratings"] >= LEAST]
    return g.sort_values("rating", ascending=False).head(10).compute()


def reference(t, p):
    r = t["ratings"]
    by = pd.Series(p.device(r["rating"]).astype(np.float64)).groupby(
        r["movieId"])
    means, counts = by.mean(), by.count()
    means = pd.Series(p.out(means.to_numpy()), index=means.index)
    means = means[counts >= LEAST]
    top = means.sort_values(ascending=False, kind="stable").head(10)
    return {"movieId": top.index.to_numpy(), "rating": top.to_numpy(),
            "ratings": counts.loc[top.index].to_numpy(),
            "means": means, "counts": counts}


def check(got, want, tally):
    # near-equal means may swap ranks between float32 and float64: the
    # ranked values must match, and so must each returned movie's own
    # mean and count
    tally.close("ratings_top ranked", got["rating"], want["rating"])
    ids = np.asarray(got["movieId"]).astype(np.int64)
    known = np.isin(ids, want["means"].index.to_numpy())
    if not known.all() or len(ids) != len(want["movieId"]):
        tally.mismatches += max(int(np.count_nonzero(~known)), 1)
        tally.fault("ratings_top movie ids", ids, want["movieId"])
        return
    tally.close("ratings_top per movie", got["rating"],
                want["means"].loc[ids].to_numpy())
    tally.exact("ratings_top counts", got["ratings"],
                want["counts"].loc[ids].to_numpy())
