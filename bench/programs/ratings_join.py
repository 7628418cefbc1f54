"""Mean rating and count per genre list of the comedies: a join of the
ratings with the movie table (on the host today), a filter on the joined
genres (a predicate over the categorical's vocabulary) and a group-by on
that categorical."""
from __future__ import annotations

import numpy as np
import pandas as pd

import repro.core as core
from bench.check import grouped

TABLES = ("ratings", "movies")


def run(S):
    r = core.read_source(S["ratings"])
    m = core.read_source(S["movies"])
    j = r.merge(m, on="movieId")
    j = j[j["genres"].str.contains("Comedy")]
    return j.groupby(["genres"]).agg(
        {"rating": ("rating", "mean"),
         "ratings": ("rating", "count")}).compute()


def _joined(t, p):
    r = pd.DataFrame({"movieId": t["ratings"]["movieId"],
                      "rating": p.device(t["ratings"]["rating"])})
    j = r.merge(pd.DataFrame({"movieId": t["movies"]["movieId"],
                              "genres": t["movies"]["genres"]}),
                on="movieId")
    return j[j.genres.astype(str).str.contains("Comedy", regex=False)]


def reference(t, p):
    j = _joined(t, p)
    by = j.rating.astype(np.float64).groupby(
        j.genres.astype(str).to_numpy())
    want = by.mean()
    return {"genres": want.index.to_numpy(),
            "rating": p.out(want.to_numpy()),
            "ratings": by.count().to_numpy()}


def groupby_sums(t):
    """The group-by sums the answer needs: (rows, values, groups, 4-byte
    words written per group and value)."""
    comedies = np.asarray(t["movies"]["genres"].astype(str))
    ids = t["movies"]["movieId"][np.char.find(comedies.astype(str),
                                              "Comedy") >= 0]
    rows = int(np.isin(t["ratings"]["movieId"], ids).sum())
    groups = len({g for g in comedies if "Comedy" in g})
    return [(rows, 1, groups, 2)]     # the mean: a sum and a count


def check(got, want, tally):
    order = np.argsort(np.asarray(got["genres"], dtype=str), kind="stable")
    got = {k: np.asarray(v)[order] for k, v in got.items()}
    tally.exact("ratings_join genres", np.asarray(got["genres"], dtype=str),
                np.asarray(want["genres"], dtype=str))
    if tally.first_fault and "ratings_join genres" in tally.first_fault:
        return
    tally.close("ratings_join", got["rating"], want["rating"])
    tally.exact("ratings_join ratings", got["ratings"], want["ratings"])
