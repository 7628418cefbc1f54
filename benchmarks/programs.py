"""Benchmark programs — analogues of the paper's 10 real workloads
(taxi / movie-ratings / startup analyses; filter, feature-add, aggregation,
merge, multi-print, reuse-heavy).  Each program takes the sources dict and
runs plain-Pandas-style code against the LaFP API.

Programs return a value (forcing computation); sizes scale with --scale.
"""
from __future__ import annotations

import numpy as np

import repro.core as core
from repro.core.func import flush, print as lprint


def build_arrays(scale: int, seed: int = 0) -> dict[str, dict[str, np.ndarray]]:
    """The synthetic tables as host column arrays: ``scale`` taxi rows,
    scale//4 ratings, scale//2 startups and 2000 movies, from ``seed``."""
    rng = np.random.default_rng(seed)
    n = scale
    taxi = {
        "fare_amount": rng.uniform(-5, 100, n),
        "passenger_count": rng.integers(0, 7, n).astype(np.int64),
        "pickup_datetime": rng.integers(1_577_836_800, 1_609_459_200, n),
        "trip_miles": rng.uniform(0, 30, n),
        "tip": rng.uniform(0, 20, n),
        "tolls": rng.uniform(0, 10, n),
        "extra1": rng.uniform(0, 1, n),
        "extra2": rng.uniform(0, 1, n),
        "extra3": rng.integers(0, 100, n).astype(np.int64),
        "extra4": rng.uniform(0, 1, n),
        "vendor": rng.integers(0, 4, n).astype(np.int64),
    }
    m = max(scale // 4, 100)
    ratings = {
        "movie_id": rng.integers(0, 2000, m).astype(np.int64),
        "user_id": rng.integers(0, 50_000, m).astype(np.int64),
        "rating": rng.uniform(0.5, 5.0, m),
        "ts": rng.integers(1_000_000_000, 1_600_000_000, m),
        "junk1": rng.uniform(0, 1, m),
        "junk2": rng.uniform(0, 1, m),
    }
    movies = {
        "movie_id": np.arange(2000),
        "year": rng.integers(1950, 2024, 2000).astype(np.int64),
        "genre": rng.integers(0, 12, 2000).astype(np.int64),
    }
    startups = {
        "funding": rng.lognormal(14, 2, max(n // 2, 100)),
        "employees": rng.integers(1, 5000, max(n // 2, 100)).astype(np.int64),
        "sector": rng.integers(0, 20, max(n // 2, 100)).astype(np.int64),
        "founded": rng.integers(1990, 2024, max(n // 2, 100)).astype(np.int64),
        "unused1": rng.uniform(0, 1, max(n // 2, 100)),
        "unused2": rng.uniform(0, 1, max(n // 2, 100)),
    }
    return {"taxi": taxi, "ratings": ratings, "movies": movies,
            "startups": startups}


def build_sources(scale: int, tmpdir: str | None = None, seed: int = 0,
                  arrays: dict | None = None):
    """Synthetic datasets sized ``scale`` rows (taxi) and scale//4 (movies),
    written as partitioned npz when tmpdir is given (out-of-core path).
    ``arrays`` (from ``build_arrays(scale, seed)``) skips regenerating."""
    arrays = arrays or build_arrays(scale, seed)
    taxi, ratings = arrays["taxi"], arrays["ratings"]
    movies, startups = arrays["movies"], arrays["startups"]
    part = max(scale // 16, 1024)
    if tmpdir is not None:
        from repro.core.source import write_npz_source
        return {
            "taxi": write_npz_source(f"{tmpdir}/taxi", taxi, part),
            "ratings": write_npz_source(f"{tmpdir}/ratings", ratings, part),
            "movies": write_npz_source(f"{tmpdir}/movies", movies, 2000),
            "startups": write_npz_source(f"{tmpdir}/startups", startups, part),
        }
    return {
        "taxi": core.InMemorySource(taxi, part, name="taxi"),
        "ratings": core.InMemorySource(ratings, part, name="ratings"),
        "movies": core.InMemorySource(movies, 2000, name="movies"),
        "startups": core.InMemorySource(startups, part, name="startups"),
    }


# --- the 10 programs -------------------------------------------------------

def prog_taxi_agg(S):
    df = core.read_source(S["taxi"])
    df = df[df["fare_amount"] > 0]
    df["day"] = (df["pickup_datetime"] // 86400 + 3) % 7
    return df.groupby(["day"])["passenger_count"].sum().compute()


def prog_taxi_feature(S):
    df = core.read_source(S["taxi"])
    df["total"] = df["fare_amount"] + df["tip"] + df["tolls"]
    df = df[df["total"] > 20]
    return df.groupby(["vendor"])["total"].mean().compute()


def prog_taxi_filter_only(S):
    df = core.read_source(S["taxi"])
    df = df[(df["trip_miles"] > 10.0) & (df["fare_amount"] > 30.0)]
    return df["tip"].mean().compute()


def prog_ratings_join(S):
    r = core.read_source(S["ratings"])
    m = core.read_source(S["movies"])
    j = r.merge(m, on="movie_id")
    j = j[j["year"] >= 2000]
    return j.groupby(["genre"])["rating"].mean().compute()


def prog_ratings_top(S):
    r = core.read_source(S["ratings"])
    g = r.groupby(["movie_id"])["rating"].mean()
    return g.sort_values("rating", ascending=False).head(10).compute()


def prog_startup_sort(S):
    df = core.read_source(S["startups"])
    df = df[df["funding"] > 1e6]
    return df.sort_values("funding", ascending=False).head(50).compute()


def prog_startup_distinct(S):
    df = core.read_source(S["startups"])
    df = df[df["employees"] > 100]
    return df.drop_duplicates(subset=("sector",)).compute()


def prog_multi_print(S):
    df = core.read_source(S["taxi"])
    lprint("rows loaded")
    df = df[df["fare_amount"] > 0]
    per_day = df.groupby(["vendor"])["trip_miles"].mean()
    lprint(per_day)
    avg = df["fare_amount"].mean()
    lprint(f"avg fare: {avg}")
    flush()
    return True


def _heavy_feature(a):
    """Deliberately expensive elementwise chain — stands in for the paper's
    CSV parse + feature engineering that makes recompute costly."""
    out = np.abs(a) + 1.0
    for _ in range(6):
        out = np.sqrt(np.log1p(out) + 1.0) * 1.7 + np.abs(np.sin(out))
    return out


def prog_reuse_stu(S):
    """The 'stu'-like reuse-heavy program (paper §5.3: 13× from persist).
    The shared subexpression df (filter + heavy feature) is forced three
    times; live_df persists it after the first.

    The projection to the three future-live columns is what the paper's
    LAA-based rewriter inserts (without it, persisting must conservatively
    keep all 11 columns and costs more than it saves — measured in
    EXPERIMENTS §Paper-validation)."""
    df = core.read_source(S["taxi"])
    df = df[df["fare_amount"] > 0]
    df["total"] = (df["fare_amount"] + df["tip"]).apply(_heavy_feature)
    df = df[["vendor", "passenger_count", "total"]]   # ← LAA rewrite
    a = df.groupby(["vendor"])["total"].mean().compute(live_df=[df])
    b = df.groupby(["passenger_count"])["total"].sum().compute(live_df=[df])
    c = df["total"].mean().compute(live_df=[])
    return (a, b, c)


def prog_wide_projection(S):
    """Uses 2 of 11 columns — column selection's best case (paper Fig. 4)."""
    df = core.read_source(S["taxi"])
    return df.groupby(["vendor"])["fare_amount"].max().compute()


PROGRAMS = {
    "taxi_agg": prog_taxi_agg,
    "taxi_feature": prog_taxi_feature,
    "taxi_filter": prog_taxi_filter_only,
    "ratings_join": prog_ratings_join,
    "ratings_top": prog_ratings_top,
    "startup_sort": prog_startup_sort,
    "startup_distinct": prog_startup_distinct,
    "multi_print": prog_multi_print,
    "reuse_stu": prog_reuse_stu,
    "wide_projection": prog_wide_projection,
}
