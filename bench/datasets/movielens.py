"""MovieLens 25M (GroupLens, files.grouplens.org/datasets/movielens/
ml-25m-README.html): ``ratings`` (userId, movieId, rating, timestamp) and
``movies`` (movieId, title, genres), under their published names and
types.  Ratings are half stars from 0.5 to 5.0, timestamps are seconds
since the epoch from 1995-01-09 to 2019-11-21, every user has at least 20
ratings, titles end in the release year in parentheses, and genres are a
pipe-separated list of the README's 18 genres, or ``(no genres listed)``.
Titles and genres are categoricals.

The counts (ratings, movies, users) come from the configuration.  The
shares below (how often each rating is given, how popular a movie or
active a user is, how many movies were never rated, release years and
genres a film) are not published in the README: they are assumed, and the
configuration lists them under ``assumed``."""
from __future__ import annotations

import numpy as np
import pandas as pd

from bench.draw import categorical

GENRES = ("Action", "Adventure", "Animation", "Children", "Comedy", "Crime",
          "Documentary", "Drama", "Fantasy", "Film-Noir", "Horror", "Musical",
          "Mystery", "Romance", "Sci-Fi", "Thriller", "War", "Western")
NO_GENRES = "(no genres listed)"
STARS = np.arange(1, 11) / 2                             # 0.5 .. 5.0
STAR_SHARES = np.array([1.6, 3.1, 1.6, 6.6, 5.0, 19.6, 12.6, 26.6, 8.8, 14.5])
FIRST, LAST = 789_609_600, 1_574_294_400                 # 1995-01-09, 2019-11-21
MIN_RATINGS = 20
RATED_SHARE = 0.946                                      # movies rated at all
HEAD, TAIL = 300, 1.3      # popularity (rank + HEAD) ** -TAIL over rated ones


def _movies(rng, n: int) -> dict:
    ids = np.sort(rng.choice(np.arange(1, 209_172), n, replace=False))
    years = np.clip(2020 - rng.exponential(18, n).astype(np.int64), 1874, 2019)
    titles = [f"Movie {i} ({y})" for i, y in zip(ids, years)]
    picks = np.argsort(rng.random((n, len(GENRES))), axis=1)
    kinds = rng.integers(1, 4, n)
    none = rng.random(n) < 0.08
    genres = [NO_GENRES if no else "|".join(sorted(GENRES[g] for g in p[:k]))
              for p, k, no in zip(picks.tolist(), kinds, none)]
    return {"movieId": ids,
            "title": pd.Categorical(titles),
            "genres": pd.Categorical(genres)}


def _ratings(rng, n: int, users: int, movie_ids: np.ndarray) -> dict:
    rated = rng.permutation(movie_ids)[: max(int(RATED_SHARE * len(movie_ids)),
                                              1)]
    popularity = 1.0 / (np.arange(len(rated)) + HEAD) ** TAIL
    activity = rng.lognormal(0, 1.5, users)
    floor = np.repeat(np.arange(1, users + 1), MIN_RATINGS)
    user = np.concatenate([floor, categorical(
        rng, n - len(floor), np.arange(1, users + 1), activity)])
    return {"userId": user,
            "movieId": categorical(rng, n, rated, popularity),
            "rating": categorical(rng, n, STARS, STAR_SHARES),
            "timestamp": rng.integers(FIRST, LAST, n)}


def build(rows: dict[str, int], rng: np.random.Generator) -> dict[str, dict]:
    movies = _movies(rng, rows["movies"])
    return {"movies": movies,
            "ratings": _ratings(rng, rows["ratings"], rows["users"],
                                movies["movieId"])}
