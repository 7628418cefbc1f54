"""The nearest-rank 95th percentile of the seconds from a program's call to
its result in host memory, over every program of the window."""
import math


def nearest_rank(values, q: float) -> float:
    ranked = sorted(values)
    return ranked[max(math.ceil(q * len(ranked)), 1) - 1]


def read(run):
    return nearest_rank([c.seconds for c in run.calls], 0.95)
