"""Device idle time, in percent of the window, while the host was inside
the ``operator`` span of a host operator (``join``, ``top_k``), the spans
nested in it included (``bench.engine_spans``)."""
from bench.engine_spans import split


def read(run):
    found = split(run)
    return found.pct("host_ops") if found else None
