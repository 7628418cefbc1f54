"""Device idle time, in percent of the window, inside a program's
``bench:<program>/compute`` phase while no engine span was open: the
engine's blind spot (``bench.engine_spans``)."""
from bench.engine_spans import split


def read(run):
    found = split(run)
    return found.pct("untraced") if found else None
