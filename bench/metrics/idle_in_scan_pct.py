"""Device idle time, in percent of the window, while the innermost engine
span on the host was an ``io`` span (partition load), an h2d ``transfer``
or the ``scan`` operator's own time (the partitions' concatenation): the
scan keeping the device waiting (``bench.engine_spans``)."""
from bench.engine_spans import split


def read(run):
    found = split(run)
    return found.pct("scan") if found else None
