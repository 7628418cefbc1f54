"""Device idle time, in percent of the window, while an ``exchange`` span
(a host shuffle round of the distributed engine) is open on the host,
its nested ``transfer`` spans included (``bench.idle_under``)."""
from bench.idle_under import idle_pct


def read(run):
    return idle_pct(run, lambda placed: [iv for iv in placed
                                         if iv.span.name == "exchange"])
