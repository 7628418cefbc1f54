"""Device idle time, in percent of the window, while the distributed
engine's ``scan`` operator span is open on the host, whatever is nested
in it: the partitions' load, their concatenation, the padding to equal
shards and the put onto the mesh (``bench.idle_under``).  A scan is the
distributed engine's when a ``segment`` span of that engine holds it."""
from bench.idle_under import idle_pct


def _sharded_scans(placed):
    segments = [iv for iv in placed if iv.span.name == "segment"
                and iv.span.attrs.get("engine") == "distributed"]
    return [iv for iv in placed
            if iv.span.name == "operator" and iv.span.attrs.get("op") == "scan"
            and any(s.lo <= iv.lo and iv.hi <= s.hi for s in segments)]


def read(run):
    return idle_pct(run, _sharded_scans)
