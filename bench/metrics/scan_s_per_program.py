"""Seconds of the scan layer's ``io`` spans (``load_partition``: decode,
pushed-down predicate, projection on the host) per program of the
window."""


def read(run):
    io = [s.duration for s in run.spans if s.name == "io"]
    return sum(io) / len(run.calls) if io else None
