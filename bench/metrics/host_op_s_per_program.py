"""Seconds of the physical operators that run on the host today, from
their ``operator`` spans, per program of the window."""

HOST_OPS = ("join", "top_k")


def read(run):
    ops = [s.duration for s in run.spans
           if s.name == "operator" and s.attrs.get("op") in HOST_OPS]
    return sum(ops) / len(run.calls) if ops else None
