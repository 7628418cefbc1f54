"""Seconds of the distributed engine's host shuffle rounds, from their
``exchange`` spans (the pull of the shards, the bucketing on the host,
the push back), per program of the window."""


def read(run):
    rounds = [s.duration for s in run.spans if s.name == "exchange"]
    return sum(rounds) / len(run.calls) if rounds else None
