"""Blocking reads of a device value the host needs to go on (a compacted
row count, the number of uniques of a factorization, int-sum totals): the
engine's ``sync`` spans per program of the window."""
from bench.engine_spans import per_program


def read(run):
    return per_program(run, lambda s: s.name == "sync")
