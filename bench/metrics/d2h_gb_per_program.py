"""GB (1e9 bytes) of column data the engine moved from the device to the
host per program of the window: the ``bytes`` of its d2h ``transfer``
spans (a host operator's inputs, results and prints read back)."""
from bench.engine_spans import transfer_gb


def read(run):
    return transfer_gb(run, "d2h")
