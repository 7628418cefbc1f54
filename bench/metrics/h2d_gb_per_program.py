"""GB (1e9 bytes) of column data the engine moved from the host to the
device per program of the window: the ``bytes`` of its h2d ``transfer``
spans (the scan's upload, a host operator's result going back)."""
from bench.engine_spans import transfer_gb


def read(run):
    return transfer_gb(run, "h2d")
