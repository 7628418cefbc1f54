"""GB (1e9 bytes) the engine pulled from the device per program of the
window at the ``gather`` site: the ``bytes`` of d2h ``transfer`` spans of
a sharded table gathered whole to the host (a fallback's input, a
result).  An exchange's pull is not a gather."""
from bench.engine_spans import per_program


def read(run):
    return per_program(run, lambda s: s.attrs["bytes"] / 1e9 if (
        s.name == "transfer" and s.attrs.get("dir") == "d2h"
        and s.attrs.get("site") == "gather") else 0)
