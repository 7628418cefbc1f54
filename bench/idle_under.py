"""Device idle time while chosen engine spans are open on the host.

The spans are placed on the trace's clock by ``bench.engine_spans``
(each takes the offset of the call it ran in).  Unlike the idle split
there, which gives each idle piece to the innermost span, a chosen span
here holds every idle piece inside it, whatever is nested in it: the
``transfer`` spans of an exchange, the ``io`` and ``transfer`` spans of a
scan."""
from __future__ import annotations

import bisect

from bench import trace as T
from bench.engine_spans import engine_intervals


def idle_pct(run, pick) -> float | None:
    """Device idle, in percent of the window and averaged over the
    devices, while any span that ``pick`` chooses from the window's placed
    spans (``engine_spans._Interval``) is open; None without a trace, a
    device or a chosen span inside the window."""
    if run.trace is None or not run.trace.devices or not run.spans:
        return None
    placed = engine_intervals(run)
    if placed is None:
        return None
    view = run.trace
    lo, hi = view.window
    held = T._merged([T.Event("", iv.lo, iv.hi, {}) for iv in pick(placed)],
                     lo, hi)
    if not held:
        return None
    starts = [a for a, _ in held]
    idle_ns = 0.0
    for ops in view.devices:
        for a, b in T._gaps(ops, lo, hi):
            i = max(bisect.bisect_right(starts, a) - 1, 0)
            while i < len(held) and held[i][0] < b:
                idle_ns += max(0.0, min(b, held[i][1]) - max(a, held[i][0]))
                i += 1
    return 100.0 * idle_ns / 1e9 / len(view.devices) / view.window_s()
