"""The engine's spans in a traced run: the bytes its transfers moved, and
the device's idle time split by the engine code the host was running.

The engine's spans (``repro.obs``, read from ``Run.spans``) are timed on
the host's ``time.perf_counter``; the device's operations and the harness's
annotations are on the profiler trace's clock.  Each call of the window is
on both: the harness reads the host clock just before it opens the call's
``bench:<program>`` annotation and just after it closes it.  So each call
gives the offset between the two clocks, to within the microseconds between
reading the clock and opening or closing the annotation, and every span
takes the offset of the call it ran in.  (While profiled, the engine also
writes each span into the trace as a ``repro:<name>`` annotation, which is
what a person reads in a trace viewer; this reduction needs only the
spans.)

Every idle interval of every device inside the window is cut at each
boundary of an engine span or a harness annotation, and each piece goes to
what was open on the host for all of it, in this order:

- ``host_ops``: an ``operator`` span of a host operator (``join`` or
  ``top_k``), whatever is nested in it;
- ``scan``: otherwise an innermost ``io`` span, h2d ``transfer`` or the
  ``scan`` operator's own time (the partitions' concatenation);
- ``engine``: otherwise any other innermost engine span;
- ``untraced``: no engine span, inside a ``bench:<program>/compute`` phase:
  the engine's blind spot;
- ``to_host``: no engine span, inside a ``/to_host`` phase;
- ``harness``: the rest of the window (between phases and calls).

The layers add up to the idle time that ``device_idle_share`` reads.
"""
from __future__ import annotations

import bisect
import dataclasses

from bench import trace as T

HOST_OPS = ("join", "top_k")
LAYERS = ("host_ops", "scan", "engine", "untraced", "to_host", "harness")


def per_program(run, count) -> float | None:
    """``count`` of the engine's ``transfer`` and ``sync`` spans, per
    program of the window; None from an engine that reports neither."""
    spans = [s for s in run.spans if s.name in ("transfer", "sync")]
    return sum(map(count, spans)) / len(run.calls) if spans else None


def transfer_gb(run, direction: str) -> float | None:
    """GB (1e9 bytes) moved in ``direction`` per program of the window."""
    return per_program(run, lambda s: s.attrs["bytes"] / 1e9 if (
        s.name == "transfer" and s.attrs.get("dir") == direction) else 0)


@dataclasses.dataclass
class IdleSplit:
    window_s: float
    layers: dict[str, float]     # idle seconds per layer, over the devices
    holders: dict[str, float]    # idle seconds per "<program>/<holder>"

    def pct(self, layer: str) -> float:
        return 100.0 * self.layers[layer] / self.window_s


@dataclasses.dataclass(frozen=True, eq=False)
class _Interval:
    lo: float
    hi: float
    span: object


def offsets(run) -> list[float] | None:
    """Each call's offset from the host clock (ns) to the trace's clock;
    None where the trace's calls are not the run's calls."""
    marked = run.trace.program_calls()
    if len(marked) != len(run.calls):
        return None
    out = []
    for c, (name, lo, hi) in zip(run.calls, marked):
        if name != c.program:
            return None
        out.append((lo - c.start * 1e9 + hi - c.end * 1e9) / 2)
    return out


def engine_intervals(run) -> list[_Interval] | None:
    """The window's engine spans on the trace's clock."""
    offs = offsets(run)
    if offs is None:
        return None
    starts = [c.start for c in run.calls]
    out = []
    for s in run.spans:
        i = bisect.bisect_right(starts, s.t0) - 1
        if i < 0 or s.t1 is None:
            continue
        out.append(_Interval(s.t0 * 1e9 + offs[i], s.t1 * 1e9 + offs[i], s))
    return out


def holder(span) -> str:
    """A span's kind and what it names: ``operator:join``, ``operator:scan``,
    ``transfer:h2d:scan``, ``sync:factorize``, ``segment:eager``."""
    a = span.attrs
    what = a.get("op") or a.get("site") or a.get("engine")
    if span.name == "transfer":
        what = f"{a.get('dir')}:{what}"
    return f"{span.name}:{what}" if what else span.name


def _engine_layer(open_spans: list) -> str:
    if any(s.name == "operator" and s.attrs.get("op") in HOST_OPS
           for s in open_spans):
        return "host_ops"
    inner = open_spans[-1]
    if (inner.name == "io"
            or (inner.name == "transfer" and inner.attrs.get("dir") == "h2d")
            or (inner.name == "operator" and inner.attrs.get("op") == "scan")):
        return "scan"
    return "engine"


def _timeline(view: T.TraceView, spans: list[_Interval]
              ) -> list[tuple[float, str, str]]:
    """``(start, layer, holder)`` of each stretch of the window in which
    the same engine spans and harness annotations are open; a stretch
    lasts to the next one's start."""
    lo, hi = view.window
    marks = [_Interval(a.start_ns, a.end_ns, a) for a in view.annotations]
    events = []
    for iv in spans + marks:
        a, b = max(iv.lo, lo), min(iv.hi, hi)
        if a < b:
            events += [(a, 1, iv), (b, 0, iv)]
    events.sort(key=lambda e: (e[0], e[1]))
    open_: list[_Interval] = []
    out: list[tuple[float, str, str]] = []
    for k, (t, starts, iv) in enumerate(events):
        if starts:
            open_.append(iv)
        else:
            open_.remove(iv)
        if k + 1 < len(events) and events[k + 1][0] == t:
            continue
        if t >= hi:
            break
        out.append((t, *_label(open_)))
    return out


def _label(open_: list[_Interval]) -> tuple[str, str]:
    """Layer and ``<program>/<holder>`` of what is open on the host."""
    engine = sorted((iv for iv in open_ if not isinstance(iv.span, T.Event)),
                    key=lambda iv: (iv.lo, -iv.hi))
    marks = sorted((iv for iv in open_ if isinstance(iv.span, T.Event)),
                   key=lambda iv: (iv.lo, -iv.hi))
    names = [iv.span.name[len(T.PREFIX):] for iv in marks]
    program = next((n for n in names if n != "window" and "/" not in n),
                   "window")
    if engine:
        spans = [iv.span for iv in engine]
        return _engine_layer(spans), f"{program}/{holder(spans[-1])}"
    phase = names[-1] if names else "outside"
    if phase.endswith("/compute"):
        return "untraced", f"{phase}/untraced"
    if phase.endswith("/to_host"):
        return "to_host", phase
    return "harness", phase


def split(run) -> IdleSplit | None:
    """The window's idle time, averaged over the devices, by layer and by
    holder; None without a trace, a device or engine spans to place."""
    if run.trace is None or not run.trace.devices or not run.spans:
        return None
    spans = engine_intervals(run)
    if spans is None:
        return None
    view = run.trace
    lo, hi = view.window
    line = _timeline(view, spans)
    starts = [t for t, _, _ in line]
    layers = dict.fromkeys(LAYERS, 0.0)
    holders: dict[str, float] = {}
    n = len(view.devices)
    for ops in view.devices:
        for a, b in T._gaps(ops, lo, hi):
            i = max(bisect.bisect_right(starts, a) - 1, 0)
            while i < len(line) and line[i][0] < b:
                end = line[i + 1][0] if i + 1 < len(line) else hi
                s = (min(b, end) - max(a, line[i][0])) / 1e9 / n
                if s > 0:
                    layers[line[i][1]] += s
                    holders[line[i][2]] = holders.get(line[i][2], 0.0) + s
                i += 1
    return IdleSplit(view.window_s(), layers, holders)

