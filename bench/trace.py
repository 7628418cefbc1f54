"""Reduce a JAX profiler trace to the benchmark's device numbers.

The harness wraps its measured window in an annotation named
``bench:window`` and each program call and its phases in annotations named
``bench:<program>`` and ``bench:<program>/<phase>``
(``jax.profiler.TraceAnnotation``), so they land on the host planes of the
same trace as the device's operations.  From the trace:

- each device's busy time: the union of its operations' intervals inside
  the window; the idle share is one less busy over the window;
- device seconds per operation, named ``<module>/<op>``: the jitted
  program (the ``XLA Modules`` event that holds the operation, without its
  fingerprint) and the HLO instruction;
- the idle gaps, each named by the innermost harness annotation that holds
  its midpoint: what the host was doing while the device waited.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os

PREFIX = "bench:"
WINDOW = PREFIX + "window"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float
    stats: dict

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


@dataclasses.dataclass
class TraceView:
    devices: list[list[Event]]       # each device's operations, by start
    annotations: list[Event]         # the harness's annotations, by start

    @property
    def window(self) -> tuple[float, float]:
        for a in self.annotations:
            if a.name == WINDOW:
                return a.start_ns, a.end_ns
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")

    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) / 1e9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        lo, hi = self.window
        return sum(_union_ns(ops, lo, hi) for ops in self.devices) \
            / len(self.devices) / 1e9

    def op_seconds(self) -> dict[str, float]:
        """Device seconds inside the window per ``<module>/<op>``, averaged
        over the devices."""
        lo, hi = self.window
        out: dict[str, float] = {}
        for ops in self.devices:
            for e in ops:
                s = (min(e.end_ns, hi) - max(e.start_ns, lo)) / 1e9
                if s > 0:
                    key = op_name(e)
                    out[key] = out.get(key, 0.0) + s / len(self.devices)
        return out

    def idle_gaps(self) -> list[tuple[str, float]]:
        """Every idle gap of every device inside the window, longest first,
        as ``(annotation, seconds)``."""
        lo, hi = self.window
        named = []
        for ops in self.devices:
            for a, b in _gaps(ops, lo, hi):
                named.append((self.host_activity((a + b) / 2), (b - a) / 1e9))
        return sorted(named, key=lambda g: -g[1])

    def program_calls(self) -> list[tuple[str, float, float]]:
        """Each program call's ``(program, start_ns, end_ns)``, from the
        harness's ``bench:<program>`` annotations."""
        return [(a.name[len(PREFIX):], a.start_ns, a.end_ns)
                for a in self.annotations
                if a.name != WINDOW and "/" not in a.name]

    def ops_between(self, lo: float, hi: float) -> list[Event]:
        """Every device's operations that start in ``[lo, hi)``."""
        return [e for ops in self.devices for e in ops
                if lo <= e.start_ns < hi]

    def host_activity(self, t_ns: float) -> str:
        """The innermost harness annotation holding ``t_ns``, without the
        prefix."""
        best = None
        for a in self.annotations:
            if a.start_ns <= t_ns <= a.end_ns and (
                    best is None or a.end_ns - a.start_ns
                    < best.end_ns - best.start_ns):
                best = a
        return best.name[len(PREFIX):] if best else "outside the window"


def op_name(e: Event) -> str:
    module = e.stats.get("hlo_module")
    return f"{module}/{e.name}" if module else e.name


def _merged(ops: list[Event], lo: float, hi: float) -> list[list[float]]:
    spans: list[list[float]] = []
    for e in sorted(ops, key=lambda e: e.start_ns):
        a, b = max(e.start_ns, lo), min(e.end_ns, hi)
        if b <= a:
            continue
        if spans and a <= spans[-1][1]:
            spans[-1][1] = max(spans[-1][1], b)
        else:
            spans.append([a, b])
    return spans


def _union_ns(ops: list[Event], lo: float, hi: float) -> float:
    return sum(b - a for a, b in _merged(ops, lo, hi))


def _gaps(ops: list[Event], lo: float, hi: float) -> list[tuple[float, float]]:
    out, t = [], lo
    for a, b in _merged(ops, lo, hi):
        if a > t:
            out.append((t, a))
        t = b
    if hi > t:
        out.append((t, hi))
    return out


def _events(line) -> list[Event]:
    return [Event(e.name, e.start_ns, e.start_ns + e.duration_ns,
                  {k: v for k, v in e.stats}) for e in line.events]


def _device_ops(plane) -> list[Event]:
    """A device plane's operations, each named by its HLO instruction
    (``%fusion.3 = f32[...] fusion(...)`` → ``fusion.3``), with the full
    text as ``long_name`` and its module as ``hlo_module``."""
    lines = {line.name: _events(line) for line in plane.lines}
    modules = sorted(lines.get(MODULE_LINE, []), key=lambda e: e.start_ns)
    starts = [m.start_ns for m in modules]
    ops = []
    for e in lines.get(OP_LINE, []):
        stats = dict(e.stats, long_name=e.name)
        i = bisect.bisect_right(starts, e.start_ns) - 1
        if i >= 0 and e.start_ns < modules[i].end_ns:
            stats["hlo_module"] = modules[i].name.split("(")[0]
        name = e.name.split(" = ")[0].lstrip("%")
        ops.append(Event(name, e.start_ns, e.end_ns, stats))
    return sorted(ops, key=lambda e: e.start_ns)


def from_planes(planes) -> TraceView:
    """A view of profiler planes: objects with ``name`` and ``lines``, whose
    lines have ``name`` and ``events``, whose events have ``name``,
    ``start_ns``, ``duration_ns`` and ``stats`` (pairs), as
    ``jax.profiler.ProfileData`` gives them."""
    devices, annotations = [], []
    for plane in planes:
        if plane.name.startswith("/device:"):
            ops = _device_ops(plane)
            if ops:
                devices.append(ops)
        elif plane.name.startswith("/host:"):
            annotations += [e for line in plane.lines for e in _events(line)
                            if e.name.startswith(PREFIX)]
    return TraceView(devices, sorted(annotations, key=lambda e: e.start_ns))


def load(log_dir: str) -> TraceView:
    """The view of the newest ``.xplane.pb`` that ``jax.profiler`` wrote
    under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return from_planes(ProfileData.from_file(paths[-1]).planes)
