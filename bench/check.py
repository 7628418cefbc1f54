"""The comparison that decides a run's ``correct``.

Each program's ``reference`` computes its answer in plain pandas and numpy
under a :class:`Precision`: :data:`REFERENCE` mirrors what the engine
promises (predicates on the host's float64 values, columns the device
computes in float32, aggregates exact in float64), :data:`BFLOAT16` is the
control, the same reference with every float held and computed in bfloat16.
Its ``check`` feeds a :class:`Tally`, which keeps the numbers compared:

- ``mismatches``: elements that must match exactly and do not (keys, ints,
  counts, maxima, selected rows);
- ``rel_err``: the worst relative error of a float aggregate.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import ml_dtypes
import numpy as np


def _float_only(cast: Callable) -> Callable:
    def apply(x):
        a = np.asarray(x)
        if a.dtype.kind != "f":
            return x
        out = cast(a)
        return out if a.ndim else out.item()
    return apply


def _bf16(a: np.ndarray) -> np.ndarray:
    """Round to bfloat16; the values are then held exactly in float32."""
    return a.astype(ml_dtypes.bfloat16).astype(np.float32)


@dataclasses.dataclass(frozen=True)
class Precision:
    """Where a reference rounds its floats: ``host`` for values the engine
    keeps on the host (pushed-down predicates), ``device`` for columns the
    device holds and computes, ``out`` for the aggregates returned."""

    host: Callable
    device: Callable
    out: Callable


def _same(x):
    return x


REFERENCE = Precision(host=_same,
                      device=_float_only(lambda a: a.astype(np.float32)),
                      out=_same)
BFLOAT16 = Precision(host=_float_only(_bf16), device=_float_only(_bf16),
                     out=_float_only(_bf16))


class Tally:
    """The numbers compared over every result a run checks."""

    def __init__(self):
        self.mismatches = 0
        self.rel_err = 0.0
        self.compared = 0
        self.first_fault: str | None = None

    def fault(self, what: str, got, want) -> None:
        if self.first_fault is None:
            self.first_fault = f"{what}: got {got!r:.300} want {want!r:.300}"

    def exact(self, what: str, got, want) -> None:
        got, want = np.asarray(got), np.asarray(want)
        self.compared += 1
        if got.shape != want.shape:
            self.mismatches += max(got.size, want.size, 1)
            self.fault(what, got, want)
            return
        bad = int(np.count_nonzero(got.astype(want.dtype) != want))
        if bad:
            self.mismatches += bad
            self.fault(what, got, want)

    def close(self, what: str, got, want) -> None:
        got = np.asarray(got, np.float64)
        want = np.asarray(want, np.float64)
        self.compared += 1
        if got.shape != want.shape or not np.all(np.isfinite(got)):
            self.mismatches += max(got.size, want.size, 1)
            self.fault(what, got, want)
            return
        if not want.size:
            return
        scale = np.where(want != 0, np.abs(want), 1.0)
        err = float(np.max(np.abs(got - want) / scale))
        if err > self.rel_err:
            self.rel_err = err

    def numbers(self) -> dict[str, float]:
        return {"rel_err": self.rel_err, "mismatches": self.mismatches}


def grouped(tally: Tally, what: str, got: dict, want: dict, key: str,
            col: str, exact: bool) -> None:
    """Group keys exactly, then the aggregate exactly or by relative error."""
    tally.exact(f"{what} keys", got[key], want[key])
    (tally.exact if exact else tally.close)(what, got[col], want[col])


def rows(tally: Tally, what: str, got: dict, want: dict) -> None:
    """Selected rows: the same columns, in order, with the same values."""
    if list(got) != list(want):
        tally.mismatches += 1
        tally.fault(f"{what} columns", list(got), list(want))
        return
    for c in want:
        tally.exact(f"{what}.{c}", got[c], want[c])


def top_rows(tally: Tally, what: str, got: dict, want, key: str,
             n: int) -> None:
    """The ``n`` rows of ``want`` (a frame) with the largest ``key``: the
    same columns, the keys in descending order exactly, and each row one of
    ``want``'s with a key at or above the n-th largest, none twice as
    often as there.  Rows that tie on the key may come in any order."""
    if list(got) != list(want.columns):
        tally.mismatches += 1
        tally.fault(f"{what} columns", list(got), list(want.columns))
        return
    keys = np.sort(want[key].to_numpy())[::-1][:n]
    tally.exact(f"{what}.{key}", got[key], keys)
    if not len(keys) or len(got[key]) != len(keys):
        return
    pool = want[want[key] >= keys[-1]]
    left = pool.value_counts().to_dict()
    for row in zip(*(np.asarray(got[c]).tolist() for c in want.columns)):
        if left.get(row, 0) <= 0:
            tally.mismatches += 1
            tally.fault(f"{what} row", row, "one of the reference's top rows")
        else:
            left[row] -= 1


def to_host(value):
    """A program's result in host memory: frames as dicts of numpy columns
    (categorical columns as their strings), device scalars as Python
    numbers."""
    if hasattr(value, "columns"):
        return {c: value.decode(c) if c in getattr(value, "vocab", {})
                else np.asarray(v) for c, v in value.columns.items()}
    if isinstance(value, (tuple, list)):
        return type(value)(to_host(v) for v in value)
    if hasattr(value, "shape"):
        return np.asarray(value).item()
    return value
