"""Four lazy prints flushed at the end: a constant line, a per-vendor
mean trip length, the mean fare of the paid trips and their count, planned
together."""
from __future__ import annotations

import numpy as np
import pandas as pd

import repro.core as core
from repro.core.context import get_context
from repro.core.func import flush, len as lazy_len, print as lprint
from bench.check import grouped

TABLES = ("taxi",)


def run(S):
    printed: list[str] = []
    get_context().print_fn = lambda *a, **_: printed.append(
        " ".join(str(x) for x in a))
    df = core.read_source(S["taxi"])
    lprint("rows loaded")
    df = df[df["fare_amount"] > 0]
    per_vendor = df.groupby(["VendorID"])["trip_distance"].mean()
    lprint(per_vendor)
    avg = df["fare_amount"].mean()
    lprint(f"avg fare: {avg}")
    lprint(f"paid trips: {lazy_len(df)}")
    flush()
    return printed


def reference(t, p):
    t = t["taxi"]
    m = p.host(t["fare_amount"]) > 0
    per_vendor = pd.Series(p.device(t["trip_distance"][m]).astype(
        np.float64)).groupby(t["VendorID"][m]).mean()
    avg = p.out(float(np.mean(p.device(t["fare_amount"][m]).astype(
        np.float64))))
    table = "\n".join(f"{k} | {float(v)!r}" for k, v in zip(
        per_vendor.index, p.out(per_vendor.to_numpy())))
    return ["rows loaded", f"frame\nVendorID | trip_distance\n{table}",
            f"avg fare: {avg!r}", f"paid trips: {int(np.count_nonzero(m))}"]


def groupby_sums(t):
    """The group-by sums the answer needs: (rows, values, groups, 4-byte
    words written per group and value)."""
    rows = int((t["taxi"]["fare_amount"] > 0).sum())
    return [(rows, 1, 4, 2)]          # the per-vendor mean: sum and count


def _frame(text: str) -> dict:
    """Columns of a frame as the print sink rendered it: a title line, a
    header of names and one line a row, fields split by ``|``."""
    lines = text.splitlines()
    names = [c.strip() for c in lines[1].split("|")]
    values = [[float(x) for x in ln.split("|")] for ln in lines[2:]]
    return {c: np.asarray(v) for c, v in zip(names, zip(*values))}


def check(got, want, tally):
    if len(got) != 4 or got[0] != want[0] or \
            not got[2].startswith("avg fare:") or \
            not got[3].startswith("paid trips:"):
        tally.mismatches += 1
        tally.fault("multi_print lines", got, want)
        return
    g, w = _frame(got[1]), _frame(want[1])
    g["VendorID"] = g["VendorID"].astype(np.int64)
    w["VendorID"] = w["VendorID"].astype(np.int64)
    grouped(tally, "multi_print per-vendor", g, w, "VendorID",
            "trip_distance", exact=False)
    tally.close("multi_print avg fare", float(got[2].split(":")[1]),
                float(want[2].split(":")[1]))
    tally.exact("multi_print paid trips", int(got[3].split(":")[1]),
                int(want[3].split(":")[1]))
