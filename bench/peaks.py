"""Published peaks of each device the benchmark runs on, keyed by JAX's
``device_kind``, and the least work of the kernels whose roofline share it
reports.

A device that is not in the table is an error: a share of an unknown peak
is no number.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def groupby_sum_bytes(rows: int, values: int, groups: int,
                      words: int = 1) -> int:
    """HBM bytes a group-by sum needs at least: each 4-byte code and value
    read once, and ``words`` 4-byte words written once per group and value
    (1 for a float sum; 2 for an exact int sum, or for a sum with its
    count).  Counted from the logical shapes, not from padded tiles or the
    kernel's lane-wide partial sums."""
    return 4 * rows * (1 + values) + 4 * groups * values * words
