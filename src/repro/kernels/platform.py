"""Where the Pallas kernels run: compiled by Mosaic on a TPU, interpreted
everywhere else.  Interpret mode is derived from the platform, never chosen
by a caller, so a TPU host cannot end up in the interpreter.  (Tests that
compile for a described chip patch ``on_tpu``.)"""
from __future__ import annotations

import jax

LANES = 128       # TPU vector lane width: the minor dim of every kernel tile
SUBLANES = 8      # 32-bit sublane count: the second-minor tile dim


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def interpret_mode() -> bool:
    """Pallas interpret mode: exactly when no TPU is attached."""
    return not on_tpu()


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def row_tiles(n: int, block_rows: int) -> tuple[int, int]:
    """Lay ``n`` elements out as ``(rows, LANES)`` tiles: returns
    ``(rows_per_block, padded_rows)``.  Blocks hold ``block_rows`` elements
    (a multiple of 8·128) or, for short inputs, the whole padded array."""
    if block_rows % (SUBLANES * LANES):
        raise ValueError(f"block_rows={block_rows} is not a multiple of "
                         f"{SUBLANES * LANES}")
    rows = round_up(max(n, 1), LANES) // LANES
    rb = min(block_rows // LANES, round_up(rows, SUBLANES))
    return rb, round_up(rows, rb)
