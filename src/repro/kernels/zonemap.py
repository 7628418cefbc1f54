"""Zone-map statistics kernel: per-block (min, max) for the metadata store
(paper §3.6) and partition pruning (DESIGN §6).

One pass over HBM.  The column is viewed as ``(blocks, block_rows)`` — one
zone per row — and each grid step reduces a tile of zones along the lanes
into a ``(zones, 1)`` output tile.  Ints stay ints (int32 compute), so the
bounds are exact for every value the device can hold.  Runs at read time on
the device so the "background metadata task" costs one streaming read of
the column.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .platform import SUBLANES, interpret_mode, round_up

_TILE_ELEMS = 256 * 1024       # elements per grid step (1 MiB of 32-bit)


def _zonemap_kernel(values_ref, mins_ref, maxs_ref, *, rows: int,
                    block_rows: int, lo_id, hi_id):
    vals = values_ref[...]                       # (Tb, B): one zone per row
    tb, b = vals.shape
    zone = jax.lax.broadcasted_iota(jnp.int32, (tb, b), 0) + \
        pl.program_id(0) * tb
    idx = zone * block_rows + jax.lax.broadcasted_iota(jnp.int32, (tb, b), 1)
    in_range = idx < rows                        # padding → identities
    mins_ref[...] = jnp.min(jnp.where(in_range, vals, lo_id), axis=1,
                            keepdims=True)
    maxs_ref[...] = jnp.max(jnp.where(in_range, vals, hi_id), axis=1,
                            keepdims=True)


def _compute_dtype(dt) -> jnp.dtype:
    if dt.kind == "f":
        return jnp.dtype(jnp.float32)
    if dt.kind == "u" and dt.itemsize == 4:
        return jnp.dtype(jnp.uint32)
    return jnp.dtype(jnp.int32)


def zonemap(values: jax.Array, block_rows: int = 4096
            ) -> tuple[jax.Array, jax.Array]:
    """Per-block (min, max) of a 1-D array; blocks of ``block_rows``; the
    bounds keep the input dtype."""
    return _zonemap(values, block_rows, interpret_mode())


@functools.partial(jax.jit, static_argnums=(1, 2))
def _zonemap(values, block_rows, interpret):
    n = values.shape[0]
    dt = _compute_dtype(values.dtype)
    if dt.kind == "f":
        lo_id, hi_id = float("inf"), float("-inf")
    else:
        info = jnp.iinfo(dt)
        lo_id, hi_id = int(info.max), int(info.min)
    nb = -(-max(n, 1) // block_rows)
    tb = min(round_up(max(_TILE_ELEMS // block_rows, 1), SUBLANES),
             round_up(nb, SUBLANES))
    nbp = round_up(nb, tb)
    vals = jnp.pad(values.astype(dt), (0, nbp * block_rows - n)
                   ).reshape(nbp, block_rows)
    mins, maxs = pl.pallas_call(
        functools.partial(_zonemap_kernel, rows=n, block_rows=block_rows,
                          lo_id=lo_id, hi_id=hi_id),
        grid=(nbp // tb,),
        in_specs=[pl.BlockSpec((tb, block_rows), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((tb, 1), lambda i: (i, 0)),
                   pl.BlockSpec((tb, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((nbp, 1), dt),
                   jax.ShapeDtypeStruct((nbp, 1), dt)],
        interpret=interpret,
    )(vals)
    return (mins[:nb, 0].astype(values.dtype),
            maxs[:nb, 0].astype(values.dtype))
