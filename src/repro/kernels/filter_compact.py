"""Filter + stream compaction kernel (TPU adaptation of LaFP's filter hot
path, DESIGN §2).

GPU compaction uses warp ballots and shared-memory scans; neither exists on
TPU.  The TPU-native design works on lane-dense ``(rows, 128)`` tiles:

* the in-row slot of every surviving element is an inclusive prefix count,
  computed on the MXU as ``mask · U`` with ``U`` upper-triangular (Mosaic
  has no cumsum);
* each row is then packed by a **permutation matmul**
  ``packed[s] = Σ_j word[j] · onehot[s, j]`` with ``onehot[s, j] = mask_j ∧
  slot_j == s`` — scatter-free, branch-free.  Elements move as their 32-bit
  pattern split into four bytes, each exact in bf16, so any 32-bit dtype
  (NaN, ±inf and -0.0 included) round-trips bit for bit;
* rows come out packed at their own row (aligned stores only); one XLA pass
  then stitches the rows' valid prefixes into a contiguous column.  The
  stitch's gather index depends on the mask alone, so the columns of one
  table compact in one call that computes it once.

Everything streams through HBM, so whole columns compact in one call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import LANES, SUBLANES, interpret_mode, row_tiles


def _compact_kernel(mask_ref, words_ref, out_ref, slot_ref):
    rb = mask_ref.shape[0]
    keep = mask_ref[...]                                       # (Rb, 128) f32
    upper = (jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0) <=
             jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1))
    incl = jnp.dot(keep.astype(jnp.bfloat16), upper.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)         # prefix counts
    # slot of each survivor in its row; -1 for dropped elements
    slot_ref[...] = jnp.where(keep > 0, incl.astype(jnp.int32) - 1, -1)

    target = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    byte_row = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0)

    def body(r, carry):
        slot = slot_ref[pl.ds(r, 1), :]                        # (1, 128)
        word = words_ref[pl.ds(r, 1), :]                       # (1, 128)
        onehot = (target == slot).astype(jnp.bfloat16)         # (128 s, 128 j)
        wide = jnp.broadcast_to(word, (SUBLANES, LANES))
        parts = jnp.where(byte_row < 4,
                          jnp.right_shift(wide, byte_row * 8) & 0xFF, 0)
        moved = jax.lax.dot_general(
            parts.astype(jnp.float32).astype(jnp.bfloat16), onehot,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.int32)  # (8, 128 s)
        out_ref[pl.ds(r, 1), :] = (
            moved[0:1] | (moved[1:2] << 8) | (moved[2:3] << 16)
            | (moved[3:4] << 24))
        return carry

    jax.lax.fori_loop(0, rb, body, 0)


def _to_words(values: jax.Array) -> jax.Array:
    """32-bit pattern of each element (narrow types widen exactly)."""
    dt = values.dtype
    if dt.kind == "f":
        if dt.itemsize != 4:
            values = values.astype(jnp.float32)
        return jax.lax.bitcast_convert_type(values, jnp.int32)
    if dt.itemsize == 4:
        return jax.lax.bitcast_convert_type(values, jnp.int32)
    return values.astype(jnp.int32)


def _from_words(words: jax.Array, dt) -> jax.Array:
    if dt.kind == "f":
        out = jax.lax.bitcast_convert_type(words, jnp.float32)
        return out if dt.itemsize == 4 else out.astype(dt)
    if dt.itemsize == 4:
        return jax.lax.bitcast_convert_type(words, dt)
    return words.astype(dt)


def filter_compact(values: jax.Array, mask: jax.Array,
                   block_rows: int = 32 * 1024
                   ) -> tuple[jax.Array, jax.Array]:
    """Pack values[mask] to the front (stable); returns (packed (N,), count)
    with the input dtype.  Slots ≥ count are zeroed.  ``block_rows``
    elements per grid step (a multiple of 1024)."""
    (out,), count = filter_compact_columns((values,), mask, block_rows)
    return out, count


def filter_compact_columns(columns, mask: jax.Array,
                           block_rows: int = 32 * 1024
                           ) -> tuple[tuple[jax.Array, ...], jax.Array]:
    """``filter_compact`` of each (N,) column under one mask; returns
    (packed columns, count)."""
    return _filter_compact(tuple(columns), mask, block_rows,
                           interpret_mode())


@functools.partial(jax.jit, static_argnums=(2, 3))
def _filter_compact(columns, mask, block_rows, interpret):
    n = mask.shape[0]
    rb, rows = row_tiles(n, block_rows)
    pad = rows * LANES - n
    keep = jnp.pad(mask.astype(jnp.int32), (0, pad)).reshape(rows, LANES)
    pack = pl.pallas_call(
        _compact_kernel,
        grid=(rows // rb,),
        in_specs=[pl.BlockSpec((rb, LANES), lambda i: (i, 0)),
                  pl.BlockSpec((rb, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rb, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        scratch_shapes=[pltpu.VMEM((rb, LANES), jnp.int32)],
        interpret=interpret,
    )
    # stitch: row r's survivors occupy output slots [end[r-1], end[r]), so
    # the row owning slot j is the number of rows that end at or before j
    per_row = keep.sum(axis=1)
    end = jnp.cumsum(per_row)
    count = end[-1]
    slot = jnp.arange(n, dtype=jnp.int32)
    owner = jnp.searchsorted(end, slot, side="right").astype(jnp.int32)
    owner = jnp.minimum(owner, rows - 1)        # slots ≥ count: masked below
    src = owner * LANES + (slot - (end[owner] - per_row[owner]))
    live = slot < count
    keep = keep.astype(jnp.float32)
    out = []
    for values in columns:
        words = jnp.pad(_to_words(values), (0, pad)).reshape(rows, LANES)
        packed = pack(keep, words).reshape(-1)
        out.append(_from_words(jnp.where(live, packed[src], 0),
                               values.dtype))
    return tuple(out), count
