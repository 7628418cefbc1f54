"""Group-by sum kernel (TPU adaptation of LaFP's group-by hot path,
DESIGN §2).

GPU/CPU dataframe engines aggregate via hash tables — branchy scalar probing
that has no TPU analogue.  Here the dense code domain that dict-encoded
(category) keys guarantee turns the aggregation into a masked reduction:
codes and value columns are laid out lane-dense as ``(rows, 128)`` tiles,
and each grid step adds, for every group ``g``,

    acc[v, g, :] += Σ_rows where(codes == g, values[v], 0)

into a ``(V, G, 128)`` per-lane accumulator that stays resident in VMEM
across the grid (constant index_map).  One pass over HBM with no padding of
the value columns; the final 128-lane reduction happens outside the kernel.

Floats sum on the VPU in f32.  Ints and bools sum exactly as two int32
words, ``hi · 2^16 + lo``: each value adds its low 16 bits to ``lo`` and
the rest (``v >> 16``) to ``hi``, and every grid step carries ``lo``'s
overflow into ``hi``, so no word wraps while a group's total stays below
2^47 (a one-hot MXU matmul would round ints above 2^24 through f32, and a
plain int32 sum would wrap past 2^31).  Codes outside [0, num_groups)
contribute nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .platform import LANES, SUBLANES, interpret_mode, round_up, row_tiles


WORD_BITS = 16                  # int sums: hi · 2^WORD_BITS + lo
_LO_MASK = (1 << WORD_BITS) - 1


def _groupby_kernel(codes_ref, values_ref, *out_refs, num_groups: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        for ref in out_refs:
            ref[...] = jnp.zeros_like(ref)

    codes = codes_ref[...]                          # (Rb, 128) int32
    n_vals = values_ref.shape[0]

    def lane_sum(hit, x):                           # (1, 128) lane partials
        return jnp.sum(jnp.where(hit, x, jnp.zeros_like(x)), axis=0,
                       keepdims=True)

    def body(g, carry):
        hit = codes == g
        for v in range(n_vals):
            vals = values_ref[v]                    # (Rb, 128)
            if len(out_refs) == 1:                  # f32 sum
                out_refs[0][v, pl.ds(g, 1), :] += lane_sum(hit, vals)
                continue
            # int words; a block's lo partial is < Rb · 2^16, so Rb < 2^15
            hi_ref, lo_ref = out_refs
            lo = lo_ref[v, pl.ds(g, 1), :] + lane_sum(hit, vals & _LO_MASK)
            hi_ref[v, pl.ds(g, 1), :] += (lane_sum(hit, vals >> WORD_BITS)
                                          + (lo >> WORD_BITS))
            lo_ref[v, pl.ds(g, 1), :] = lo & _LO_MASK
        return carry

    jax.lax.fori_loop(0, num_groups, body, 0)


def groupby_sum(codes: jax.Array, values: jax.Array, num_groups: int,
                block_rows: int = 64 * 1024) -> jax.Array:
    """Segment-sum values (N,) or (N, V) by codes (N,) → (G,) or (G, V):
    f32 for float values; for ints and bools int32, which wraps past 2^31
    as XLA's int32 segment sum does (``groupby_sum_words`` keeps the
    exact total).  ``block_rows`` elements per grid step (a multiple of
    1024)."""
    out = _groupby_sum(codes, values, num_groups, block_rows,
                       interpret_mode())
    if isinstance(out, tuple):
        hi, lo = out
        return (hi << WORD_BITS) + lo
    return out


def groupby_sum_words(codes: jax.Array, values: jax.Array, num_groups: int,
                      block_rows: int = 64 * 1024
                      ) -> tuple[jax.Array, jax.Array]:
    """Exact segment sums of int or bool values as int32 words ``(hi, lo)``,
    each (G,) or (G, V): the sum is ``hi · 2^16 + lo`` with ``0 <= lo <
    2^16``, exact while it stays below 2^47."""
    if jnp.dtype(values.dtype).kind == "f":
        raise TypeError("groupby_sum_words sums int or bool values")
    return _groupby_sum(codes, values, num_groups, block_rows,
                        interpret_mode())


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _groupby_sum(codes, values, num_groups, block_rows, interpret):
    squeeze = values.ndim == 1
    if squeeze:
        values = values[:, None]
    n, v = values.shape
    words = jnp.dtype(values.dtype).kind != "f"
    acc = jnp.int32 if words else jnp.float32
    rb, rows = row_tiles(n, block_rows)
    pad = rows * LANES - n
    codes_p = jnp.pad(codes.astype(jnp.int32), (0, pad),
                      constant_values=-1).reshape(rows, LANES)  # pad → no group
    values_p = jnp.pad(values.astype(acc).T, ((0, 0), (0, pad))
                       ).reshape(v, rows, LANES)
    gp = round_up(max(num_groups, 1), SUBLANES)
    out_block = pl.BlockSpec((v, gp, LANES), lambda i: (0, 0, 0))
    out_shape = jax.ShapeDtypeStruct((v, gp, LANES), acc)
    outs = pl.pallas_call(
        functools.partial(_groupby_kernel, num_groups=num_groups),
        grid=(rows // rb,),
        in_specs=[
            pl.BlockSpec((rb, LANES), lambda i: (i, 0)),
            pl.BlockSpec((v, rb, LANES), lambda i: (0, i, 0)),
        ],
        out_specs=[out_block] * (2 if words else 1),
        out_shape=[out_shape] * (2 if words else 1),
        interpret=interpret,
    )(codes_p, values_p)

    def groups_by_values(x):                        # (V, G) → (G, V) or (G,)
        x = x[:, :num_groups].T
        return x[:, 0] if squeeze else x

    if not words:
        return groups_by_values(outs[0].sum(axis=2))
    hi, lo = outs
    lo = lo.sum(axis=2)                             # < 128 · 2^16
    hi = hi.sum(axis=2) + (lo >> WORD_BITS)
    return groups_by_values(hi), groups_by_values(lo & _LO_MASK)
