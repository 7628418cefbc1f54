"""Pure-jnp oracles for the Pallas kernels.

Each function is the semantic ground truth; kernel tests sweep shapes/dtypes
and assert_allclose against these.
"""
from __future__ import annotations

import jax.numpy as jnp


def groupby_sum_ref(codes: jnp.ndarray, values: jnp.ndarray,
                    num_groups: int) -> jnp.ndarray:
    """Segment-sum of ``values`` (N,) or (N, V) by int ``codes`` (N,) into
    (G,) or (G, V), accumulated in f32 for floats and int32 for ints and
    bools.  Out-of-range codes contribute nothing."""
    import jax
    values = values.astype(jnp.float32 if values.dtype.kind == "f"
                           else jnp.int32)
    valid = (codes >= 0) & (codes < num_groups)
    safe = jnp.where(valid, codes, num_groups)
    if values.ndim == 1:
        vals = jnp.where(valid, values, 0)
        return jax.ops.segment_sum(vals, safe, num_groups + 1)[:num_groups]
    vals = jnp.where(valid[:, None], values, 0)
    return jax.ops.segment_sum(vals, safe, num_groups + 1)[:num_groups]


def groupby_sum_words_ref(codes: jnp.ndarray, values: jnp.ndarray,
                          num_groups: int, chunk: int = 1 << 14
                          ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact segment sums of int or bool ``values`` as int32 words
    ``(hi, lo)``, sum = ``hi · 2^16 + lo``, ``0 <= lo < 2^16``.  Rows are
    summed ``chunk`` at a time so a chunk's ``lo`` partial (< chunk · 2^16)
    cannot wrap before its carry moves into ``hi``."""
    import jax
    squeeze = values.ndim == 1
    values = values.astype(jnp.int32)
    if squeeze:
        values = values[:, None]
    n, v = values.shape
    valid = (codes >= 0) & (codes < num_groups)
    safe = jnp.where(valid, codes, num_groups).astype(jnp.int32)
    pad = -n % chunk
    safe = jnp.pad(safe, (0, pad), constant_values=num_groups).reshape(
        -1, chunk)
    values = jnp.pad(values, ((0, pad), (0, 0))).reshape(-1, chunk, v)

    def step(acc, xs):
        hi, lo = acc
        k, x = xs
        lo = lo + jax.ops.segment_sum(x & 0xFFFF, k, num_groups + 1)
        hi = hi + jax.ops.segment_sum(x >> 16, k, num_groups + 1) + (lo >> 16)
        return (hi, lo & 0xFFFF), None

    zeros = jnp.zeros((num_groups + 1, v), jnp.int32)
    (hi, lo), _ = jax.lax.scan(step, (zeros, zeros), (safe, values))
    hi, lo = hi[:num_groups], lo[:num_groups]
    return (hi[:, 0], lo[:, 0]) if squeeze else (hi, lo)


def filter_count_ref(mask: jnp.ndarray) -> jnp.ndarray:
    """Number of surviving rows."""
    return jnp.sum(mask.astype(jnp.int32))


def filter_compact_ref(values: jnp.ndarray, mask: jnp.ndarray
                       ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Stable compaction: surviving values packed to the front, padded with
    zeros; returns (packed (N,), count ())."""
    n = values.shape[0]
    idx = jnp.cumsum(mask.astype(jnp.int32)) - 1          # target slot per row
    count = jnp.sum(mask.astype(jnp.int32))
    safe_idx = jnp.where(mask, idx, n)                    # masked rows → spill
    out = jnp.zeros((n + 1,), values.dtype).at[safe_idx].set(values)[:n]
    valid = jnp.arange(n) < count
    return jnp.where(valid, out, jnp.zeros_like(out)), count


def zonemap_ref(values: jnp.ndarray, block: int
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-block (min, max) over a 1-D array padded to a multiple of block.
    Padding uses +inf/-inf identities."""
    n = values.shape[0]
    nb = -(-n // block)
    pad = nb * block - n
    if values.dtype.kind == "f":
        lo_id, hi_id = jnp.inf, -jnp.inf
    else:
        info = jnp.iinfo(values.dtype)
        lo_id, hi_id = info.max, info.min
    v_lo = jnp.concatenate([values, jnp.full((pad,), lo_id, values.dtype)])
    v_hi = jnp.concatenate([values, jnp.full((pad,), hi_id, values.dtype)])
    mins = v_lo.reshape(nb, block).min(axis=1)
    maxs = v_hi.reshape(nb, block).max(axis=1)
    return mins, maxs
