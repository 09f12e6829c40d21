"""Masked per-row SoA updates without XLA scatters.

TPU lowers `arr.at[rows, col].set/add` to a serialized element-by-element
scatter (~0.5 µs each — docs/bench_notes.md measured the engine's removal
of these at 0.84× → 3.6× baseline). Every hot-path "write one slot per
host" update in the framework goes through these helpers instead: a
broadcast compare builds the [H, S] hit mask and a single elementwise
select rewrites the array — full-bandwidth traffic, no serialization.

`arr` is [H, S] or [H, S, P]; `col` is [H] (the slot per host); `mask` is
[H] (which hosts write). `val` may be scalar, [H], or [H, P].
"""

from __future__ import annotations

import jax.numpy as jnp


def _hit(arr, mask, col):
    S = arr.shape[1]
    cols = jnp.arange(S, dtype=jnp.int32)
    return mask[:, None] & (cols[None, :] == col[:, None])  # [H, S]


def set_at(arr, mask, col, val):
    """arr[h, col[h]] = val[h] where mask[h]."""
    hit = _hit(arr, mask, col)
    val = jnp.asarray(val, arr.dtype)
    if arr.ndim == 3:
        if val.ndim == 2:
            val = val[:, None, :]
        return jnp.where(hit[:, :, None], val, arr)
    if val.ndim == 1:
        val = val[:, None]
    return jnp.where(hit, val, arr)


def get_at(arr, col):
    """arr[h, col[h]] via a one-hot masked reduce — NOT a gather, which
    serializes per output element on TPU (~9 ns/element, docs/bench_notes.md
    round-2 profile). Rows whose col is outside [0, S) return 0."""
    hit = _hit(arr, jnp.ones(col.shape, bool), col)
    if arr.ndim == 3:
        return jnp.sum(
            jnp.where(hit[:, :, None], arr, 0), axis=1, dtype=arr.dtype
        )
    return jnp.sum(jnp.where(hit, arr, 0), axis=1, dtype=arr.dtype)


def add_at(arr, mask, col, val):
    """arr[h, col[h]] += val[h] where mask[h]."""
    hit = _hit(arr, mask, col)
    val = jnp.asarray(val, arr.dtype)
    if arr.ndim == 3:
        if val.ndim == 2:
            val = val[:, None, :]
        return arr + jnp.where(hit[:, :, None], val, jnp.zeros_like(arr))
    if val.ndim == 1:
        val = val[:, None]
    return arr + jnp.where(hit, val, jnp.zeros_like(arr))


def pack_words(payload):
    """[..., P] i32 payload words → [..., ceil(P/2)] i64, pairs packed as
    (hi word 2w+1) << 32 | (lo word 2w, zero-extended).

    Packing halves the box-write traffic (and the columns the engine
    carries) at the cost of one elementwise pass at the pack/unpack
    boundaries. It does not set the row gathers' cost: engine._take_rows
    moves every row as 32-bit words, packed or not. Odd P pads the last
    high word with zero."""
    P = payload.shape[-1]
    if P % 2:
        payload = jnp.concatenate(
            [payload, jnp.zeros(payload.shape[:-1] + (1,), payload.dtype)],
            axis=-1,
        )
    lo = payload[..., 0::2].astype(jnp.int64) & 0xFFFFFFFF
    hi = payload[..., 1::2].astype(jnp.int64)
    return (hi << 32) | lo


def unpack_words(packed, P: int):
    """Inverse of pack_words: [..., PP] i64 → [..., P] i32."""
    lo = (packed & 0xFFFFFFFF).astype(jnp.int32)
    hi = (packed >> 32).astype(jnp.int32)
    out = jnp.stack([lo, hi], axis=-1).reshape(packed.shape[:-1] + (-1,))
    return out[..., :P]


def packed_words(P: int) -> int:
    return (P + 1) // 2
