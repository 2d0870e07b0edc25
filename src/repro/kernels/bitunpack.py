"""Pallas TPU kernel: k-bit little-endian unpack -> uint32.

Bit-unpacking ends every transparent integer codec in the paper (control
words §4.1.1, mini-block values §4.2, repetition indexes §4.1.4), so it is
the innermost decode hot-spot.

TPU layout.  Mosaic gathers only *within* a vector row, so the packed
stream is first cut into **row windows**: output row ``r`` holds values
``[128 r, 128 r + 128)``, whose ``128 * bits`` bits are exactly the
``4 * bits`` words starting at word ``4 * bits * r`` (<= 128 words for
``bits <= 32``).  :func:`row_windows` builds that ``(R, 128)`` layout with one
XLA gather outside the kernel; inside, every value is two in-row lane
gathers plus shifts (:func:`unpack_rows`).  No value straddles a window, so
there is no halo.  The same two helpers drive the mini-block kernel, where
the width is a per-chunk runtime scalar.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["bitunpack_pallas", "row_windows", "unpack_rows", "VALS_PER_BLOCK",
           "LANES"]

LANES = 128
ROWS_PER_BLOCK = 64
VALS_PER_BLOCK = ROWS_PER_BLOCK * LANES  # 8192 values / grid step
NAME = "bitunpack"  # the kernel's name and its ops' named scope


def row_windows(words: jax.Array, bits, n_rows: int) -> jax.Array:
    """``(..., W)`` uint32 streams -> ``(..., n_rows, 128)`` row windows.

    ``bits`` is a static int or an array broadcastable to ``words.shape[:-1]``
    (one width per stream).  Lanes past ``4 * bits`` and words past the
    stream's end read as zero.
    """
    per_row = 4 * jnp.asarray(bits, jnp.int32)[..., None, None]  # words/row
    r = jax.lax.broadcasted_iota(jnp.int32, (n_rows, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (n_rows, LANES), 1)
    idx = jnp.where(lane < per_row, r * per_row + lane, words.shape[-1])
    idx = jnp.broadcast_to(idx, words.shape[:-1] + (n_rows, LANES))
    flat = idx.reshape(words.shape[:-1] + (n_rows * LANES,))
    got = jnp.take_along_axis(words, flat, axis=-1, mode="fill", fill_value=0)
    return got.reshape(idx.shape)


def unpack_rows(w: jax.Array, bits) -> jax.Array:
    """Unpack one value per lane from ``(R, 128)`` uint32 row windows.

    ``bits`` is a static int or a uint32 scalar (0..32).
    """
    bits = jnp.asarray(bits, jnp.uint32)
    bitpos = jax.lax.broadcasted_iota(jnp.uint32, w.shape, 1) * bits
    wi = (bitpos >> 5).astype(jnp.int32)
    sh = bitpos & jnp.uint32(31)
    w0 = jnp.take_along_axis(w, wi, axis=1)
    w1 = jnp.take_along_axis(w, jnp.minimum(wi + 1, LANES - 1), axis=1)
    hi = jnp.where(sh > 0, w1 << ((jnp.uint32(32) - sh) & jnp.uint32(31)),
                   jnp.uint32(0))
    mask = jnp.where(bits >= 32, jnp.uint32(0xFFFFFFFF),
                     (jnp.uint32(1) << (bits & jnp.uint32(31))) - jnp.uint32(1))
    return ((w0 >> sh) | hi) & mask


def _kernel(w_ref, out_ref, *, bits: int):
    out_ref[...] = unpack_rows(w_ref[...], bits)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def bitunpack_pallas(words: jax.Array, bits: int, *, interpret: bool) -> jax.Array:
    """Unpack a uint32 word stream into ``(n_blocks * 8192,)`` uint32 values.

    ``words`` holds at least ``ceil(n_values * bits / 32)`` words, padded up
    to a multiple of ``256 * bits`` (the per-block word count); callers slice
    the result to their true length.
    """
    wpb = VALS_PER_BLOCK * bits // 32
    assert words.shape[0] % wpb == 0, (words.shape, wpb)
    n_blocks = words.shape[0] // wpb
    with jax.named_scope(NAME):
        rows = row_windows(words, bits, n_blocks * ROWS_PER_BLOCK)
        out = pl.pallas_call(
            functools.partial(_kernel, bits=bits),
            grid=(n_blocks,),
            in_specs=[pl.BlockSpec((ROWS_PER_BLOCK, LANES), lambda b: (b, 0))],
            out_specs=pl.BlockSpec((ROWS_PER_BLOCK, LANES), lambda b: (b, 0)),
            out_shape=jax.ShapeDtypeStruct(rows.shape, jnp.uint32),
            interpret=interpret,
            name=NAME,
        )(rows)
        return out.reshape(-1)
