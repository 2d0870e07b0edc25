"""Pallas TPU kernel: mini-block chunk decode.

One grid step decodes one mini-block chunk (§4.2): unpack the bit-packed
repetition / definition level streams and the (frame-of-reference)
bit-packed or byte-packed values.  Per-chunk parameters (entry count, value
bit width, FoR reference) arrive via scalar prefetch; every stream arrives
in the row-window layout of :func:`repro.kernels.bitunpack.row_windows`, so
the kernel body is in-row lane gathers and shifts only.

Coverage (static per call, constant per column):

* ``rep_bits``/``def_bits``: 0 (stream absent) or any width — multi-bit
  definition streams of nested/struct columns decode on device, not just the
  1-bit flat bitmap.
* ``vpe`` (values per entry): 1 for primitives, the list size for
  fixed-size-list chunks — each valid entry owns ``vpe`` consecutive values.
* values: dense little-endian bit stream at any per-chunk width <= 31 bits
  (``bitpack``), or byte-aligned FoR (``bytepack``, width*8 bits) with the
  per-chunk reference added back.

The parameters reach the kernel's scalar memory as one flat ``(3 C,)``
array: a ``(C, 3)`` one pads each row to 128 words there, and a call of a
few thousand chunks (a take across many leaves) would not fit.

Values come back in stream order (the i-th non-null value at slot i), which
is what a reader assembles arrays from; placing them at entry positions is
left to the caller.

VMEM budget: a chunk holds <= 4096 entries, so a grid step keeps the level
tiles at <= 32 KiB each plus the ``(tile_entries * vpe,)`` value tile — the
reader caps ``tile_entries * vpe`` so this stays far inside a TPU core's
VMEM even with double buffering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .bitunpack import LANES, row_windows, unpack_rows

__all__ = ["miniblock_decode_pallas", "MAX_ENTRIES", "MIN_TILE"]

MAX_ENTRIES = 4096  # the format's per-chunk value ceiling (sec 4.2.1)
MIN_TILE = 8 * LANES  # tiles are whole (8, 128) int32 vregs
NAME = "miniblock_decode"  # the kernel's name and its ops' named scope


def _kernel(params_ref, *refs, rep_bits: int, def_bits: int, vpe: int):
    n_in = 1 + bool(rep_bits) + bool(def_bits)
    ins, outs = refs[:n_in], refs[n_in:]
    c = 3 * pl.program_id(0)
    n = params_ref[c]
    bits = params_ref[c + 1].astype(jnp.uint32)
    ref = params_ref[c + 2]

    shape = ins[-1].shape[1:]
    rows = shape[0] // vpe
    entry = (jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0) * LANES
             + jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1))
    in_range = entry < n
    lv = None
    for i, b in enumerate(x for x in (rep_bits, def_bits) if x):
        lv = jnp.where(in_range, unpack_rows(ins[i][0], b).astype(jnp.int32), 0)
        outs[i][0] = lv
    valid = in_range & (lv == 0) if def_bits else in_range
    n_vals = jnp.sum(valid.astype(jnp.int32), axis=(0, 1), keepdims=True) * vpe
    slot = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    vals = unpack_rows(ins[-1][0], bits).astype(jnp.int32) + ref
    outs[-1][0] = jnp.where(slot < n_vals, vals, 0)


@functools.partial(
    jax.jit,
    static_argnames=("rep_bits", "def_bits", "vpe", "tile_entries",
                     "interpret"))
def miniblock_decode_pallas(
    rep_words: jax.Array,  # (C, RW) uint32 (ignored when rep_bits == 0)
    def_words: jax.Array,  # (C, DW) uint32 (ignored when def_bits == 0)
    val_words: jax.Array,  # (C, VW) uint32
    params: jax.Array,  # (C, 3) int32: [n_entries, vbits, ref]
    *,
    rep_bits: int,
    def_bits: int,
    vpe: int = 1,
    tile_entries: int = MAX_ENTRIES,
    interpret: bool,
):
    """Decode C chunks -> (rep, defs, vals) int32 tiles.

    ``rep``/``defs`` are ``(C, tile_entries)`` level streams (zero where the
    stream is absent or past ``n_entries``); ``vals`` is ``(C, tile_entries *
    vpe)``: the chunk's values in stream order, zero past the last one.
    """
    assert tile_entries % MIN_TILE == 0, tile_entries
    C = params.shape[0]
    R = tile_entries // LANES
    with jax.named_scope(NAME):
        streams = [row_windows(w, b, R) for w, b in
                   ((rep_words, rep_bits), (def_words, def_bits)) if b]
        streams.append(row_windows(val_words, params[:, 1], R * vpe))
        n_levels = len(streams) - 1
        spec = lambda r: pl.BlockSpec(  # noqa: E731
            (1, r, LANES), lambda c, p: (c, 0, 0))
        outs = pl.pallas_call(
            functools.partial(_kernel, rep_bits=rep_bits, def_bits=def_bits,
                              vpe=vpe),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(C,),
                in_specs=[spec(s.shape[1]) for s in streams],
                out_specs=[spec(s.shape[1]) for s in streams],
            ),
            out_shape=[jax.ShapeDtypeStruct(s.shape, jnp.int32)
                       for s in streams],
            interpret=interpret,
            name=NAME,
        )(params.reshape(-1), *streams)
        levels = iter(o.reshape(C, tile_entries) for o in outs[:n_levels])
        zeros = jnp.zeros((C, tile_entries), jnp.int32)
        rep = next(levels) if rep_bits else zeros
        defs = next(levels) if def_bits else zeros
        return rep, defs, outs[-1].reshape(C, tile_entries * vpe)
