"""Pallas TPU kernel: full-zip random-access gather ("take").

The paper's full-zip random access is: look up a row's byte range (repetition
index / fixed stride) and issue one IOP for the zipped bytes (§4.1.4).  The
TPU-native translation is a **DMA gather**: each zipped row is one
HBM->HBM copy, so a row is one "IOP" and the kernel body issues no vector
instructions at all.  A grid step starts the copies of ``ROWS_PER_STEP``
rows, whose ids it reads from SMEM, then waits for all of them.

Rows are moved as whole 128-word HBM tiles: each row sits on a leading axis
of its own, padded to a multiple of 512 bytes, since a DMA may not cut a
tile.  :func:`repro.kernels.ops.fullzip_gather` pads and views the bytes as
uint32 words on the host, where the fetched rows already are.

Wired into :meth:`repro.core.fullzip.FullZipReader.take` behind the
``decode="pallas"`` knob: the unique fetched rows are gathered straight into
request order (``rows`` = the request's inverse permutation, duplicates
included), replacing the host fan-out permutation with one device gather.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fullzip_gather_pallas", "ROWS_PER_STEP", "ROW_WORDS"]

ROWS_PER_STEP = 128  # row copies in flight per grid step (one SMEM row of ids)
ROW_WORDS = 128  # a row copy moves whole 128-word HBM tiles
NAME = "fullzip_gather"  # the kernel's name and its ops' named scope


def _kernel(idx_ref, zipped_ref, out_ref, sem):
    base = pl.program_id(0) * ROWS_PER_STEP

    def copy(j):
        return pltpu.make_async_copy(
            zipped_ref.at[idx_ref[0, 0, j]], out_ref.at[base + j], sem.at[0])

    def start(j, carry):
        copy(j).start()
        return carry

    def wait(j, carry):
        copy(j).wait()
        return carry

    # rolled loops: unrolled, the 128 copies cost ~0.6 s of tracing per shape
    jax.lax.fori_loop(0, ROWS_PER_STEP, start, 0)
    jax.lax.fori_loop(0, ROWS_PER_STEP, wait, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fullzip_gather_pallas(
    words: jax.Array,  # (n_rows, W) uint32 zipped rows, W % ROW_WORDS == 0
    rows: jax.Array,  # (n_take,) int32 row ids (from the repetition index)
    *,
    interpret: bool,
) -> jax.Array:
    """``words[rows]``, one DMA per row."""
    n_rows, width = words.shape
    assert width % ROW_WORDS == 0, width
    n_take = rows.shape[0]
    steps = max(1, -(-n_take // ROWS_PER_STEP))
    with jax.named_scope(NAME):
        ids = jnp.zeros(steps * ROWS_PER_STEP, jnp.int32).at[:n_take].set(
            rows.astype(jnp.int32)).reshape(steps, 1, ROWS_PER_STEP)
        out = pl.pallas_call(
            _kernel,
            grid=(steps,),
            in_specs=[
                pl.BlockSpec((1, 1, ROWS_PER_STEP), lambda i: (i, 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=jax.ShapeDtypeStruct((steps * ROWS_PER_STEP, 1, width),
                                           jnp.uint32),
            scratch_shapes=[pltpu.SemaphoreType.DMA((1,))],
            interpret=interpret,
            name=NAME,
        )(ids, words.reshape(n_rows, 1, width))
        return out.reshape(-1, width)[:n_take]
