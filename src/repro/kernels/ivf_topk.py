"""Pallas TPU kernel: batched IVF distance + top-k selection.

The grid is (query tiles, candidate tiles).  One step scores an 8-row
query tile against one tile of the candidate matrix (the posting lists of
every probed partition, concatenated by the search path): squared-L2
distances via one MXU matmul at f32 precision, then ``k`` masked-argmin
sweeps that merge the tile into the query tile's running top-k, kept in the
output block across the candidate axis.  Ties break toward the lowest
candidate row id, so the winner set does not depend on how the posting
lists happened to be ordered on disk.  Since only one candidate tile sits in
VMEM at a time, the candidate count is bounded by HBM, not VMEM.

The per-query eligibility ``mask`` is what makes one shared candidate
matrix serve a *batch* of IVF queries: each query probes its own
``nprobe`` partitions, so a candidate fetched for query A may be out of
scope for query B; masked (and padding) entries score ``+inf`` and carry
the id sentinel, which the selection sweep can never prefer.

Inputs are pre-padded by :func:`repro.kernels.ops.ivf_topk` (queries to a
multiple of 8 rows, candidates to a multiple of the candidate tile, dims to
a multiple of 128) so the BlockSpec tiling is static.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import IVF_ID_SENTINEL

__all__ = ["ivf_topk_pallas", "cand_tile", "QUERY_TILE", "K_PAD"]

QUERY_TILE = 8   # f32 min sublane tile: one grid step scores 8 queries
K_PAD = 128      # output lane width; k <= K_PAD, columns >= k are sentinel
NAME = "ivf_topk"  # the kernel's name and its ops' named scope
_TILE_BYTES = 2 << 20  # one candidate tile in VMEM (double-buffered)


def cand_tile(n: int, dp: int) -> int:
    """Candidate rows per grid step for ``n`` candidates of padded width
    ``dp``: a multiple of 128 holding at most ``_TILE_BYTES``."""
    cap = max(128, _TILE_BYTES // (4 * dp) // 128 * 128)
    return min(cap, -(-max(n, 1) // 128) * 128)


def _kernel(q_ref, c_ref, cc_ref, id_ref, m_ref, out_d_ref, out_i_ref, *,
            k: int):
    @pl.when(pl.program_id(1) == 0)
    def _():
        out_d_ref[...] = jnp.full(out_d_ref.shape, jnp.inf, jnp.float32)
        out_i_ref[...] = jnp.full(out_i_ref.shape, IVF_ID_SENTINEL, jnp.int32)

    q = q_ref[...]                     # (QT, Dp) f32
    dot = jax.lax.dot_general(         # (QT, TN)
        q, c_ref[...], (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    d = jnp.sum(q * q, axis=1, keepdims=True) - 2.0 * dot + cc_ref[...]
    eligible = m_ref[...] != 0
    d = jnp.where(eligible, d, jnp.inf)
    idrow = jnp.where(eligible, id_ref[...], IVF_ID_SENTINEL)
    # running top-k of the candidate tiles seen so far
    run_d, run_i = out_d_ref[...], out_i_ref[...]
    colk = jax.lax.broadcasted_iota(jnp.int32, run_d.shape, 1)
    out_d = jnp.full(run_d.shape, jnp.inf, jnp.float32)
    out_i = jnp.full(run_i.shape, IVF_ID_SENTINEL, jnp.int32)
    for j in range(k):
        m = jnp.minimum(jnp.min(d, axis=1, keepdims=True),
                        jnp.min(run_d, axis=1, keepdims=True))
        wid = jnp.minimum(
            jnp.min(jnp.where(d == m, idrow, IVF_ID_SENTINEL), axis=1,
                    keepdims=True),
            jnp.min(jnp.where(run_d == m, run_i, IVF_ID_SENTINEL), axis=1,
                    keepdims=True))
        out_d = jnp.where(colk == j, m, out_d)
        out_i = jnp.where(colk == j, wid, out_i)
        sel = (d == m) & (idrow == wid)
        d = jnp.where(sel, jnp.inf, d)
        idrow = jnp.where(sel, IVF_ID_SENTINEL, idrow)
        sel = (run_d == m) & (run_i == wid)
        run_d = jnp.where(sel, jnp.inf, run_d)
        run_i = jnp.where(sel, IVF_ID_SENTINEL, run_i)
    out_d_ref[...] = out_d
    out_i_ref[...] = out_i


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def ivf_topk_pallas(queries: jax.Array, cands: jax.Array, ids: jax.Array,
                    mask: jax.Array, *, k: int, interpret: bool):
    """(Qp, Dp) f32 queries x (Np, Dp) f32 candidates -> top-k per query.

    ``ids`` is (1, Np) int32, ``mask`` (Qp, Np) int32; all shapes
    pre-padded (Qp % 8 == Dp % 128 == 0, Np a multiple of
    :func:`cand_tile`, sentinel/zero in the padding).  Returns ``(dists,
    winners)`` of shape (Qp, K_PAD) with the selection semantics of
    :func:`repro.kernels.ref.ivf_topk_ref`.
    """
    qp, dp = queries.shape
    np_ = cands.shape[0]
    tn = cand_tile(np_, dp)
    assert qp % QUERY_TILE == 0 and dp % 128 == 0 and np_ % tn == 0
    assert 1 <= k <= K_PAD
    with jax.named_scope(NAME):
        cc = jnp.sum(cands * cands, axis=1)[None, :]                # (1, Np)
        out_spec = pl.BlockSpec((QUERY_TILE, K_PAD), lambda i, j: (i, 0))
        return pl.pallas_call(
            functools.partial(_kernel, k=k),
            grid=(qp // QUERY_TILE, np_ // tn),
            in_specs=[
                pl.BlockSpec((QUERY_TILE, dp), lambda i, j: (i, 0)),
                pl.BlockSpec((tn, dp), lambda i, j: (j, 0)),
                pl.BlockSpec((1, tn), lambda i, j: (0, j)),
                pl.BlockSpec((1, tn), lambda i, j: (0, j)),
                pl.BlockSpec((QUERY_TILE, tn), lambda i, j: (i, j)),
            ],
            out_specs=[out_spec, out_spec],
            out_shape=[
                jax.ShapeDtypeStruct((qp, K_PAD), jnp.float32),
                jax.ShapeDtypeStruct((qp, K_PAD), jnp.int32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
            name=NAME,
        )(queries, cands, cc, ids, mask)
