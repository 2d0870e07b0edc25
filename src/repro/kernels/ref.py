"""Pure-jnp oracles for every Pallas kernel, and the float64 contract of
the distance/top-k kernel.

The oracles are the ground truth the decode kernels are validated against
bit for bit (shape/dtype sweeps in ``tests/test_kernels.py``) and the
``decode="numpy"`` route of the search path.  ``ivf_topk`` sums in float32,
so both of its routes are held instead to a float64 reference within a
stated tolerance (:func:`topk_mismatches`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["bitunpack_ref", "miniblock_decode_ref", "fullzip_gather_ref",
           "ivf_topk_ref", "topk_tolerance", "topk_mismatches",
           "IVF_ID_SENTINEL"]

# Padding / exhaustion marker for ivf_topk: never a valid row id (global row
# ids are dispatch-checked to fit in 31 bits), and maximal so the
# min-id tie-break never prefers it over a real candidate.
IVF_ID_SENTINEL = (1 << 31) - 1


@functools.partial(jax.jit, static_argnums=(1, 2))
def bitunpack_ref(words: jax.Array, n: int, bits: int) -> jax.Array:
    """Unpack ``n`` little-endian ``bits``-wide values from uint32 words."""
    j = jnp.arange(n, dtype=jnp.uint32)
    bitpos = j * jnp.uint32(bits)
    w = (bitpos // 32).astype(jnp.int32)
    sh = bitpos % 32
    w0 = words[w]
    w1 = words[jnp.minimum(w + 1, words.shape[0] - 1)]
    hi_shift = (jnp.uint32(32) - sh) & jnp.uint32(31)
    hi = jnp.where(sh > 0, w1 << hi_shift, jnp.uint32(0))
    mask = jnp.uint32((1 << bits) - 1) if bits < 32 else jnp.uint32(0xFFFFFFFF)
    return ((w0 >> sh) | hi) & mask


def _extract_ref(words: jax.Array, bitpos: jax.Array, bits, mask) -> jax.Array:
    """Little-endian dynamic-width field extraction from uint32 words."""
    w = (bitpos // 32).astype(jnp.int32)
    sh = bitpos % 32
    w0 = words[w]
    w1 = words[jnp.minimum(w + 1, words.shape[0] - 1)]
    hi_shift = (jnp.uint32(32) - sh) & jnp.uint32(31)
    hi = jnp.where(sh > 0, w1 << hi_shift, jnp.uint32(0))
    return ((w0 >> sh) | hi) & mask


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def miniblock_decode_ref(
    rep_words: jax.Array,  # (C, RW) uint32 bit-packed rep levels (dummy if absent)
    def_words: jax.Array,  # (C, DW) uint32 bit-packed def levels (dummy if absent)
    val_words: jax.Array,  # (C, VW) uint32 bit/byte-packed FoR values
    n_entries: jax.Array,  # (C,) int32 valid entries per chunk
    vbits: jax.Array,  # (C,) int32 value bit width per chunk
    refs: jax.Array,  # (C,) int32 frame-of-reference per chunk
    max_entries: int,
    rep_bits: int,
    def_bits: int,
    vpe: int = 1,
):
    """Decode C mini-block chunks -> ``(rep, defs, vals)`` int32 tiles.

    Models the §4.2 decode for integer chunks: per chunk, unpack the rep/def
    level streams (widths are column constants; 0 = stream absent; zero past
    ``n_entries``) and the packed values — ``vpe`` consecutive values per
    non-null entry (fixed-size lists set ``vpe`` to the list size), in
    stream order, FoR reference added, zero past the last value.  Ground
    truth for the Pallas kernel.
    """

    def one(rw, dw, vw, n, bits, ref):
        j = jnp.arange(max_entries, dtype=jnp.uint32)
        in_range = j < n.astype(jnp.uint32)
        if rep_bits:
            rep = _extract_ref(rw, j * jnp.uint32(rep_bits),
                               jnp.uint32(rep_bits),
                               jnp.uint32((1 << rep_bits) - 1))
            rep = jnp.where(in_range, rep.astype(jnp.int32), 0)
        else:
            rep = jnp.zeros(max_entries, jnp.int32)
        if def_bits:
            d = _extract_ref(dw, j * jnp.uint32(def_bits),
                             jnp.uint32(def_bits),
                             jnp.uint32((1 << def_bits) - 1))
            valid = (d == 0) & in_range
            d = jnp.where(in_range, d.astype(jnp.int32), 0)
        else:
            valid = in_range
            d = jnp.zeros(max_entries, jnp.int32)
        n_vals = jnp.sum(valid.astype(jnp.int32)) * vpe
        slot = jnp.arange(max_entries * vpe, dtype=jnp.uint32)
        mask = jnp.where(
            bits >= 32, jnp.uint32(0xFFFFFFFF),
            (jnp.uint32(1) << bits.astype(jnp.uint32)) - 1)
        vals = _extract_ref(vw, slot * bits.astype(jnp.uint32), bits, mask)
        out = jnp.where(slot < n_vals.astype(jnp.uint32),
                        vals.astype(jnp.int32) + ref, 0)
        return rep, d, out

    return jax.vmap(one)(rep_words, def_words, val_words, n_entries, vbits, refs)


@functools.partial(jax.jit, static_argnames=("k", "kp"))
def ivf_topk_ref(queries: jax.Array, cands: jax.Array, ids: jax.Array,
                 mask: jax.Array, k: int, kp: int = 128):
    """Batched squared-L2 distance + deterministic top-k selection.

    ``queries``: (Q, D) float; ``cands``: (N, D) float; ``ids``: (1, N)
    int32 candidate row ids (``IVF_ID_SENTINEL`` in padding); ``mask``:
    (Q, N) int32 — 1 where candidate n is eligible for query q (IVF probes
    different partitions per query over one shared candidate matrix), 0
    where it is not (and in padding columns).

    Returns ``(dists, winners)`` of shape (Q, kp): entry j is the j-th
    nearest eligible candidate, ties broken toward the *lowest row id*
    (independent of candidate order); entries past the eligible count — and
    columns >= k — hold ``(inf, IVF_ID_SENTINEL)``.  The dot product runs at
    full f32 precision, which is not the TPU default.
    """
    acc = queries.dtype
    qq = jnp.sum(queries * queries, axis=1, keepdims=True)        # (Q, 1)
    cc = jnp.sum(cands * cands, axis=1)[None, :]                  # (1, N)
    dot = jnp.dot(queries, cands.T, precision=jax.lax.Precision.HIGHEST,
                  preferred_element_type=acc)                     # (Q, N)
    d = qq - 2.0 * dot + cc
    eligible = mask != 0
    d = jnp.where(eligible, d, jnp.inf).astype(acc)
    # the kernel route is int32-only; the ref also backs the >31-bit-id
    # fallback, where the sentinel has to stay maximal in the wider dtype
    sent = IVF_ID_SENTINEL if ids.dtype == jnp.int32 \
        else jnp.iinfo(ids.dtype).max
    idrow = jnp.where(eligible, ids, sent)                        # (Q, N)
    colk = jax.lax.broadcasted_iota(jnp.int32, (queries.shape[0], kp), 1)
    out_d = jnp.full((queries.shape[0], kp), jnp.inf, acc)
    out_i = jnp.full((queries.shape[0], kp), sent, ids.dtype)
    for j in range(k):
        m = jnp.min(d, axis=1, keepdims=True)                     # (Q, 1)
        tie = jnp.where(d == m, idrow, sent)
        wid = jnp.min(tie, axis=1, keepdims=True)                 # (Q, 1)
        out_d = jnp.where(colk == j, m, out_d)
        out_i = jnp.where(colk == j, wid, out_i)
        sel = (d == m) & (idrow == wid)
        d = jnp.where(sel, jnp.inf, d)
        idrow = jnp.where(sel, sent, idrow)
    return out_d, out_i


def topk_tolerance(queries, cands) -> np.ndarray:
    """Per-query bound on |float32 distance - exact distance|.

    ``|q|^2 - 2 q.c + |c|^2`` sums ``D`` products per term; each term errs
    by at most ``D * u`` times the sum of its magnitudes (``u`` = 2^-24, the
    f32 unit roundoff), the magnitudes add up to at most ``(|q| + |c|)^2``,
    and the two final additions add ``2 u`` of the same.  The bound below
    takes ``eps = 2u`` (a factor 2 of margin for the TPU's multi-pass f32
    matmul) and the largest candidate norm: ``(D + 2) eps (|q| + max|c|)^2``.
    """
    q = np.atleast_2d(np.asarray(queries, np.float64))
    c = np.atleast_2d(np.asarray(cands, np.float64))
    cmax = np.sqrt((c * c).sum(1).max()) if len(c) else 0.0
    scale = (np.sqrt((q * q).sum(1)) + cmax) ** 2
    return (q.shape[1] + 2) * float(np.finfo(np.float32).eps) * scale


def topk_mismatches(queries, cands, ids, k: int, dists, winners,
                    mask=None, sentinel: int = IVF_ID_SENTINEL) -> list:
    """How ``(dists, winners)`` departs from float64 top-k (empty = agrees).

    The contract of both ``ivf_topk`` routes, with ``tol`` from
    :func:`topk_tolerance`: each query returns ``min(k, eligible)`` distinct
    eligible ids, then ``(inf, sentinel)``; each returned distance is
    within ``tol`` of the exact distance of its id and never below the
    previous one; and the winners are the exact top-k except for ties
    inside the tolerance — a winner the exact ranking leaves out, and an
    exact winner that is missing, both lie within ``2 tol`` of the exact
    k-th distance.
    """
    q = np.atleast_2d(np.asarray(queries, np.float64))
    c = np.atleast_2d(np.asarray(cands, np.float64))
    ids = np.asarray(ids).reshape(-1)
    d_got = np.atleast_2d(np.asarray(dists, np.float64))
    w_got = np.atleast_2d(np.asarray(winners))
    exact = ((q * q).sum(1)[:, None] - 2.0 * q @ c.T + (c * c).sum(1)[None])
    elig = (np.ones(exact.shape, bool) if mask is None
            else np.atleast_2d(np.asarray(mask)).astype(bool))
    tol = topk_tolerance(q, c)
    pos = {int(i): p for p, i in enumerate(ids)}
    bad = []
    for qi in range(q.shape[0]):
        cand = np.flatnonzero(elig[qi])
        order = cand[np.lexsort((ids[cand], exact[qi, cand]))]
        kk = min(k, len(order))
        w, d = w_got[qi], d_got[qi]
        if (w[kk:] != sentinel).any() or not np.isinf(d[kk:]).all():
            bad.append(f"q{qi}: expected {kk} winners then sentinels")
            continue
        got = [pos.get(int(x), -1) for x in w[:kk]]
        if min(got, default=0) < 0 or len(set(got)) < kk \
                or not elig[qi, got].all():
            bad.append(f"q{qi}: winners not distinct eligible ids: {w[:kk]}")
            continue
        err = np.abs(d[:kk] - exact[qi, got])
        if (err > tol[qi]).any():
            bad.append(f"q{qi}: distance error {err.max()} > tol {tol[qi]}")
        if (np.diff(d[:kk]) < 0).any():
            bad.append(f"q{qi}: distances not ascending")
        if kk:
            kth = exact[qi, order[kk - 1]]
            extra = set(got) - set(order[:kk].tolist())
            missed = set(order[:kk].tolist()) - set(got)
            if any(exact[qi, p] > kth + 2 * tol[qi] for p in extra) or \
                    any(exact[qi, p] < kth - 2 * tol[qi] for p in missed):
                bad.append(f"q{qi}: winners differ beyond ties: "
                           f"extra {sorted(ids[list(extra)])}, "
                           f"missed {sorted(ids[list(missed)])}")
    return bad


def fullzip_gather_ref(zipped: jax.Array, rows: jax.Array) -> jax.Array:
    """Random-access take on a fixed-stride full-zip buffer.

    ``zipped``: (n_rows, stride) uint8 — each row is [control word | value
    bytes].  ``rows``: (n_take,) int32.  One gathered row ≙ the paper's
    "1 IOP for fixed-width random access"; on TPU it is one DMA per row.
    """
    return zipped[rows]
