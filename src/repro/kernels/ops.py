"""Public kernel entry points.

Each op dispatches to the Pallas TPU kernel or to the pure-jnp oracle in
``ref.py``.  On a TPU the kernel lowers to Mosaic; on any other backend it
runs in interpret mode, so the CPU test suite executes the kernel *body*.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .bitunpack import VALS_PER_BLOCK, bitunpack_pallas
from .fullzip_gather import ROW_WORDS, fullzip_gather_pallas
from .ivf_topk import K_PAD, QUERY_TILE, cand_tile, ivf_topk_pallas
from .miniblock_decode import MAX_ENTRIES, miniblock_decode_pallas
from .ref import IVF_ID_SENTINEL

__all__ = [
    "bitunpack",
    "miniblock_decode",
    "fullzip_gather",
    "ivf_topk",
    "pack_words",
    "pow2",
    "on_tpu",
    "IVF_ID_SENTINEL",
]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pack_words(buf: np.ndarray, pad_words: int = 1) -> np.ndarray:
    """uint8 packed stream -> uint32 little-endian words (host helper)."""
    b = np.asarray(buf, np.uint8)
    pad = (-len(b)) % 4
    b = np.pad(b, (0, pad))
    w = b.view(np.uint32)
    if pad_words:
        w = np.pad(w, (0, pad_words))
    return w


def bitunpack(words: jax.Array, n: int, bits: int, *, use_pallas: bool = True) -> jax.Array:
    """Unpack ``n`` ``bits``-wide values from a uint32 word stream."""
    if not use_pallas:
        return ref.bitunpack_ref(words, n, bits)
    wpb = VALS_PER_BLOCK * bits // 32
    n_blocks = max(1, -(-n // VALS_PER_BLOCK))
    need = n_blocks * wpb
    w = jnp.pad(words, (0, max(0, need - words.shape[0])))[:need]
    out = bitunpack_pallas(w, bits, interpret=not on_tpu())
    return out[:n]


def miniblock_decode(
    rep_words: jax.Array,
    def_words: jax.Array,
    val_words: jax.Array,
    params: jax.Array,
    *,
    rep_bits: int,
    def_bits: int,
    vpe: int = 1,
    tile_entries: int = MAX_ENTRIES,
    use_pallas: bool = True,
):
    """Decode C mini-block chunks -> ``(rep, defs, vals)`` int32 tiles.

    ``rep``/``defs`` are ``(C, tile_entries)``, zero past a chunk's
    ``n_entries``; ``vals`` is ``(C, tile_entries * vpe)``: the chunk's
    values in stream order (``vpe`` values per valid entry — fixed-size-list
    chunks set it to the list size), zero past the last one.
    ``tile_entries`` is a multiple of 1024.
    """
    if not use_pallas:
        return ref.miniblock_decode_ref(
            rep_words, def_words, val_words,
            params[:, 0], params[:, 1], params[:, 2],
            tile_entries, rep_bits, def_bits, vpe,
        )
    return miniblock_decode_pallas(
        rep_words, def_words, val_words, params,
        rep_bits=rep_bits, def_bits=def_bits, vpe=vpe,
        tile_entries=tile_entries, interpret=not on_tpu(),
    )


def fullzip_gather(zipped, rows, *, use_pallas: bool = True):
    """Gather zipped fixed-stride rows (the §4.1 take path).

    ``zipped`` is (n_rows, ...) of any dtype (the take path's rows are
    uint8 [control word | value bytes]), ``rows`` (n_take,) int; returns
    ``zipped[rows]``.  The Pallas route moves each row's bytes as padded
    uint32 words, which are built and taken apart on the host.
    """
    if not use_pallas:
        return ref.fullzip_gather_ref(zipped, rows)
    z = np.ascontiguousarray(np.asarray(zipped))
    row = z.reshape(len(z), -1).view(np.uint8)
    nbytes = row.shape[1]
    # row counts round up to powers of two so takes share compiled shapes
    padded = np.zeros((pow2(len(z)),
                       -(-nbytes // (4 * ROW_WORDS)) * 4 * ROW_WORDS), np.uint8)
    padded[: len(z), :nbytes] = row
    ids = np.zeros(pow2(len(rows)), np.int32)
    ids[: len(rows)] = np.asarray(rows)
    out = fullzip_gather_pallas(jnp.asarray(padded.view(np.uint32)),
                                jnp.asarray(ids), interpret=not on_tpu())
    got = np.ascontiguousarray(
        np.asarray(out)[: len(rows)].view(np.uint8)[:, :nbytes])
    return got.view(z.dtype).reshape((-1,) + z.shape[1:])


def pow2(n: int) -> int:
    """Smallest power of two >= n: padded sizes that share compiled shapes."""
    return 1 << max(0, int(n) - 1).bit_length()


def _ivf_pad(queries, cands, ids, mask):
    """Pad (queries, cands, ids, mask) to the kernel's static tiling:
    query rows to a multiple of 8, dims to a multiple of 128, candidates to
    a multiple of the kernel's candidate tile.  Zero dim-padding is
    L2-exact; padded candidate columns are masked out and carry the id
    sentinel."""
    q2 = np.atleast_2d(np.asarray(queries))
    c2 = np.atleast_2d(np.asarray(cands))
    qn, d = q2.shape
    n = c2.shape[0]
    qp = -(-max(qn, 1) // QUERY_TILE) * QUERY_TILE
    dp = -(-max(d, 1) // 128) * 128
    tn = cand_tile(n, dp)
    np_ = tn * pow2(-(-max(n, 1) // tn))  # a power of two of tiles
    qpad = np.zeros((qp, dp), q2.dtype)
    qpad[:qn, :d] = q2
    cpad = np.zeros((np_, dp), c2.dtype)
    cpad[:n, :d] = c2
    idp = np.full((1, np_), IVF_ID_SENTINEL,
                  np.asarray(ids).dtype if np.asarray(ids).size else np.int32)
    idp[0, :n] = np.asarray(ids).reshape(-1)
    mpad = np.zeros((qp, np_), np.int32)
    if mask is None:
        mpad[:qn, :n] = 1
    else:
        mpad[:qn, :n] = np.asarray(mask, np.int32).reshape(qn, n)
    return q2, qpad, cpad, idp, mpad


def ivf_topk(queries, cands, ids, k: int, mask=None, *,
             use_pallas: bool = True, tracer=None):
    """Batched squared-L2 distance + deterministic top-k over one shared
    candidate matrix (the IVF search hot loop).

    ``queries``: (Q, D) or (D,); ``cands``: (N, D); ``ids``: (N,)
    candidate row ids; ``mask``: optional (Q, N) per-query eligibility
    (1 = candidate in one of this query's probed partitions).  Returns
    ``(dists, winners)`` of shape (Q, k) — ties break toward the lowest
    row id, entries past a query's eligible count hold
    ``(inf, IVF_ID_SENTINEL)``.  Both routes agree with a float64 reference
    within :func:`repro.kernels.ref.topk_tolerance` (see
    :func:`repro.kernels.ref.topk_mismatches`), not bit for bit: they sum
    in different orders.

    Dispatches to the Pallas kernel when eligible (float32 vectors, ids
    within 31 bits, k <= 128, at least one candidate); otherwise falls
    back to the jnp oracle and reports the structured reason through
    ``tracer`` as a ``decode.fallback.ivf.<reason>`` counter — the same
    no-silent-fallback contract as the decode kernels.
    """
    k = int(k)
    if k < 1:
        raise ValueError("k must be positive")
    q2 = np.atleast_2d(np.asarray(queries))
    c2 = np.atleast_2d(np.asarray(cands))
    ids_arr = np.asarray(ids).reshape(-1)
    qn, n = q2.shape[0], c2.shape[0]
    reason = None
    if q2.dtype != np.float32 or c2.dtype != np.float32:
        reason = "non-float32"
    elif n == 0:
        reason = "no-candidates"
    elif k > K_PAD:
        reason = f">{K_PAD}-k"
    elif ids_arr.size and int(ids_arr.max()) >= IVF_ID_SENTINEL:
        reason = ">31-bit-ids"
    wide = reason == ">31-bit-ids"
    if wide:
        # jnp is int32 on CPU: select over *positions* of the candidates
        # sorted by id (position tie-break == id tie-break) and map back
        order = np.argsort(ids_arr, kind="stable")
        c2 = c2[order]
        if mask is not None:
            mask = np.atleast_2d(np.asarray(mask))[:, order]
        ids_sorted, ids_run = ids_arr[order], np.arange(n, dtype=np.int32)
    else:
        ids_run = ids_arr if ids_arr.dtype == np.int32 \
            else ids_arr.astype(np.int32)
    _, qpad, cpad, idp, mpad = _ivf_pad(q2, c2, ids_run, mask)
    if not use_pallas or reason is not None:
        if use_pallas and tracer is not None:
            tracer.fallback("ivf", reason, n_queries=qn, n_candidates=n, k=k)
        d, w = ref.ivf_topk_ref(jnp.asarray(qpad), jnp.asarray(cpad),
                                jnp.asarray(idp), jnp.asarray(mpad),
                                k, kp=max(K_PAD, k))
    else:
        d, w = ivf_topk_pallas(jnp.asarray(qpad), jnp.asarray(cpad),
                               jnp.asarray(idp), jnp.asarray(mpad),
                               k=k, interpret=not on_tpu())
    d, w = d[:qn, :k], w[:qn, :k]
    if wide:
        wnp = np.asarray(w)
        w = np.where(wnp == IVF_ID_SENTINEL, np.int64(IVF_ID_SENTINEL),
                     ids_sorted[np.minimum(wnp, n - 1)])
    return d, w
