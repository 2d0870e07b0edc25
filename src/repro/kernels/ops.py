"""Public kernel entry points.

Each op dispatches to the Pallas TPU kernel or to the pure-jnp oracle in
``ref.py``.  On a TPU the kernel lowers to Mosaic; on any other backend it
runs in interpret mode, so the CPU test suite executes the kernel *body*.

Kernel dispatch is traced step by step on the caller's tracer (``tracer=``;
the no-op :data:`~repro.obs.NULL_TRACER` by default), each span named
``kernel.<step>:<kernel>`` (:class:`KernelSpans`): ``pack`` (host padding
and packing), ``h2d`` (the inputs' transfer to the device), ``launch`` (the
dispatch of the jitted program), ``wait`` (an explicit
``jax.block_until_ready`` on the outputs), ``d2h`` (their copy to the host)
and ``unpack`` (slicing and views back).  Where the caller copies the outputs
back (``ivf_topk``, ``miniblock_decode``, ``bitunpack`` return device
arrays), the caller opens ``wait``, ``d2h`` and ``unpack``.  An enabled
tracer also counts the bytes each kernel moves: ``kernel.bytes_h2d.<kernel>``
and ``kernel.bytes_d2h.<kernel>`` (padded, as transferred) and
``kernel.bytes_true.<kernel>`` (the call's true bytes, read and written).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import NULL_TRACER
from . import ref
from .bitunpack import VALS_PER_BLOCK, bitunpack_pallas
from .fullzip_gather import ROW_WORDS, fullzip_gather_pallas
from .ivf_topk import K_PAD, QUERY_TILE, cand_tile, ivf_topk_pallas
from .miniblock_decode import MAX_ENTRIES, miniblock_decode_pallas
from .ref import IVF_ID_SENTINEL

__all__ = [
    "KernelSpans",
    "IVF_TOPK",
    "MINIBLOCK_DECODE",
    "FULLZIP_GATHER",
    "BITUNPACK",
    "bitunpack",
    "miniblock_decode",
    "fullzip_gather",
    "ivf_topk",
    "pack_words",
    "pow2",
    "on_tpu",
    "IVF_ID_SENTINEL",
]


class KernelSpans(NamedTuple):
    """The span names of one kernel's dispatch steps."""

    kernel: str
    pack: str
    h2d: str
    launch: str
    wait: str
    d2h: str
    unpack: str

    @classmethod
    def of(cls, kernel: str) -> "KernelSpans":
        return cls(kernel, *(f"kernel.{step}:{kernel}"
                             for step in cls._fields[1:]))

    def count(self, tracer, h2d: int = 0, d2h: int = 0, true: int = 0) -> None:
        """Add the call's bytes to the kernel's transfer counters (call only
        with ``tracer.enabled``)."""
        for kind, n in (("h2d", h2d), ("d2h", d2h), ("true", true)):
            if n:
                tracer.count(f"kernel.bytes_{kind}.{self.kernel}", int(n))


IVF_TOPK = KernelSpans.of("ivf_topk")
MINIBLOCK_DECODE = KernelSpans.of("miniblock_decode")
FULLZIP_GATHER = KernelSpans.of("fullzip_gather")
BITUNPACK = KernelSpans.of("bitunpack")


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _host_bytes(*arrays) -> int:
    """Bytes of the host arrays among ``arrays``: what ``jnp.asarray``
    copies to the device (device arrays stay where they are)."""
    return sum(a.nbytes for a in arrays if isinstance(a, np.ndarray))


def pack_words(buf: np.ndarray, pad_words: int = 1) -> np.ndarray:
    """uint8 packed stream -> uint32 little-endian words (host helper)."""
    b = np.asarray(buf, np.uint8)
    pad = (-len(b)) % 4
    b = np.pad(b, (0, pad))
    w = b.view(np.uint32)
    if pad_words:
        w = np.pad(w, (0, pad_words))
    return w


def bitunpack(words: jax.Array, n: int, bits: int, *, use_pallas: bool = True,
              tracer=None) -> jax.Array:
    """Unpack ``n`` ``bits``-wide values from a uint32 word stream."""
    if not use_pallas:
        return ref.bitunpack_ref(words, n, bits)
    tracer = tracer or NULL_TRACER
    sp = BITUNPACK
    wpb = VALS_PER_BLOCK * bits // 32
    n_blocks = max(1, -(-n // VALS_PER_BLOCK))
    need = n_blocks * wpb
    with tracer.span(sp.h2d):
        w = jnp.asarray(words)
    with tracer.span(sp.launch):
        w = jnp.pad(w, (0, max(0, need - w.shape[0])))[:need]
        out = bitunpack_pallas(w, bits, interpret=not on_tpu())[:n]
    if tracer.enabled:
        sp.count(tracer, h2d=_host_bytes(words),
                 true=-(-n * bits // 8) + 4 * n)
    return out


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
    tracer=None,
):
    """Decode C mini-block chunks -> ``(rep, defs, vals)`` int32 tiles.

    ``rep``/``defs`` are ``(C, tile_entries)``, zero past a chunk's
    ``n_entries``; ``vals`` is ``(C, tile_entries * vpe)``: the chunk's
    values in stream order (``vpe`` values per valid entry — fixed-size-list
    chunks set it to the list size), zero past the last one.
    ``tile_entries`` is a multiple of 1024.  The outputs stay on the device:
    the caller waits for and copies them (``kernel.wait``/``d2h``/``unpack``
    of :data:`MINIBLOCK_DECODE`).
    """
    if not use_pallas:
        return ref.miniblock_decode_ref(
            rep_words, def_words, val_words,
            params[:, 0], params[:, 1], params[:, 2],
            tile_entries, rep_bits, def_bits, vpe,
        )
    tracer = tracer or NULL_TRACER
    sp = MINIBLOCK_DECODE
    args = (rep_words, def_words, val_words, params)
    with tracer.span(sp.h2d):
        ins = [jnp.asarray(a) for a in args]
    with tracer.span(sp.launch):
        out = miniblock_decode_pallas(
            *ins, rep_bits=rep_bits, def_bits=def_bits, vpe=vpe,
            tile_entries=tile_entries, interpret=not on_tpu(),
        )
    if tracer.enabled:
        sp.count(tracer, h2d=_host_bytes(*args))
    return out


def fullzip_gather(zipped, rows, *, use_pallas: bool = True, tracer=None):
    """Gather zipped fixed-stride rows (the §4.1 take path).

    ``zipped`` is (n_rows, ...) of any dtype (the take path's rows are
    uint8 [control word | value bytes]), ``rows`` (n_take,) int; returns
    ``zipped[rows]``.  The Pallas route moves each row's bytes as padded
    uint32 words, which are built and taken apart on the host.
    """
    if not use_pallas:
        return ref.fullzip_gather_ref(zipped, rows)
    tracer = tracer or NULL_TRACER
    sp = FULLZIP_GATHER
    with tracer.span(sp.pack):
        z = np.ascontiguousarray(np.asarray(zipped))
        row = z.reshape(len(z), -1).view(np.uint8)
        nbytes = row.shape[1]
        # row counts round up to powers of two so takes share compiled shapes
        padded = np.zeros((pow2(len(z)),
                           -(-nbytes // (4 * ROW_WORDS)) * 4 * ROW_WORDS),
                          np.uint8)
        padded[: len(z), :nbytes] = row
        ids = np.zeros(pow2(len(rows)), np.int32)
        ids[: len(rows)] = np.asarray(rows)
    with tracer.span(sp.h2d):
        words_d, ids_d = jnp.asarray(padded.view(np.uint32)), jnp.asarray(ids)
    with tracer.span(sp.launch):
        out = fullzip_gather_pallas(words_d, ids_d, interpret=not on_tpu())
    with tracer.span(sp.wait):
        out = jax.block_until_ready(out)
    with tracer.span(sp.d2h):
        out = np.asarray(out)
    with tracer.span(sp.unpack):
        got = np.ascontiguousarray(out[: len(rows)].view(np.uint8)[:, :nbytes])
        got = got.view(z.dtype).reshape((-1,) + z.shape[1:])
    if tracer.enabled:
        # true bytes as the roofline counts them: each row read and written
        sp.count(tracer, h2d=padded.nbytes + ids.nbytes, d2h=out.nbytes,
                 true=2 * len(rows) * nbytes)
    return got


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
    no-silent-fallback contract as the decode kernels.  The outputs stay on
    the device: the caller waits for and copies them (``kernel.wait``/
    ``d2h``/``unpack`` of :data:`IVF_TOPK`).
    """
    k = int(k)
    if k < 1:
        raise ValueError("k must be positive")
    tracer = tracer or NULL_TRACER
    sp = IVF_TOPK
    with tracer.span(sp.pack):
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
    with tracer.span(sp.h2d):
        ins = [jnp.asarray(a) for a in (qpad, cpad, idp, mpad)]
    with tracer.span(sp.launch):
        if not use_pallas or reason is not None:
            if use_pallas:
                tracer.fallback("ivf", reason, n_queries=qn, n_candidates=n,
                                k=k)
            d, w = ref.ivf_topk_ref(*ins, k, kp=max(K_PAD, k))
        else:
            d, w = ivf_topk_pallas(*ins, k=k, interpret=not on_tpu())
        d, w = d[:qn, :k], w[:qn, :k]
    if tracer.enabled:
        # true bytes as the roofline counts them: queries, candidates and
        # ids at the true shapes, and the (Q, k) results
        d_true = q2.shape[1]
        sp.count(tracer, h2d=_host_bytes(qpad, cpad, idp, mpad),
                 true=4 * (qn * d_true + n * d_true + n) + 8 * qn * k)
    if wide:
        wnp = np.asarray(w)
        w = np.where(wnp == IVF_ID_SENTINEL, np.int64(IVF_ID_SENTINEL),
                     ids_sorted[np.minimum(wnp, n - 1)])
    return d, w
