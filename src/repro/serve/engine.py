"""Batched serving engine: prefill + decode loop with jit'd steps, plus the
random-access retrieval path (the paper's `take`) for embedding/document
fetch — search results feed generation, storage feeds search.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.file import FileReader
from ..kernels import ops
from ..obs import NULL_TRACER

__all__ = ["BatchedEngine", "Retriever", "SearchResult"]

# the steps of one search, each a span inside ``search``
SPAN_PROBE = "serve.probe"
SPAN_POSTINGS = "serve.postings"
SPAN_MASK = "serve.mask"
SPAN_CANDIDATES = "serve.candidates"
SPAN_TOPK = "serve.topk"
SPAN_WINNERS = "serve.winners"


@dataclasses.dataclass
class GenResult:
    tokens: np.ndarray  # (B, n_gen)
    steps: int


@dataclasses.dataclass
class SearchResult:
    """One batched IVF search: per-query winners plus the one batched take
    that materialized them.

    ``ids``/``distances`` are (Q, k); a query with fewer than ``k``
    eligible candidates pads with ``id = -1`` / ``distance = inf``.
    ``winner_rows`` is the deduplicated ascending union of valid ids —
    the row set the winner ``take`` fetched; ``values`` is that take's
    result, aligned with ``winner_rows`` (``None`` when ``fetch=False``).
    """

    ids: np.ndarray          # (Q, k) int64 global row ids, -1 at padding
    distances: np.ndarray    # (Q, k) float32 squared L2, inf at padding
    probes: np.ndarray       # (Q, nprobe) probed partition ids
    winner_rows: np.ndarray  # unique valid ids, ascending
    values: Optional[object] = None
    n_candidates: int = 0    # posting entries scored across probed parts


class BatchedEngine:
    """Static-batch generate: prefill once, decode N steps with a
    pre-allocated cache (capacity = prompt + max_new)."""

    def __init__(self, model, params, max_new: int = 32):
        self.model = model
        self.params = params
        self.max_new = max_new
        self._prefill = jax.jit(model.prefill)
        self._decode = jax.jit(model.decode_step, donate_argnums=(1,))

    def _pad_cache(self, cache, extra: int):
        fam = self.model.cfg.family

        def pad(x, axis):
            cfgpad = [(0, 0)] * x.ndim
            cfgpad[axis] = (0, extra)
            return jnp.pad(x, cfgpad)

        if fam in ("dense", "moe"):
            keys = cache["layers"].keys()
            lay = {k: pad(v, 2) for k, v in cache["layers"].items()}
            return {"layers": lay, "length": cache["length"]}
        if fam == "ssm":
            return cache  # state caches need no capacity
        if fam == "hybrid":
            return {"mamba": cache["mamba"],
                    "shared": {k: pad(v, 2) for k, v in cache["shared"].items()},
                    "length": cache["length"]}
        if fam == "vlm":
            return {"self": {k: pad(v, 3) for k, v in cache["self"].items()},
                    "cross": cache["cross"], "length": cache["length"]}
        if fam == "audio":
            return {"self": {k: pad(v, 2) for k, v in cache["self"].items()},
                    "cross": cache["cross"], "length": cache["length"]}
        raise ValueError(fam)

    def generate(self, batch: Dict, n_new: Optional[int] = None,
                 greedy: bool = True) -> GenResult:
        n_new = n_new or self.max_new
        logits, cache = self._prefill(self.params, batch)
        cache = self._pad_cache(cache, n_new + 8)
        toks = []
        cur = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        for _ in range(n_new):
            toks.append(np.asarray(cur))
            logits, cache = self._decode(self.params, cache, cur)
            cur = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        return GenResult(np.concatenate(toks, axis=1), n_new)


class Retriever:
    """Random-access retrieval over a Lance file *or dataset*: the
    search-path consumer (§1: 'search workloads fetch small subsets not
    aligned with the clustered index').

    ``source`` is one Lance file (bytes), a list of fragment files (served
    through :class:`repro.dataset.DatasetReader` — one shared NVMe budget
    and cross-file coalescing over the whole dataset), or a ready
    ``FileReader``/``DatasetReader``.  ``store`` selects the tier stack
    (see :func:`repro.store.make_store`): the serving deployment shape is
    ``store="tiered"`` — an NVMe block cache over S3 that turns the hot
    working set into NVMe-priced reads while cold rows pay the object-store
    round trip ("tiered-auto" additionally adapts cache admission to the
    observed scan/take mix).
    """

    def __init__(self, source, column: str = "embedding", store=None,
                 index=None, decode: Optional[str] = None):
        if isinstance(source, (list, tuple)):
            from ..dataset import DatasetReader

            self.reader = DatasetReader(list(source), store=store,
                                        decode=decode)
        elif isinstance(source, (bytes, bytearray)):
            self.reader = FileReader(source, store=store, decode=decode)
        else:
            if store is not None:
                raise ValueError("store is fixed by a ready reader")
            self.reader = source
        self.column = column
        # ``index``: an IvfIndex whose attached writer shares this reader's
        # scheduler/store — :meth:`search` turns queries into row ids.
        # ``decode`` selects the kernel route for both file decode and the
        # search distance/top-k ("numpy" = jnp oracles, default Pallas).
        self.index = index
        self.decode = decode

    def fetch(self, row_ids: np.ndarray):
        """take() — at most 2 IOPS/row via full-zip (§4.1.4).  Row ids are
        global over the dataset when serving from fragments."""
        self.reader.reset_io()
        out = self.reader.take(self.column, np.asarray(row_ids, np.int64))
        return out, self.reader.io_stats()

    def search(self, query, k: int = 10, nprobe: int = 4,
               fetch: bool = True, index_version: Optional[int] = None,
               ) -> SearchResult:
        """IVF search: probe partitions → batched posting-list fetch →
        distance/top-k kernel → one batched ``take`` of the winners.

        Every IO lands on the retriever's shared scheduler/store — index
        reads (centroids, posting lists) and data reads (candidate
        vectors, winner rows) share one cache budget and one drain log, so
        per-request attribution sees the whole search, not just its data
        half.  Accepts one query ``(D,)`` or a batch ``(Q, D)``;
        multi-query batches score one shared candidate matrix under a
        per-query partition mask, so each query still sees exactly its own
        ``nprobe`` probes.  Deterministic end to end: k-means is seeded
        and ties break toward the lowest row id.  The numpy and Pallas
        distance routes (``decode`` knob) each agree with float64 top-k up
        to ties within :func:`repro.kernels.ref.topk_tolerance`.

        Traced as one ``search`` span holding a span per step
        (``serve.probe``, ``serve.postings``, ``serve.mask``,
        ``serve.candidates``, ``serve.topk``, ``serve.winners``); the
        top-k kernel's outputs are waited for and copied back in
        ``serve.probe`` and ``serve.topk``.
        """
        if self.index is None:
            raise ValueError(
                "no index attached — IvfIndex.build(writer, column) first")
        q = np.atleast_2d(np.asarray(query, np.float32))
        nq = q.shape[0]
        p = self.index.n_partitions
        k = int(k)
        nprobe = min(max(1, int(nprobe)), p)
        use_pallas = self.decode != "numpy"
        tracer = getattr(self.reader, "tracer", NULL_TRACER)
        sp = ops.IVF_TOPK
        with tracer.span("search", cat="serve", n_queries=nq, k=k,
                         nprobe=nprobe):
            # 1. probe: nearest centroids per query (centroid rows come
            # through the shared store; warm after the first search)
            with tracer.span(SPAN_PROBE):
                cent = self.index.centroids(index_version)
                _, probes = ops.ivf_topk(
                    q, cent, np.arange(p, dtype=np.int32), nprobe,
                    use_pallas=use_pallas, tracer=tracer)
                with tracer.span(sp.wait):
                    probes = jax.block_until_ready(probes)
                with tracer.span(sp.d2h):
                    probes = np.asarray(probes)
                if tracer.enabled:
                    sp.count(tracer, d2h=probes.nbytes)
                with tracer.span(sp.unpack):
                    probes = probes.astype(np.int64)        # (Q, nprobe)
            # 2. one batched posting fetch for the union of probed parts
            with tracer.span(SPAN_POSTINGS):
                parts = np.unique(probes)
                posts = self.index.postings(parts, index_version)
                cand_ids = np.concatenate(posts) if posts else \
                    np.zeros(0, np.int64)
            # per-query eligibility: candidate row -> owning partition,
            # eligible iff that partition is in the query's probe set
            with tracer.span(SPAN_MASK):
                probed = np.zeros((nq, p), bool)
                probed[np.repeat(np.arange(nq), nprobe),
                       probes.reshape(-1)] = True
                part_of = np.repeat(parts, [len(pl) for pl in posts])
                mask = probed[:, part_of]                   # (Q, N)
            # 3. one batched take of the candidate vectors, then the kernel
            with tracer.span(SPAN_CANDIDATES):
                cand = self.reader.take(self.column, cand_ids)
            with tracer.span(SPAN_TOPK):
                d, w = ops.ivf_topk(q, np.asarray(cand.values, np.float32),
                                    cand_ids, k, mask=mask,
                                    use_pallas=use_pallas, tracer=tracer)
                with tracer.span(sp.wait):
                    d, w = jax.block_until_ready((d, w))
                with tracer.span(sp.d2h):
                    d, w = np.asarray(d), np.asarray(w)
                if tracer.enabled:
                    sp.count(tracer, d2h=d.nbytes + w.nbytes)
                with tracer.span(sp.unpack):
                    d = np.asarray(d, np.float32)
                    w = np.asarray(w, np.int64)
                    w[w == ops.IVF_ID_SENTINEL] = -1
            # 4. one batched take of the deduplicated winner rows — the
            # response payload, served (and priced) like any data read
            with tracer.span(SPAN_WINNERS):
                winners = np.unique(w[w >= 0])
                values = None
                if fetch and winners.size:
                    values = self.reader.take(self.column, winners)
            return SearchResult(ids=w, distances=d, probes=probes,
                                winner_rows=winners, values=values,
                                n_candidates=int(cand_ids.size))

    def tier_stats(self):
        """Per-tier dispatched-IO stats since the last fetch."""
        return self.reader.tier_stats()

    def modelled_time(self) -> float:
        return self.reader.modelled_time()
