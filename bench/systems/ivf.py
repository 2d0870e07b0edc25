"""IVF vector search over a dataset's vector column (configurations with
``"system": "ivf"``).

The corpus and the query pool are drawn from one SIFT-like mixture (queries
from the corpus' own distribution, as SIFT1M's are); the corpus is written
as fragments, ingested, indexed with ``IvfIndex.build``, and each request is
one ``Retriever.search`` of its batch of queries on the configuration's
decode route.

Corpus, pool and index come from the configuration's ``data_seed``, and the
run's ``--seed`` orders the pool (``traffic/query_pool.py``): a corpus and
its k-means partitions set the size of every query's candidate set, so
drawing them per seed changed the work of a window by tens of percent,
where every seed now serves queries of one set in another order.
"""

from __future__ import annotations

import numpy as np

from harness.gen import rng, sift_like, store

DATA_STREAM, QUERY_STREAM = 1, 2


def generate(cfg: dict, mix: dict, seed: int) -> dict:
    n, modes, fixed = int(cfg["rows"]), int(cfg["modes"]), cfg["data_seed"]
    vecs, centers = sift_like(n, rng(fixed, DATA_STREAM), modes)
    queries, _ = sift_like(int(mix["query_pool"]), rng(fixed, QUERY_STREAM),
                           modes, centers)
    return {"n_rows": n, "vectors": vecs, "queries": queries,
            "k": int(cfg["k"])}


def build(cfg: dict, data: dict, seed: int, tracer=None):
    """Write and ingest the corpus, build the index; returns the retriever
    requests use, with the search parameters."""
    from repro.core import arrays as A
    from repro.core.file import WriteOptions
    from repro.dataset import DatasetWriter, IvfIndex, write_fragments
    from repro.serve.engine import Retriever

    table = {cfg["column"]: A.FixedSizeListArray.build(data["vectors"])}
    files = write_fragments(table, int(cfg["fragments"]),
                            WriteOptions(cfg["encoding"]))
    writer = DatasetWriter(files=files, store=store(cfg), tracer=tracer)
    ivf = IvfIndex.build(writer, cfg["column"],
                         n_partitions=int(cfg["partitions"]),
                         seed=int(cfg["data_seed"]))
    decode = cfg["decode"]
    index = IvfIndex(ivf.writer, ivf.column, ivf.n_partitions, ivf.dim,
                     decode=decode)
    rows_per_fragment = -(-int(cfg["rows"]) // int(cfg["fragments"]))
    return Retriever(writer.reader(decode=decode), cfg["column"],
                     index=index, decode=decode), {
        "k": int(cfg["k"]), "nprobe": int(cfg["nprobe"]),
        "rows_per_fragment": rows_per_fragment}


def pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def shape_keys(sut, pool: list) -> tuple:
    """``(keys, parts)``: for each request of ``pool``, the sizes that set
    its padded shapes, each rounded up to a power of two: the candidate
    count (the top-k kernel's tiles) and the candidates in each fragment
    (its gathers); and the partitions it probes, ranked on the host by
    exact distance to the centroids."""
    retriever, params = sut
    index = retriever.index
    cent = index.centroids().astype(np.float64)
    lists = index.postings(np.arange(index.n_partitions))
    sizes = np.array([len(p) for p in lists])
    owner = np.repeat(np.arange(index.n_partitions), sizes)
    posts = np.concatenate(lists)
    n_frag = -(-retriever.reader.n_rows // params["rows_per_fragment"])
    frag_count = np.zeros((index.n_partitions, n_frag), np.int64)
    np.add.at(frag_count, (owner, posts // params["rows_per_fragment"]), 1)
    keys, probed = [], []
    for req in pool:
        q = np.asarray(req["vectors"], np.float64)
        d = ((q[:, None, :] - cent[None]) ** 2).sum(-1)
        probes = np.argsort(d, axis=1, kind="stable")[:, :params["nprobe"]]
        parts = np.unique(probes)
        probed.append(parts)
        per_frag = frag_count[parts].sum(0)
        keys.append({("candidates", pow2(sizes[parts].sum()))}
                    | {("fragment", f, pow2(c))
                       for f, c in enumerate(per_frag) if c})
    return keys, probed


def warm(sut, pool: list, warmup: dict) -> list:
    """Warm-up that the system knows the traffic needs: takes of every
    winner count a request can fetch from one fragment (1 to k x queries);
    the posting takes of the first ``warmup["postings"]`` requests of the
    window's order (their mini-block shapes follow from which partitions
    share chunks, which the host does not count); and, returned to be
    served, the requests of ``pool`` that between them reach every key of
    :func:`shape_keys` (greedy cover, taking from the far end of the
    window's order first)."""
    retriever, params = sut
    most = params["k"] * max(len(r["queries"]) for r in pool)
    n = 1
    while True:
        retriever.reader.take(retriever.column,
                              np.arange(min(n, most), dtype=np.int64))
        if n >= most:
            break
        n *= 2
    keys, parts = shape_keys(sut, pool)
    for p in parts[:int(warmup.get("postings", 0))]:
        retriever.index.postings(p)
    left = set().union(*keys)
    chosen = []
    while left:
        i = max(range(len(pool) - 1, -1, -1),
                key=lambda j: len(keys[j] & left))
        chosen.append(pool[i])
        left -= keys[i]
    return chosen


def serve(sut, req: dict) -> tuple:
    """One search of the request's queries; returns ``(result, queries
    served)``."""
    retriever, params = sut
    vecs = req["vectors"]
    return retriever.search(vecs, k=params["k"], nprobe=params["nprobe"],
                            fetch=True), len(vecs)


def plain(res) -> dict:
    return {"ids": np.asarray(res.ids, np.int64),
            "distances": np.asarray(res.distances, np.float64),
            "winner_rows": np.asarray(res.winner_rows, np.int64),
            "winner_values": (np.zeros((0, 0), np.float32)
                              if res.values is None
                              else np.asarray(res.values.values))}
