"""Plain reference for k-nearest-neighbour search: float64 brute force in
blocks over the whole generated corpus.  It uses neither the index nor the
kernels, and imports nothing of the program.

Numbers compared (``check``), over the queries of a run (a request holds
a batch of them):

* ``distance_gap``: the largest ``|returned distance - exact distance of the
  returned id|`` in units of the float32 tolerance
  ``(D + 2) eps (|q| + max|c|)^2`` (the arithmetic of the program's stated
  contract, copied; ``max|c|`` over the corpus).
* ``value_mismatches``: winner rows whose fetched vector differs from the
  corpus row, returned ids outside the corpus, and returned ids whose row
  was not fetched.
* ``missed_neighbors``: exact top-k neighbours not returned, where a
  returned id within ``2 tol`` of the exact k-th distance counts as found
  (ties, as in the program's contract).  Summed over queries and divided
  by ``k`` times the number of queries: one minus recall@k.

``control`` is the reference in the program's place at a lower precision:
brute force over int8 vectors (per-tensor symmetric scale), on the device.
"""

from __future__ import annotations

import numpy as np

BLOCK = 1 << 17
QUERY_BLOCK = 64
EPS32 = float(np.finfo(np.float32).eps)


def corpus_stats(data: dict) -> dict:
    """Per-corpus constants, computed once: squared norms and max norm."""
    if "_stats" not in data:
        x = data["vectors"]
        sq = np.empty(len(x))
        for lo in range(0, len(x), BLOCK):
            b = x[lo:lo + BLOCK].astype(np.float64)
            sq[lo:lo + len(b)] = (b * b).sum(1)
        data["_stats"] = {"sq": sq, "cmax": float(np.sqrt(sq.max()))}
    return data["_stats"]


def exact(data: dict, queries: np.ndarray) -> np.ndarray:
    """(Q, N) float64 squared distances, blockwise."""
    x, st = data["vectors"], corpus_stats(data)
    q = np.atleast_2d(queries).astype(np.float64)
    d = np.empty((len(q), len(x)))
    qq = (q * q).sum(1)[:, None]
    for lo in range(0, len(x), BLOCK):
        b = x[lo:lo + BLOCK].astype(np.float64)
        d[:, lo:lo + len(b)] = (qq - 2.0 * q @ b.T
                                + st["sq"][None, lo:lo + len(b)])
    return d


def tolerance(data: dict, q: np.ndarray) -> float:
    q = np.asarray(q, np.float64)
    scale = (np.sqrt((q * q).sum()) + corpus_stats(data)["cmax"]) ** 2
    return (len(q) + 2) * EPS32 * scale


def compare_all(data: dict, reqs: list, got: list) -> list:
    """Per-request numbers for the requests ``reqs`` (each a batch of
    ``vectors``) and their plain results ``got`` (one exact pass over the
    corpus for every ``QUERY_BLOCK`` queries or so).  A query the result
    has no row for counts its ``k`` neighbours missed and its ``k`` ids
    mismatched."""
    out = []
    lo = 0
    while lo < len(reqs):
        hi, nq = lo, 0
        while hi < len(reqs) and (hi == lo or nq < QUERY_BLOCK):
            nq += len(np.atleast_2d(reqs[hi]["vectors"]))
            hi += 1
        out += compare_block(data, reqs[lo:hi], got[lo:hi])
        lo = hi
    return out


def compare_block(data: dict, reqs: list, got: list) -> list:
    k = data["k"]
    x = data["vectors"]
    d = exact(data, np.concatenate([np.atleast_2d(r["vectors"])
                                    for r in reqs]))
    out = []
    qi = 0
    for req, g in zip(reqs, got):
        vecs = np.atleast_2d(req["vectors"])
        ids_all = np.atleast_2d(g["ids"])
        dist_all = np.atleast_2d(g["distances"])
        gap, missed, absent, outside = 0.0, 0, 0, 0
        returned = []
        for b, q in enumerate(vecs):
            row = d[qi + b]
            if b >= len(ids_all):
                missed += k
                absent += k
                continue
            ids, dist = ids_all[b], dist_all[b]
            tol = tolerance(data, q)
            real = ids >= 0
            inside = real & (ids < len(x))
            if inside.any():
                gap = max(gap, float(np.max(np.abs(
                    dist[inside] - row[ids[inside]])) / tol))
            kth = np.partition(row, k - 1)[k - 1]
            found = len(set(ids[inside][row[ids[inside]] <= kth + 2 * tol]))
            missed += k - min(found, k)
            outside += int((real & ~inside).sum())
            returned.extend(ids[inside].tolist())
        qi += len(vecs)
        rows = g["winner_rows"]
        vals = g["winner_values"]
        if len(rows) and (rows.min() < 0 or rows.max() >= len(x)
                          or vals.shape != (len(rows), x.shape[1])):
            mism = len(rows)
        else:
            mism = int((vals != x[rows]).reshape(len(rows), -1).any(1).sum()) \
                if len(rows) else 0
        missing = set(returned) - set(rows.tolist())
        out.append({"distance_gap": gap,
                    "value_mismatches": mism + len(missing) + outside + absent,
                    "missed": missed, "queries": len(vecs), "k": k})
    return out


def check(data: dict, reqs: list, got: list) -> dict:
    """The numbers a run compares, over all its queries."""
    per_request = compare_all(data, reqs, got)
    n = sum(r["k"] * r["queries"] for r in per_request)
    return {"distance_gap": max((r["distance_gap"] for r in per_request),
                                default=0.0),
            "value_mismatches": sum(r["value_mismatches"]
                                    for r in per_request),
            "missed_neighbors": (sum(r["missed"] for r in per_request) / n
                                 if n else 0.0),
            "queries_checked": sum(r["queries"] for r in per_request)}


def control(data: dict, reqs: list) -> list:
    """The reference at int8 in the program's place: plain results of the
    same shape as the program's, from brute force over int8 vectors on the
    default device."""
    import jax
    import jax.numpy as jnp

    k, x = data["k"], data["vectors"]
    scale = float(np.abs(x).max()) / 127.0
    x8_host = np.rint(x / scale).astype(np.int8)
    x8 = jnp.asarray(x8_host)
    xx = jnp.sum(x8.astype(jnp.int32) ** 2, axis=1)

    @jax.jit
    def top(x8, xx, q8):
        qx = jax.lax.dot_general(
            x8, q8, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        d = (jnp.sum(q8.astype(jnp.int32) ** 2) - 2 * qx + xx).astype(
            jnp.float32) * scale * scale
        neg, ids = jax.lax.top_k(-d, k)
        return -neg, ids

    out = []
    for req in reqs:
        found = [[np.asarray(a) for a in top(
            x8, xx, jnp.asarray(np.rint(q / scale).astype(np.int8)))]
            for q in np.atleast_2d(req["vectors"])]
        dist = np.stack([f[0] for f in found])
        ids = np.stack([f[1] for f in found])
        rows = np.unique(ids).astype(np.int64)
        out.append({"ids": ids.astype(np.int64),
                    "distances": dist.astype(np.float64),
                    "winner_rows": rows,
                    "winner_values": (x8_host[rows].astype(np.float32)
                                      * np.float32(scale))})
    return out
