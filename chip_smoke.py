#!/usr/bin/env python3
"""Smoke run of the dataset read path and IVF search on one TPU chip.

Drives the system through the entry points a user calls — ``DatasetWriter``,
``IvfIndex.build``, ``DatasetReader.take`` and ``Retriever.search`` — at the
shape of TEXMEX SIFT1M as ann-benchmarks uses it: 1,000,000 x 128 float32
vectors under L2, generated from ``--seed`` (clustered, rows not sorted by
cluster), in 8 fragments on a tiered store, with an IVF index of 1024
partitions.  Beside the vectors the dataset holds a nullable int64 column
and a nullable ``List<int32>`` column.

Checks, every one of which fails the run:

* ``take`` with ``decode="pallas"`` is bit-identical to ``decode="numpy"``
  for all three columns;
* search at k=10, nprobe=32 through ``decode="pallas"`` agrees with the
  ``decode="numpy"`` route and with a float64 reference over its candidates,
  up to ties within the stated tolerance
  (:func:`repro.kernels.ref.topk_mismatches`);
* recall@10 against float64 brute force over the whole corpus is at least
  ``RECALL_MIN``;
* no ``decode.fallback.*`` counter moved: no part of the path went to numpy
  or to the jnp oracle in place of a kernel.

Earlier lines print the device, build and phase times (host clock, cold =
first call with compiles, warm = repeat), compile counts, candidate counts
and recall: readings of a smoke run, not benchmark metrics.  The last line
is ``{"ok": true, "device": {...}}``.  With no TPU the script exits non-zero
before any work.

    python chip_smoke.py [--rows N] [--seed S]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import resource
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

FULL_ROWS = 1_000_000
DIM = 128
N_FRAGMENTS = 8
K, NPROBE = 10, 32
N_TAKE = 4096
N_SINGLE, N_BATCH = 4, 16
RECALL_MIN = 0.9


def sift_like(n: int, seed: int, n_modes: int) -> np.ndarray:
    """(n, 128) float32 with SIFT's value shape: non-negative integers below
    256, drawn from a Gaussian mixture; rows are in random mode order."""
    rng = np.random.default_rng(seed)
    centers = rng.uniform(0.0, 100.0, (n_modes, DIM)).astype(np.float32)
    out = np.empty((n, DIM), np.float32)
    step = 1 << 17
    for lo in range(0, n, step):
        m = min(step, n - lo)
        x = centers[rng.integers(0, n_modes, m)]
        x += 20.0 * rng.standard_normal((m, DIM), dtype=np.float32)
        out[lo:lo + m] = np.rint(np.clip(x, 0.0, 255.0))
    return out


def make_table(vecs: np.ndarray, seed: int) -> dict:
    from repro.core import arrays as A, types as T

    n = len(vecs)
    rng = np.random.default_rng(seed + 1)
    tag = A.PrimitiveArray(T.int64(), rng.random(n) < 0.9,
                           rng.integers(0, 1 << 31, n, dtype=np.int64))
    lengths = rng.integers(0, 9, n)
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(lengths, out=offsets[1:])
    items = A.PrimitiveArray.build(
        rng.integers(0, 1 << 20, int(offsets[-1])).astype(np.int32),
        nullable=False)
    labels = A.ListArray.build(items, offsets, validity=rng.random(n) < 0.95)
    return {"embedding": A.FixedSizeListArray.build(vecs), "tag": tag,
            "labels": labels}


def identical(a, b) -> bool:
    """Bit-identical arrays: same types, buffers and children."""
    if type(a) is not type(b):
        return False
    if isinstance(a, np.ndarray):
        return a.dtype == b.dtype and np.array_equal(a, b)
    if dataclasses.is_dataclass(a):
        return all(identical(getattr(a, f.name), getattr(b, f.name))
                   for f in dataclasses.fields(a))
    return a == b


def exact_topk(vecs: np.ndarray, queries: np.ndarray, k: int) -> np.ndarray:
    """float64 brute force over the whole corpus, ties toward lower ids."""
    q = queries.astype(np.float64)
    d = np.empty((len(q), len(vecs)))
    for lo in range(0, len(vecs), 1 << 17):
        b = vecs[lo:lo + (1 << 17)].astype(np.float64)
        d[:, lo:lo + len(b)] = ((q * q).sum(1)[:, None] - 2.0 * q @ b.T
                                + (b * b).sum(1)[None])
    ids = np.arange(len(vecs))
    return np.stack([np.lexsort((ids, row))[:k] for row in d])


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip smoke failed: {what}")


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def smoke(rows: int = FULL_ROWS, seed: int = 0, say=print) -> dict:
    """Build, take and search; raise on any failed check; return readings."""
    from repro.dataset import DatasetWriter, IvfIndex, write_fragments
    from repro.core.file import WriteOptions
    from repro.kernels.ref import topk_mismatches, topk_tolerance
    from repro.obs import Tracer
    from repro.runtime import compile_counts
    from repro.serve.engine import Retriever

    n_parts = max(8, rows * 1024 // FULL_ROWS)
    nprobe = min(NPROBE, n_parts)
    compile_counts()
    say(f"rows: {rows}" + ("" if rows == FULL_ROWS
                           else f" (cut from {FULL_ROWS})")
        + f", dim {DIM}, fragments {N_FRAGMENTS}, partitions {n_parts}")

    # -- build ----------------------------------------------------------------
    # queries come from the corpus' own mixture, as SIFT1M's do
    vecs, t_gen = timed(lambda: sift_like(rows + N_SINGLE + N_BATCH, seed,
                                          n_parts))
    vecs, queries = vecs[:rows], vecs[rows:]
    table = make_table(vecs, seed)
    files, t_write = timed(lambda: write_fragments(
        table, N_FRAGMENTS, WriteOptions("lance")))
    tracer = Tracer()
    w, t_ingest = timed(lambda: DatasetWriter(files=files, store="tiered",
                                              tracer=tracer))
    ivf, t_index = timed(lambda: IvfIndex.build(
        w, "embedding", n_partitions=n_parts, seed=seed))
    say(f"build: generate {t_gen:.3f} s, write {t_write:.3f} s, "
        f"ingest {t_ingest:.3f} s, index {t_index:.3f} s")

    # -- take -------------------------------------------------------------------
    rng = np.random.default_rng(seed + 2)
    take_rows = rng.integers(0, rows, N_TAKE)
    r_np, r_pl = w.reader(), w.reader(decode="pallas")
    for col in table:
        c0 = compile_counts()["compiles"]
        want, t_np = timed(lambda: r_np.take(col, take_rows))
        got, t_cold = timed(lambda: r_pl.take(col, take_rows))
        again, t_warm = timed(lambda: r_pl.take(col, take_rows))
        check(identical(got, want) and identical(again, want),
              f"take({col}) pallas differs from numpy")
        say(f"take[{col}]: {N_TAKE} rows, cold {t_cold:.4f} s, warm "
            f"{t_warm:.4f} s (numpy route {t_np:.4f} s), compiles "
            f"{compile_counts()['compiles'] - c0}, bit-identical")

    # -- search -----------------------------------------------------------------
    ivf_pl = IvfIndex(ivf.writer, ivf.column, ivf.n_partitions, ivf.dim,
                      decode="pallas")
    routes = {
        "numpy": Retriever(r_np, "embedding", index=ivf, decode="numpy"),
        "pallas": Retriever(r_pl, "embedding", index=ivf_pl,
                            decode="pallas"),
    }
    centroids = ivf.centroids()
    postings = ivf.postings(np.arange(n_parts))
    cent_tol = topk_tolerance(queries, centroids)

    def agree(q, res_np, res_pl) -> int:
        """Check both routes of one query; return 1 if the winners match."""
        exact = ((centroids.astype(np.float64) - q) ** 2).sum(1)
        kth = np.sort(exact)[nprobe - 1]
        for res in (res_np, res_pl):
            differ = set(res.probes[0]) ^ set(np.argsort(exact)[:nprobe])
            check(all(abs(exact[p] - kth) <= 2 * cent_tol.max()
                      for p in differ), "probes differ beyond ties")
            cand = np.concatenate([postings[p] for p in res.probes[0]])
            bad = topk_mismatches(q[None], vecs[cand], cand, K,
                                  res.distances, res.ids, sentinel=-1)
            check(not bad, f"search winners: {bad}")
        return int(np.array_equal(res_np.ids, res_pl.ids))

    times = {}
    results = {}
    for name, retr in routes.items():
        singles = []
        for i in range(N_SINGLE):
            res, dt = timed(lambda: retr.search(queries[i], k=K,
                                                nprobe=nprobe))
            singles.append(res)
            times[(name, "single", "cold" if i == 0 else "warm")] = dt
        batch, t_cold = timed(lambda: retr.search(queries[N_SINGLE:], k=K,
                                                  nprobe=nprobe))
        _, t_warm = timed(lambda: retr.search(queries[N_SINGLE:], k=K,
                                              nprobe=nprobe))
        times[(name, "batch", "cold")] = t_cold
        times[(name, "batch", "warm")] = t_warm
        results[name] = (singles, batch)
    same = sum(agree(queries[i], results["numpy"][0][i],
                     results["pallas"][0][i]) for i in range(N_SINGLE))
    b_np, b_pl = results["numpy"][1], results["pallas"][1]
    for i in range(N_BATCH):
        pick = lambda r: dataclasses.replace(  # noqa: E731
            r, ids=r.ids[i:i + 1], distances=r.distances[i:i + 1],
            probes=r.probes[i:i + 1])
        same += agree(queries[N_SINGLE + i], pick(b_np), pick(b_pl))
    for name in routes:
        say(f"search[{name}]: single cold {times[(name, 'single', 'cold')]:.4f}"
            f" s, single warm {times[(name, 'single', 'warm')]:.4f} s, "
            f"batch{N_BATCH} cold {times[(name, 'batch', 'cold')]:.4f} s, "
            f"batch{N_BATCH} warm {times[(name, 'batch', 'warm')]:.4f} s")
    say(f"candidates: single {[r.n_candidates for r in results['pallas'][0]]}"
        f", batch{N_BATCH} {b_pl.n_candidates}; routes agree on "
        f"{same}/{N_SINGLE + N_BATCH} winner lists exactly, the rest on ties")

    exact = exact_topk(vecs, queries, K)
    got = np.concatenate([np.concatenate([r.ids for r in results["pallas"][0]]),
                          b_pl.ids])
    recall = float(np.mean([len(set(g) & set(e)) / K
                            for g, e in zip(got, exact)]))
    say(f"recall@{K}: {recall:.4f} at nprobe {nprobe}/{n_parts} "
        f"(threshold {RECALL_MIN})")
    check(recall >= RECALL_MIN, f"recall@{K} {recall} < {RECALL_MIN}")

    fallbacks = tracer.metrics.counter_values("decode.fallback")
    check(not any(fallbacks.values()), f"kernel fallbacks: {fallbacks}")
    counts = compile_counts()
    say(f"compiles: {counts['compiles']}, persistent-cache hits "
        f"{counts['cache_hits']}; decode.fallback counters: none; host peak "
        f"RSS {resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20:.2f}"
        " GiB")
    return {"recall": recall, **counts}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=FULL_ROWS)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    from repro.runtime import enable_compile_cache

    print(f"device_kind: {dev.device_kind}, compile cache: "
          f"{enable_compile_cache()}")
    smoke(args.rows, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
