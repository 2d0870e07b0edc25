"""Property-based IVF search tests.

The search path's contracts, stated as properties over random datasets,
partition counts and query batches:

* **exhaustive probing is exact** — with ``nprobe == n_partitions`` every
  candidate is eligible, so recall@k against brute force is 1.0 for any
  data, any seed, any k;
* **index maintenance is invisible** — ``compact()``-ing the index
  fragments, and time-travelling across index manifest versions, never
  changes a search result (ids and distances bit-identical);
* **decode routes are accounting-identical** — the ``decode="numpy"`` and
  ``decode="pallas"`` search paths issue bit-identical logical IO traces
  (same ops, same IOPS, same bytes): the kernel route is a compute detail,
  never an IO detail.
"""

import numpy as np

from hypothesis import given, settings, strategies as st

from repro.core import arrays as A
from repro.core.file import WriteOptions
from repro.dataset import DatasetWriter, IvfIndex, write_fragments
from repro.serve.engine import Retriever


def _build(n_rows, dim, n_fragments, n_partitions, seed, decode=None):
    rng = np.random.default_rng(seed)
    vecs = rng.standard_normal((n_rows, dim)).astype(np.float32)
    files = write_fragments({"embedding": A.FixedSizeListArray.build(vecs)},
                            n_fragments, WriteOptions("lance"))
    w = DatasetWriter(files=files, store="tiered", decode=decode)
    ivf = IvfIndex.build(w, "embedding", n_partitions=n_partitions,
                         n_fragments=2, seed=seed)
    r = Retriever(w.reader(), "embedding", index=ivf, decode=decode)
    return w, ivf, r, vecs


def _brute_topk(vecs, queries, k):
    """Exact float64 ground truth (expanded form, stable order)."""
    d = ((vecs[None].astype(np.float64)
          - queries[:, None].astype(np.float64)) ** 2).sum(-1)
    top = np.argsort(d, axis=1, kind="stable")[:, :k]
    return d, top


@settings(max_examples=12, deadline=None)
@given(
    n_rows=st.integers(40, 150),
    dim=st.integers(4, 24),
    n_partitions=st.integers(2, 6),
    k=st.integers(1, 8),
    nq=st.integers(1, 5),
    seed=st.integers(0, 2 ** 16),
)
def test_recall_is_exact_when_probing_every_partition(
        n_rows, dim, n_partitions, k, nq, seed):
    _, _, r, vecs = _build(n_rows, dim, 3, n_partitions, seed)
    rng = np.random.default_rng(seed + 1)
    q = vecs[rng.integers(0, n_rows, nq)] \
        + 0.05 * rng.standard_normal((nq, dim)).astype(np.float32)
    res = r.search(q, k=k, nprobe=n_partitions)
    d64, top = _brute_topk(vecs, q, k)
    hits = 0
    for i in range(nq):
        kth = d64[i, top[i, -1]]
        for rid in res.ids[i]:
            # a retrieved id counts if it is in the exact top-k, or tied
            # with the k-th distance within f32-arithmetic noise
            hits += rid in top[i] or d64[i, rid] <= kth * (1 + 1e-5) + 1e-7
    assert hits == nq * k


@settings(max_examples=10, deadline=None)
@given(
    n_rows=st.integers(50, 140),
    n_partitions=st.integers(3, 7),
    nprobe=st.integers(1, 3),
    seed=st.integers(0, 2 ** 16),
)
def test_search_invariant_under_index_compact_and_versions(
        n_rows, n_partitions, nprobe, seed):
    _, ivf, r, vecs = _build(n_rows, 12, 3, n_partitions, seed)
    rng = np.random.default_rng(seed + 2)
    q = vecs[rng.integers(0, n_rows, 3)]
    before = r.search(q, k=5, nprobe=nprobe)
    v1 = ivf.writer.version
    ivf.compact()  # merges the index fragments -> new index manifest
    assert ivf.writer.version > v1
    after = r.search(q, k=5, nprobe=nprobe)
    np.testing.assert_array_equal(before.ids, after.ids)
    np.testing.assert_array_equal(before.distances, after.distances)
    np.testing.assert_array_equal(before.probes, after.probes)
    # time travel: the pre-compaction index version answers identically
    old = r.search(q, k=5, nprobe=nprobe, index_version=v1)
    np.testing.assert_array_equal(before.ids, old.ids)
    np.testing.assert_array_equal(before.distances, old.distances)


@settings(max_examples=8, deadline=None)
@given(
    n_rows=st.integers(40, 120),
    n_partitions=st.integers(2, 6),
    nprobe=st.integers(1, 4),
    k=st.integers(1, 6),
    seed=st.integers(0, 2 ** 16),
)
def test_decode_routes_issue_identical_logical_io(
        n_rows, n_partitions, nprobe, k, seed):
    w_np, _, r_np, vecs = _build(n_rows, 10, 3, n_partitions, seed,
                                 decode="numpy")
    w_pl, _, r_pl, _ = _build(n_rows, 10, 3, n_partitions, seed,
                              decode="pallas")
    rng = np.random.default_rng(seed + 3)
    q = vecs[rng.integers(0, n_rows, 2)]
    w_np.reset_io()
    w_pl.reset_io()
    res_np = r_np.search(q, k=k, nprobe=nprobe)
    res_pl = r_pl.search(q, k=k, nprobe=nprobe)
    np.testing.assert_array_equal(res_np.ids, res_pl.ids)
    np.testing.assert_array_equal(res_np.distances, res_pl.distances)
    # logical IO trace bit-identical: same (offset, size, phase) ops
    assert w_np.scheduler.ops == w_pl.scheduler.ops
    s_np, s_pl = w_np.io_stats(), w_pl.io_stats()
    assert s_np.n_iops == s_pl.n_iops
    assert s_np.bytes_read == s_pl.bytes_read
