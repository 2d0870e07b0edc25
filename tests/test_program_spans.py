"""Program spans down the take and search paths.

A take and a search through an enabled in-memory tracer open a span at each
layer boundary: every ``kernel.*`` span sits inside its leaf reader's or its
search step's span, every ``store.read`` inside a leaf reader's, and every
parent's host steps have names.  With the default ``NULL_TRACER`` the results
are bit-identical, no span object is made and no counter moves.
"""

import numpy as np
import pytest

from repro.core import arrays as A
from repro.core.file import WriteOptions
from repro.dataset import DatasetWriter, IvfIndex, write_fragments
from repro.obs import NULL_TRACER, Tracer
from repro.obs import trace as obs_trace
from repro.serve.engine import Retriever

N_ROWS, DIM = 1200, 128


def _system(tracer=None):
    """A vector column in 3 fragments with an IVF index, on the Pallas
    route: the full-zip gather, mini-block decode and top-k kernels."""
    rng = np.random.default_rng(7)
    vecs = rng.integers(0, 256, (N_ROWS, DIM)).astype(np.float32)
    files = write_fragments({"embedding": A.FixedSizeListArray.build(vecs)},
                            3, WriteOptions("lance"))
    w = DatasetWriter(files=files, store="tiered", tracer=tracer)
    ivf = IvfIndex.build(w, "embedding", n_partitions=8, seed=3)
    index = IvfIndex(ivf.writer, ivf.column, ivf.n_partitions, ivf.dim,
                     decode="pallas")
    return Retriever(w.reader(decode="pallas"), "embedding", index=index,
                     decode="pallas"), vecs


def _serve(retriever, vecs):
    rows = np.random.default_rng(1).integers(0, N_ROWS, 96)
    took = retriever.reader.take("embedding", rows)
    res = retriever.search(vecs[[5, 900]] + 0.5, k=4, nprobe=3)
    return took, res


def _spans(tracer):
    """``(name, start, end)`` of the recorded spans."""
    return [(e["name"], e["ts"], e["ts"] + e["dur"])
            for e in tracer.events if e["ph"] == "X"]


def _parent_of(spans):
    """Each span's innermost enclosing span (spans are recorded as they
    close, so an enclosing span comes later in the list)."""
    out = {}
    for i, (name, a, b) in enumerate(spans):
        best = None
        for j in range(i + 1, len(spans)):
            _, pa, pb = spans[j]
            if pa <= a and b <= pb and (best is None
                                        or pb - pa < best[2] - best[1]):
                best = spans[j]
        out[i] = best[0] if best else None
    return out


@pytest.fixture(scope="module")
def traced():
    tracer = Tracer()
    retriever, vecs = _system(tracer)
    tracer.reset()
    took, res = _serve(retriever, vecs)
    return tracer, took, res


def test_a_take_and_a_search_open_every_layer_span(traced):
    tracer, _, _ = traced
    names = {n for n, _, _ in _spans(tracer)}
    assert {"search", "serve.probe", "serve.postings", "serve.mask",
            "serve.candidates", "serve.topk", "serve.winners",
            "dataset.take:embedding", "dataset.take:posting",
            "dataset.locate", "dataset.assemble",
            "fullzip.take", "fullzip.unique", "fullzip.unzip",
            "miniblock.take", "miniblock.ranges", "miniblock.parse",
            "miniblock.select", "store.read"} <= names
    for kernel in ("fullzip_gather", "miniblock_decode", "ivf_topk"):
        for step in ("pack", "h2d", "launch", "wait", "d2h", "unpack"):
            assert f"kernel.{step}:{kernel}" in names


# the spans a step may sit in: kernel dispatch inside its caller's span
PARENTS = {
    "kernel.%s:fullzip_gather": {"fullzip.take"},
    "kernel.%s:miniblock_decode": {"miniblock.take"},
    "kernel.%s:ivf_topk": {"serve.probe", "serve.topk"},
}


@pytest.mark.parametrize("step", ["pack", "h2d", "launch", "wait", "d2h",
                                  "unpack"])
@pytest.mark.parametrize("kernel", sorted(PARENTS))
def test_kernel_spans_sit_in_their_callers_span(traced, kernel, step):
    tracer, _, _ = traced
    spans = _spans(tracer)
    parent = _parent_of(spans)
    got = {parent[i] for i, (n, _, _) in enumerate(spans)
           if n == kernel % step}
    assert got and got <= PARENTS[kernel]


@pytest.mark.parametrize("child,parents", [
    ("store.read", {"fullzip.take", "miniblock.take"}),
    ("fullzip.take", {"dataset.take_columns"}),
    ("miniblock.take", {"dataset.take_columns", "miniblock.decode_batch"}),
    ("dataset.locate", {"dataset.take_columns"}),
    ("dataset.take:embedding", {None, "serve.candidates", "serve.winners"}),
    ("serve.topk", {"search"}),
    ("dataset.take_columns", {"dataset.take:embedding", "dataset.take:posting",
                              "dataset.take:centroid"}),
    ("miniblock.decode_batch", {"dataset.take_columns"}),
])
def test_spans_nest_by_layer(traced, child, parents):
    tracer, _, _ = traced
    spans = _spans(tracer)
    parent = _parent_of(spans)
    got = {parent[i] for i, (n, _, _) in enumerate(spans) if n == child}
    assert got and got <= parents


def test_counters_count_the_bytes_each_boundary_moves(traced):
    tracer, took, _ = traced
    c = tracer.metrics.counter_values()
    assert c["store.read_spans"] > 0 and c["store.read_bytes"] > 0
    for kernel in ("fullzip_gather", "miniblock_decode", "ivf_topk"):
        assert c[f"kernel.bytes_h2d.{kernel}"] > 0
        assert c[f"kernel.bytes_d2h.{kernel}"] > 0
        assert c[f"kernel.bytes_true.{kernel}"] > 0
    # full-zip rows (a control word + 512 B of values) move as 1024 B
    # tiles: padding amplifies the transfer
    assert c["kernel.bytes_d2h.fullzip_gather"] > \
        c["kernel.bytes_true.fullzip_gather"] / 2


def test_null_tracer_is_bit_identical_and_allocates_no_span(traced,
                                                            monkeypatch):
    _, took, res = traced

    def no_span(*args, **kwargs):
        raise AssertionError("a span object was made with tracing off")

    monkeypatch.setattr(obs_trace, "_Span", no_span)
    monkeypatch.setattr(obs_trace, "_ProfilerSpan", no_span)
    retriever, vecs = _system()
    assert retriever.reader.tracer is NULL_TRACER
    took0, res0 = _serve(retriever, vecs)
    np.testing.assert_array_equal(took0.values, took.values)
    for f in ("ids", "distances", "probes", "winner_rows"):
        np.testing.assert_array_equal(getattr(res0, f), getattr(res, f))
    np.testing.assert_array_equal(res0.values.values, res.values.values)
    assert NULL_TRACER.metrics.counter_values() == {}
    assert NULL_TRACER.events == []


def test_profiler_sink_keeps_names_only_and_counts():
    tr = Tracer(sink="profiler")
    with tr.span("dataset.take:embedding", n_rows=3) as sp:
        sp.set(n_fragments=1)
        with tr.span("store.read"):
            pass
    tr.instant("i")
    tr.counter("c", {"v": 1})
    tr.fallback("fullzip", "variable-stride")
    tr.count("store.read_spans", 2)
    assert tr.events == [] and tr.track("request-1") == tr.tid
    assert tr.metrics.counter_values() == {
        "decode.fallback.fullzip.variable-stride": 1, "store.read_spans": 2}
    with pytest.raises(ValueError):
        Tracer(sink="file")
    off = Tracer(enabled=False, sink="profiler")
    assert off.span("x") is obs_trace.NULL_SPAN
    off.count("store.read_spans")
    assert off.metrics.counter_values() == {}

