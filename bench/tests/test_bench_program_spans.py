"""The per-layer metrics that read the program's own spans: each metric file
on a small trace built here (a leaf reader's nested ``fullzip.unzip`` counts
as its self time), nothing read from a program that opens no such spans, and
the program's spans read back from a real profile through the profiler
sink."""

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts bench/ on the path)
from bench_tiny import BENCH, SEED, tiny_cell
from harness import trace as tr
from harness.cell import load_module


def view():
    """One search of two queries: a candidate take through one full-zip
    leaf reader with the gather kernel, then the top-k kernel."""
    spans = [
        ("search", 0.0, 20.0),
        ("serve.candidates", 1.0, 15.0),
        ("dataset.take:embedding", 1.0, 15.0),
        ("dataset.locate", 1.0, 2.0),
        ("encodings:FullZipReader.take", 2.0, 10.0),  # a harness span
        ("fullzip.take", 2.0, 10.0),
        ("fullzip.unique", 2.0, 2.5),
        ("store.read", 2.5, 4.5),
        ("kernel.pack:fullzip_gather", 4.5, 5.0),
        ("kernel.h2d:fullzip_gather", 5.0, 5.5),
        ("kernel.launch:fullzip_gather", 5.5, 6.0),
        ("kernel.wait:fullzip_gather", 6.0, 7.0),
        ("kernel.d2h:fullzip_gather", 7.0, 8.0),
        ("kernel.unpack:fullzip_gather", 8.0, 8.5),
        ("fullzip.unzip", 8.5, 9.5),
        ("drain:take:embedding", 10.0, 12.0),
        ("dataset.assemble", 12.0, 14.0),
        ("serve.topk", 15.0, 18.0),
        ("kernel.h2d:ivf_topk", 15.5, 16.0),
        ("kernel.d2h:ivf_topk", 16.5, 17.0),
    ]
    return tr.TraceView((0.0, 20.0), spans, [], n_requests=2)


# seconds of the view above each metric reads, over its 2 requests
EXPECTED_S = {
    # dataset.take (14) minus fullzip.take (8) minus drain (2)
    "dataset_self_ms": 4.0,
    # fullzip.take (8) minus store.read (2) minus kernel.* (4); the nested
    # fullzip.unique and fullzip.unzip are the reader's own time
    "leaf_reader_self_ms": 2.0,
    "store_read_ms": 2.0,
    # h2d and d2h of both kernels
    "transfer_ms": 2.5,
}
METRICS = [f"{m}.{cell}" for m in sorted(EXPECTED_S)
           for cell in ("search", "take")]


@pytest.mark.parametrize("name", METRICS)
def test_metric_reads_its_program_spans(name):
    mod = load_module(BENCH / "metrics" / f"{name}.py")
    want = 1e3 * EXPECTED_S[name.split(".")[0]] / 2
    assert mod.read(view()) == pytest.approx(want)


@pytest.mark.parametrize("name", METRICS)
def test_metric_reads_nothing_without_program_spans(name):
    """The spans an older program opens: the metric is left out."""
    old = tr.TraceView((0.0, 10.0), [
        ("search", 0.0, 10.0), ("dataset.take:embedding", 1.0, 8.0),
        ("encodings:FullZipReader.take", 2.0, 6.0),
        ("ops.fullzip_gather", 3.0, 5.0), ("drain:take:embedding", 6.0, 7.0),
    ], [], n_requests=1)
    mod = load_module(BENCH / "metrics" / f"{name}.py")
    assert mod.read(old) is None


def _inside(view, child: str, parents) -> bool:
    """Every span named ``child`` lies inside a span named in ``parents``."""
    outer = [(a, b) for n, a, b in view.spans if n in parents]
    kids = [(a, b) for n, a, b in view.spans if n == child]
    return bool(kids) and all(
        any(pa <= a and b <= pb for pa, pb in outer) for a, b in kids)


def test_program_spans_read_back_through_the_profiler_sink(tmp_path):
    from repro.obs import Tracer

    cell = tiny_cell("search.sift1m-ivf1024")
    data = cell.system.generate(cell.config, cell.traffic, SEED)
    tracer = Tracer(sink="profiler")
    sut = cell.system.build(cell.config, data, SEED, tracer)
    retriever, params = sut
    rows = np.arange(0, data["n_rows"], 37, dtype=np.int64)
    query = data["queries"][:1]
    retriever.reader.take(retriever.column, rows)       # warm up
    retriever.search(query, k=params["k"], nprobe=params["nprobe"])
    with tr.profiled(str(tmp_path)):
        retriever.reader.take(retriever.column, rows)
        retriever.search(query, k=params["k"], nprobe=params["nprobe"])
    v = tr.load(str(tmp_path))
    names = {n for n, _, _ in v.spans}
    assert {"search", "serve.probe", "serve.postings", "serve.mask",
            "serve.candidates", "serve.topk", "serve.winners",
            "dataset.locate", "dataset.assemble", "fullzip.take",
            "fullzip.unzip", "miniblock.take", "store.read"} <= names
    assert tracer.events == []
    for step in ("pack", "h2d", "launch", "wait", "d2h", "unpack"):
        assert _inside(v, f"kernel.{step}:fullzip_gather", {"fullzip.take"})
        assert _inside(v, f"kernel.{step}:miniblock_decode",
                       {"miniblock.take"})
        assert _inside(v, f"kernel.{step}:ivf_topk",
                       {"serve.probe", "serve.topk"})
    assert _inside(v, "store.read", {"fullzip.take", "miniblock.take"})
    assert _inside(v, "fullzip.take", {"dataset.take:embedding",
                                       "dataset.take:centroid"})
    for step in ("serve.probe", "serve.topk", "serve.candidates"):
        assert _inside(v, step, {"search"})
    counts = tracer.metrics.counter_values()
    assert counts["store.read_spans"] > 0
    assert counts["kernel.bytes_h2d.fullzip_gather"] > 0
