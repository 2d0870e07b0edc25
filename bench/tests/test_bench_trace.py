"""The reduction from a trace to per-layer numbers, on small traces built
here: busy union, idle share, self time, idle gaps labelled by host span,
roofline share; and a real profile of annotated spans on the CPU."""

import pytest

import bench_tiny  # noqa: F401  (puts bench/ on the path)
from harness import trace as tr
from harness.cell import load_module
from bench_tiny import BENCH


def view():
    spans = [
        ("search", 0.0, 10.0),
        ("dataset.take:embedding", 1.0, 4.0),
        ("drain:take:embedding", 3.0, 3.5),
        ("ops.fullzip_gather", 2.0, 2.5),
        ("ops.ivf_topk", 6.0, 8.0),
    ]
    device = [("%fullzip_gather_pallas.1 = u32[256,1,256]{2,1,0:T(1,128)} "
               "custom-call(s32[2,1,128]{2,1,0} %bitcast.3)", 2.1, 2.3),
              ("%ivf_topk_pallas.2 = (f32[8,128]{1,0}, s32[8,128]{1,0}) "
               "custom-call(f32[8,128]{1,0} %q)", 6.5, 7.0),
              ("%ivf_topk_pallas.2 = (f32[8,128]{1,0}, s32[8,128]{1,0}) "
               "custom-call(f32[8,128]{1,0} %q)", 6.8, 7.5),  # overlaps
              ("%copy.3 = f32[8]{0} copy(f32[8]{0} %x)", 9.0, 9.25)]
    modules = [("jit_fullzip_gather_pallas(123)", 2.05, 2.35),
               ("jit_ivf_topk_pallas(456)", 6.4, 7.0),
               ("jit_ivf_topk_pallas(789)", 7.0, 7.6)]
    return tr.TraceView((0.0, 10.0), spans, device, modules, n_requests=2,
                        peaks={"flops_s": 100.0, "hbm_bytes_s": 10.0})


def test_busy_is_the_union_of_device_intervals():
    v = view()
    assert v.busy_s == pytest.approx(0.2 + 1.0 + 0.25)
    idle = load_module(BENCH / "metrics" / "device_idle_pct.take.py")
    assert idle.read(v) == pytest.approx(100 * (1 - 1.45 / 10))


def test_span_and_self_time():
    v = view()
    assert v.span_s(["dataset.take:"]) == pytest.approx(3.0)
    assert v.span_s(["ops."]) == pytest.approx(2.5)
    # search minus dataset.take (3.0) minus ops.ivf_topk (2.0); the
    # fullzip dispatch lies inside the take and is not subtracted twice
    assert v.self_s(["search"], ["dataset.take:", "ops."]) == \
        pytest.approx(5.0)
    serve = load_module(BENCH / "metrics" / "serve_self_ms.search.py")
    assert serve.read(v) == pytest.approx(1e3 * 5.0 / 2)
    drain = load_module(BENCH / "metrics" / "store_drain_ms.take.py")
    assert drain.read(v) == pytest.approx(1e3 * 0.5 / 2)


def test_covered_by_disjoint_intervals():
    import numpy as np

    got = tr.covered([(1.0, 2.0), (3.0, 5.0)], np.array([0.0, 1.5, 4.0, 6.0]),
                     np.array([10.0, 3.5, 4.5, 7.0]))
    assert got == pytest.approx([3.0, 1.0, 0.5, 0.0])


def test_timeline_names_the_innermost_open_span():
    v = view()
    assert v.timeline() == [
        (0.0, 1.0, "search"), (1.0, 2.0, "dataset.take:embedding"),
        (2.0, 2.5, "ops.fullzip_gather"), (2.5, 3.0, "dataset.take:embedding"),
        (3.0, 3.5, "drain:take:embedding"),
        (3.5, 4.0, "dataset.take:embedding"), (4.0, 6.0, "search"),
        (6.0, 8.0, "ops.ivf_topk"), (8.0, 10.0, "search")]
    # a span that outlives the one it started in, and time outside any span
    w = tr.TraceView((0.0, 4.0), [("a", 1.0, 2.0), ("b", 1.5, 3.0)], [])
    assert w.timeline() == [(0.0, 1.0, "(no span)"), (1.0, 1.5, "a"),
                            (1.5, 3.0, "b"), (3.0, 4.0, "(no span)")]


def test_idle_time_by_host_span():
    v = view()
    assert v.idle_gaps() == [(0.0, 2.1), (2.3, 6.5), (7.5, 9.0), (9.25, 10.0)]
    bd = v.breakdown()
    idle = dict(bd["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(10 - v.busy_s)
    assert idle == pytest.approx({
        "search": 1.0 + 2.0 + 1.0 + 0.75, "dataset.take:embedding": 2.0,
        "ops.fullzip_gather": 0.3, "drain:take:embedding": 0.5,
        "ops.ivf_topk": 1.0})
    ops = dict(bd["device_ops"])
    assert ops["%ivf_topk_pallas.2 = (f32[8,128], s32[8,128]) custom-call"] \
        == pytest.approx(1.2)
    assert "%copy.3 = f32[8] copy" in ops
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_roofline_share():
    v = view()
    v.work = {"ivf_topk_roofline": [(200.0, 5.0), (100.0, 20.0)]}
    # least time: max(2, 0.5) + max(1, 2) = 4 s over the program's 1.2 s
    assert v.roofline_pct("ivf_topk_roofline", ("jit_ivf_topk_pallas",)) == \
        pytest.approx(100 * 4 / 1.2)
    assert v.roofline_pct("fullzip_gather_roofline",
                          ("jit_fullzip_gather_pallas",)) is None
    roof = load_module(BENCH / "metrics" / "ivf_topk_roofline.py")
    assert roof.read(v) == pytest.approx(100 * 4 / 1.2)


def test_a_cpu_profile_reads_back(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with tr.profiled(str(tmp_path)):
        with tr._Annotation("dataset.take:tag"):
            with tr._Annotation("ops.miniblock_decode"):
                f(x).block_until_ready()
    v = tr.load(str(tmp_path))
    names = [s[0] for s in v.spans]
    assert "dataset.take:tag" in names and "ops.miniblock_decode" in names
    lo, hi = v.window
    assert all(lo <= a <= b <= hi for _, a, b in v.spans)
    assert v.span_s(["dataset.take:"]) >= v.span_s(["ops."]) > 0
