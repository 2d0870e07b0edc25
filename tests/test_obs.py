"""Observability layer: span tracer, metrics registry, per-request latency
attribution, the store's drain log, and the bench regression gate.

The two contracts everything else leans on:

* tracing is *observation only* — logical IO stats and modelled times are
  bit-identical traced vs untraced, and a disabled tracer allocates no span
  objects on the hot path;
* attribution is *exact* — per-tier attributed drain costs sum to each
  tier's ``model_time`` within 1e-9 relative (floating-point remainder
  assignment, not approximation).
"""

import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

from repro.core import arrays as A
from repro.core.file import FileReader, WriteOptions, write_table
from repro.core.io_sim import NVME
from repro.obs import (
    MetricsRegistry,
    NULL_SPAN,
    NULL_TRACER,
    NullTracer,
    Tracer,
    attribute,
    percentile,
)
from repro.store import DrainRecord, TierStats, WorkloadStats

ROOT = Path(__file__).resolve().parent.parent


def _load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _mb_reader(n=20_000, seed=0, **kw):
    rng = np.random.default_rng(seed)
    arr = A.PrimitiveArray.build(
        rng.integers(0, 1 << 20, n).astype(np.int64),
        validity=rng.random(n) > 0.03)
    fb = write_table({"c": arr}, WriteOptions("lance-miniblock"))
    return FileReader(fb, **kw), n


# ---------------------------------------------------------------------------
# TierStats / WorkloadStats direct coverage
# ---------------------------------------------------------------------------


def test_tier_stats_phase_buckets_roundtrip():
    s = TierStats("t")
    s.add_op(4096, phase=0)
    s.add_op(8192, phase=0, prefetch=True)
    s.add_write_op(4096, phase=1, flush=True)
    assert s.phase_ops == {0: 2, 1: 1}
    assert s.phase_bytes == {0: 12288, 1: 4096}
    assert (s.n_iops, s.write_iops) == (2, 1)
    assert (s.prefetch_bytes, s.flush_bytes) == (8192, 4096)
    drained = s.end_batch()
    assert drained == ({0: 2, 1: 1}, {0: 12288, 1: 4096})
    assert s.phase_ops == {} and s.phase_bytes == {}
    assert s.batch_phases == [{0: 2, 1: 1}]
    assert s.end_batch() is None          # empty batch drains nothing
    snap = s.snapshot()
    s.reset()
    assert snap.batch_phases == [{0: 2, 1: 1}] and s.batch_phases == []


def test_tier_stats_hit_rate_never_nan():
    s = TierStats("t")
    assert s.hit_rate is None
    s.hits, s.misses = 3, 1
    assert s.hit_rate == 0.75


def test_more_phases_cost_strictly_more_latency():
    """Same ops and bytes, deeper dependency chain => strictly more queue
    drains => strictly more modelled time."""
    flat, deep = TierStats("flat"), TierStats("deep")
    for i in range(8):
        flat.add_op(4096, phase=0)
        deep.add_op(4096, phase=i)
    flat.end_batch()
    deep.end_batch()
    assert deep.model_time(NVME) > flat.model_time(NVME)
    # the gap is exactly the 7 extra round trips
    assert deep.model_time(NVME) - flat.model_time(NVME) == \
        pytest.approx(7 * NVME.latency)


def test_workload_scan_fraction_none_and_bias_flip():
    w = WorkloadStats()
    assert w.scan_fraction is None
    assert w.preferred_admission() == "always"   # cold-start default
    # the scan must beat takes by the hysteresis margin to earn a flip
    w.note_batch("scan:c", prefetch=True, n_ops=4, nbytes=1100)
    w.note_batch("take:c", prefetch=False, n_ops=4, nbytes=999)
    assert w.scan_fraction == pytest.approx(1100 / 2099)
    assert w.preferred_admission() == "second_touch"
    # bias < 1 discounts scans: the same trace now reads take-heavy
    w2 = WorkloadStats(scan_bias=0.5)
    w2.note_batch("scan:c", prefetch=True, n_ops=4, nbytes=1100)
    w2.note_batch("take:c", prefetch=False, n_ops=4, nbytes=999)
    assert w2.preferred_admission() == "always"


# ---------------------------------------------------------------------------
# Attribution exactness
# ---------------------------------------------------------------------------


def test_attribution_sums_match_model_time_1e9():
    fr, n = _mb_reader(store="tiered")
    rng = np.random.default_rng(3)
    for _ in range(6):
        fr.take("c", rng.integers(0, n, 64))
    fr.scan("c")
    att = attribute(fr.store, queue_depth=fr.scheduler.queue_depth)
    sums = att.tier_sums()
    devices = [lvl.device for lvl in fr.store.levels] + [fr.store.backing]
    checked = 0
    for stats, dev in zip(fr.store.tier_stats(), devices):
        mt = stats.model_time(dev, fr.scheduler.queue_depth)
        if mt:
            assert abs(sums[stats.name] - mt) / mt < 1e-9
            checked += 1
    assert checked >= 2  # NVMe cache and S3 backing both saw traffic
    assert att.total == pytest.approx(fr.modelled_time(), rel=1e-9)


def test_attribution_per_request_population():
    fr, n = _mb_reader(store="tiered")
    rng = np.random.default_rng(4)
    for _ in range(5):
        fr.take("c", rng.integers(0, n, 32))
    att = attribute(fr.store, queue_depth=fr.scheduler.queue_depth)
    lats = att.per_request_latencies("take:c")
    assert len(lats) == 5 * 32            # one latency per requested row
    assert all(x >= 0 for x in lats)
    pct = att.percentiles("take:c")
    assert pct["count"] == 160
    assert pct["p50"] <= pct["p99"] <= pct["p999"] <= pct["max"]
    assert att.percentiles("no-such-label") is None   # never NaN


def test_attribution_drain_log_labels_and_requests():
    fr, n = _mb_reader(store="tiered")
    fr.take("c", np.arange(10))
    fr.scan("c")
    log = fr.store.drain_log
    assert [r.label for r in log] == ["take:c", "scan:c"]
    assert isinstance(log[0], DrainRecord)
    assert log[0].n_requests == 10 and log[1].n_requests == 0
    # every logged tier bucket is a ({phase: ops}, {phase: bytes}) pair
    for rec in log:
        for ops, nbytes in rec.tiers.values():
            assert set(ops) == set(nbytes)
            assert all(v > 0 for v in ops.values())


# ---------------------------------------------------------------------------
# Tracer: zero-cost disabled, schema, bit-identity
# ---------------------------------------------------------------------------


def test_disabled_tracer_allocates_no_spans():
    tr = NullTracer()
    s1 = tr.span("a", x=1)
    s2 = tr.span("b")
    assert s1 is NULL_SPAN and s2 is NULL_SPAN   # singleton, no allocation
    assert NULL_TRACER.span("c") is NULL_SPAN
    with s1 as sp:
        sp.set(ignored=True)
    tr.instant("i")
    tr.counter("c", {"v": 1})
    assert tr.events == []


def test_traced_vs_untraced_bit_identical():
    plain, n = _mb_reader(store="tiered")
    traced, _ = _mb_reader(store="tiered", tracer=Tracer())
    rng_a, rng_b = np.random.default_rng(9), np.random.default_rng(9)
    for _ in range(4):
        plain.take("c", rng_a.integers(0, n, 48))
        traced.take("c", rng_b.integers(0, n, 48))
    plain.scan("c")
    traced.scan("c")
    sa, sb = plain.io_stats(), traced.io_stats()
    assert (sa.n_iops, sa.bytes_read) == (sb.n_iops, sb.bytes_read)
    assert plain.modelled_time() == traced.modelled_time()   # bit-equal
    for ta, tb in zip(plain.tier_stats(), traced.tier_stats()):
        assert (ta.n_iops, ta.bytes_read, ta.hits, ta.misses) == \
            (tb.n_iops, tb.bytes_read, tb.hits, tb.misses)
    assert len(traced.tracer.events) > 0 and plain.tracer.events == []


def test_trace_export_chrome_schema(tmp_path):
    tr = Tracer()
    fr, n = _mb_reader(store="tiered", tracer=tr)
    fr.take("c", np.random.default_rng(1).integers(0, n, 32))
    doc = tr.trace_events()
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    assert doc["traceEvents"], "instrumented take emitted no events"
    for ev in doc["traceEvents"]:
        assert {"name", "ph", "ts", "pid", "tid"} <= set(ev)
        # "M" = thread_name metadata naming the per-request tracks
        assert ev["ph"] in ("X", "i", "C", "M")
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
        if ev["ph"] == "i":
            assert ev["s"] == "t"
    names = {e["name"] for e in doc["traceEvents"]}
    assert any(nm.startswith("take:") for nm in names)
    assert any(nm.startswith("drain:") for nm in names)
    out = tmp_path / "trace.json"
    n_events = tr.export(str(out))
    loaded = json.loads(out.read_text())
    assert len(loaded["traceEvents"]) == n_events


def test_trace_export_refuses_nan(tmp_path):
    tr = Tracer()
    tr.instant("bad", value=float("nan"))
    with pytest.raises(ValueError):
        tr.export(str(tmp_path / "t.json"))


def test_pallas_fallback_reason_event():
    tr = Tracer()
    rng = np.random.default_rng(0)
    arr = A.PrimitiveArray.build(rng.standard_normal(512).astype(np.float32))
    fb = write_table({"c": arr}, WriteOptions("lance-miniblock"))
    fr = FileReader(fb, decode="pallas", tracer=tr)
    fr.take("c", rng.integers(0, 512, 16))
    evs = [e for e in tr.events if e["name"] == "pallas_fallback"]
    assert evs and evs[0]["args"]["reason"] == "float-values"
    assert tr.metrics.counter_values("decode.fallback") == \
        {"decode.fallback.miniblock.float-values": 1}


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert percentile(xs, 50) == 50
    assert percentile(xs, 99) == 99
    assert percentile(xs, 99.9) == 100
    assert percentile(xs, 100) == 100
    assert percentile([7.0], 50) == 7.0
    with pytest.raises(ValueError):
        percentile([], 50)


def test_metrics_registry_counters_and_histograms():
    m = MetricsRegistry()
    m.counter("a.b").inc()
    m.counter("a.b").inc(2)
    m.counter("a.c").inc()
    assert m.counter_values("a.") == {"a.b": 3, "a.c": 1}
    h = m.histogram("lat")
    h.observe_many([1.0, 2.0, 3.0, 4.0])
    s = h.summary()
    assert s["count"] == 4 and s["mean"] == pytest.approx(2.5)
    assert s["p50"] == 2.0 and s["max"] == 4.0
    m.reset()
    assert m.counter_values() == {}


# ---------------------------------------------------------------------------
# bench_gate + run.py harness
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def bench_gate():
    return _load_module(ROOT / "tools" / "bench_gate.py", "bench_gate")


def test_bench_gate_compare_rules(bench_gate):
    base = {"meta": {"run": {"git_sha": "aaa"}},
            "cell": {"n_iops": 10, "model_io_s": 0.5,
                     "rows_per_s": 1000, "bytes_read": 4096}}
    same = json.loads(json.dumps(base))
    same["meta"]["run"]["git_sha"] = "bbb"     # provenance never compared
    same["cell"]["rows_per_s"] = 1            # measured rate ignored
    assert bench_gate.compare(base, same) == []
    worse = json.loads(json.dumps(base))
    worse["cell"]["n_iops"] = 11
    fails = bench_gate.compare(base, worse)
    assert len(fails) == 1 and "n_iops" in fails[0]
    drift = json.loads(json.dumps(base))
    drift["cell"]["model_io_s"] = 0.5000001
    assert bench_gate.compare(base, drift) == []       # within 1e-6 rel
    drift["cell"]["model_io_s"] = 0.51
    assert bench_gate.compare(base, drift)
    missing = json.loads(json.dumps(base))
    del missing["cell"]["bytes_read"]
    assert any("missing" in f for f in bench_gate.compare(base, missing))
    # --rates opts measured numbers into a loose band
    assert bench_gate.compare(base, same, rates=True, rate_tol=0.5)


def test_bench_gate_exit_codes(bench_gate, tmp_path):
    basedir, curdir = tmp_path / "base", tmp_path / "cur"
    basedir.mkdir()
    curdir.mkdir()
    art = {"cell": {"n_iops": 10}}
    (basedir / "BENCH_x.json").write_text(json.dumps(art))
    (curdir / "BENCH_x.json").write_text(json.dumps(art))
    assert bench_gate.gate(str(basedir), str(curdir)) == 0
    (curdir / "BENCH_x.json").write_text(
        json.dumps({"cell": {"n_iops": 12}}))
    assert bench_gate.gate(str(basedir), str(curdir)) == 1
    (curdir / "BENCH_x.json").unlink()
    assert bench_gate.gate(str(basedir), str(curdir)) == 1
    assert bench_gate.gate(str(tmp_path / "nothing"), str(curdir)) == 2


def test_committed_smoke_baselines_exist():
    """CI's regression gate is only as real as the committed baselines."""
    basedir = ROOT / "benchmarks" / "baselines" / "smoke"
    names = {p.name for p in basedir.glob("BENCH_*.json")}
    assert {"BENCH_take.json", "BENCH_decode.json",
            "BENCH_dataset.json", "BENCH_ingest.json"} <= names
    take = json.loads((basedir / "BENCH_take.json").read_text())
    assert take["meta"]["run"]["smoke"] is True
    pct = take["serving_latency"]["per_row_us"]
    assert {"p50", "p99", "p999"} <= set(pct)
    assert take["serving_latency"]["attribution_residual_rel"] < 1e-9
    assert take["pallas_fallback_probe"]["n_events"] >= 1


@pytest.fixture(scope="module")
def bench_run():
    return _load_module(ROOT / "benchmarks" / "run.py", "bench_run")


def test_run_name_validation(bench_run):
    with pytest.raises(SystemExit) as ei:
        bench_run._parse_args(["take_decoed"])       # typo must not pass
    assert "unknown benchmark" in str(ei.value)
    assert bench_run._parse_args(["take"]) == {"take"}
    assert bench_run._parse_args(["take_decode"]) == {"take_decode"}
    with pytest.raises(SystemExit):
        bench_run._parse_args(["--store", "bogus"])
    with pytest.raises(SystemExit) as ei:
        bench_run._parse_args(["--list"])
    assert ei.value.code == 0


def test_run_meta_and_nan_refusal(bench_run, tmp_path):
    out = tmp_path / "BENCH_t.json"
    bench_run._dump_json(str(out), {"v": 1})
    doc = json.loads(out.read_text())
    assert {"git_sha", "store", "smoke", "timestamp", "traced", "platform",
            "device_kind", "device_count"} <= set(doc["meta"]["run"])
    with pytest.raises(ValueError):
        bench_run._dump_json(str(out), {"v": float("nan")})


# ---------------------------------------------------------------------------
# Serving plane: attribution exactness with flushes in flight, per-request
# trace tracks, percentile gate rules
# ---------------------------------------------------------------------------


def test_attribution_exact_with_reads_and_flushes_in_flight():
    """Per-tier attributed sums must stay exact to model_time (1e-9) when a
    service window holds concurrent reads AND write-back flush runs — the
    event loop is a timing overlay and must not perturb the accounting the
    attributor prices."""
    from repro.dataset import DatasetWriter
    from repro.store import TieredStore

    rng = np.random.default_rng(6)
    arr = A.PrimitiveArray.build(
        rng.integers(0, 1 << 16, 4000).astype(np.int64))
    fb = write_table({"c": arr}, WriteOptions("lance-fullzip"))
    w = DatasetWriter(
        files=[fb],
        store=lambda d: TieredStore.cached(d, cache_bytes=16 * 4096),
        flush="write-back")
    with w.scheduler.service_window() as win:
        for i in range(4):
            with win.request(tenant="reader", at=i * 1e-4):
                w.take("c", rng.integers(0, 4000, 64))
            with win.request(tenant="ingest", at=i * 1e-4):
                w.append({"c": A.PrimitiveArray.build(
                    rng.integers(0, 100, 300).astype(np.int64))},
                    commit=(i % 2 == 1))
        res = win.run("interleaved")
    # flush runs really were in flight alongside the reads
    labels = {c.label for c in res.completions}
    assert any(lab.startswith("take:") for lab in labels)
    assert any(lab.startswith("flush:") for lab in labels)
    qd = w.scheduler.queue_depth
    att = attribute(w.store, queue_depth=qd)
    sums = att.tier_sums()
    devices = [lvl.device for lvl in w.store.levels] + [w.store.backing]
    checked = 0
    for stats, dev in zip(w.tier_stats(), devices):
        mt = stats.model_time(dev, qd)
        if mt:
            assert abs(sums[stats.name] - mt) / mt < 1e-9
            checked += 1
    assert checked >= 2


def test_trace_per_request_tracks_for_concurrent_takers():
    """Bugfix regression: multi-request traces used to emit one flat span
    stream; scheduler spans must carry a per-request tid (plus the request
    id in args) so Perfetto renders concurrent takers as separate lanes."""
    tr = Tracer()
    fr, n = _mb_reader(store="tiered", tracer=tr)
    with fr.scheduler.service_window() as win:
        with win.request(tenant="a", request="a/0"):
            fr.take("c", np.arange(40))
        with win.request(tenant="b", request="b/0"):
            fr.take("c", np.arange(40, 80))
    drains = [e for e in tr.events
              if e["ph"] == "X" and e["name"].startswith("drain:")]
    assert len(drains) == 2
    assert drains[0]["tid"] != drains[1]["tid"]          # separate lanes
    assert {d["args"]["request"] for d in drains} == {"a/0", "b/0"}
    # thread_name metadata labels each lane with its request id
    meta = {e["tid"]: e["args"]["name"] for e in tr.events
            if e["ph"] == "M" and e["name"] == "thread_name"}
    for d in drains:
        assert meta[d["tid"]] == d["args"]["request"]
    # child spans (coalesce/dispatch) ride the same lane as their drain
    children = [e for e in tr.events if e["ph"] == "X"
                and e["name"].startswith(("coalesce", "dispatch:"))]
    assert children and all(e["tid"] in meta for e in children)
    # untagged requests still get stable distinct per-batch tracks
    tr2 = Tracer()
    fr2, _ = _mb_reader(store="tiered", tracer=tr2)
    fr2.take("c", np.arange(10))
    fr2.take("c", np.arange(10, 20))
    d2 = [e for e in tr2.events
          if e["ph"] == "X" and e["name"].startswith("drain:")]
    assert d2[0]["tid"] != d2[1]["tid"]


def test_bench_gate_percentile_keys_are_strict(bench_gate):
    """Percentile metrics are modelled, not measured: they must be compared
    deterministically even when the key carries a rate-marker substring."""
    assert bench_gate._is_percentile_key("p99_interleaved_ms")
    assert bench_gate._is_percentile_key("latency_p50")
    assert bench_gate._is_percentile_key("p999")
    assert bench_gate._is_percentile_key("p99_speedup_serial_over_interleaved")
    assert not bench_gate._is_percentile_key("rows_per_s")
    assert not bench_gate._is_percentile_key("phase2_ops")
    assert not bench_gate._is_percentile_key("top99")
    base = {"headline": {"p99_interleaved_ms": 10.0, "p50_count": 7,
                         "p99_speedup_serial_over_interleaved": 3.0,
                         "rows_per_s": 100.0}}
    drift = json.loads(json.dumps(base))
    drift["headline"]["p99_interleaved_ms"] = 10.5
    fails = bench_gate.compare(base, drift)
    assert len(fails) == 1 and "p99_interleaved_ms" in fails[0]
    # the speedup percentile is NOT skipped as a rate
    drift2 = json.loads(json.dumps(base))
    drift2["headline"]["p99_speedup_serial_over_interleaved"] = 2.0
    assert bench_gate.compare(base, drift2)
    # integer percentile metadata stays counted-strict
    drift3 = json.loads(json.dumps(base))
    drift3["headline"]["p50_count"] = 8
    assert bench_gate.compare(base, drift3)
    # plain rates are still ignored without --rates
    drift4 = json.loads(json.dumps(base))
    drift4["headline"]["rows_per_s"] = 9.0
    assert bench_gate.compare(base, drift4) == []
