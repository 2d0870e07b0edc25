"""Live metrics plane + SLO subsystem: log-bucket histograms, windowed
rotation, virtual-clock gauges, burn-rate alerts, fault injection, per-tier
queue depths, closed-loop arrivals, and the exporters/tools on top.

The tentpole contracts under test:

* log-bucket quantiles agree with exact nearest-rank within the configured
  relative error; merge is exact (bucket-wise addition);
* window rotation never loses counts (``total.count == dropped + live``);
* the disabled plane allocates nothing, and an enabled plane attached to
  the event loop leaves completions bit-identical (sampling is read-only);
* a Degradation on a device stretches only the interleaved timing after
  its start — serial pricing and all priced accounting stay fault-blind;
* the SLO monitor fires on the rising edge of a multi-window burn and the
  serve-style degradation is detected within a bounded virtual delay.
"""

import importlib.util
import json
import random
from pathlib import Path

import pytest

from repro.core.io_sim import DRAM, NVME, S3, Degradation
from repro.obs import (
    NULL_PLANE,
    NULL_TRACER,
    BurnWindow,
    GaugeSeries,
    LogBucketHistogram,
    MetricsPlane,
    MetricsRegistry,
    SLObjective,
    SLOMonitor,
    Tracer,
    WindowedHistogram,
    percentile,
    prometheus_text,
)
from repro.store import EventLoop, Job, QoS, build_job
from repro.store.stats import DrainRecord

ROOT = Path(__file__).resolve().parent.parent


def _load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _rec(label, tiers, n_requests=1):
    """Shorthand synthetic drain: tiers = {tier: (ops, nbytes, phase)}."""
    return DrainRecord(label, n_requests,
                       {t: ({p: ops}, {p: nb})
                        for t, (ops, nb, p) in tiers.items()})


# ---------------------------------------------------------------------------
# log-bucket histogram
# ---------------------------------------------------------------------------


def test_log_bucket_quantiles_within_relative_error():
    rng = random.Random(42)
    for rel_err in (0.05, 0.01):
        h = LogBucketHistogram(rel_err)
        xs = [rng.lognormvariate(0.0, 2.0) for _ in range(4000)]
        for x in xs:
            h.observe(x)
        for q in (1, 10, 25, 50, 75, 90, 99, 99.9):
            exact = percentile(xs, q)
            approx = h.quantile(q)
            assert abs(approx - exact) <= rel_err * exact * 1.0001, \
                (rel_err, q, exact, approx)


def test_log_bucket_extremes_and_zeros_exact():
    h = LogBucketHistogram(0.01)
    for v in (0.0, 0.0, 3.5, 700.25):
        h.observe(v)
    assert h.min == 0.0 and h.max == 700.25
    assert h.quantile(0) == 0.0 and h.quantile(100) == 700.25
    assert h.quantile(50) == 0.0                 # 2 of 4 samples are zero
    assert h.count == 4 and h.sum == pytest.approx(703.75)
    with pytest.raises(ValueError):
        h.observe(-1.0)


def test_log_bucket_merge_is_exact():
    rng = random.Random(7)
    xs = [rng.expovariate(1.0) for _ in range(500)]
    ys = [rng.expovariate(0.1) for _ in range(300)]
    both = LogBucketHistogram(0.02)
    for v in xs + ys:
        both.observe(v)
    a = LogBucketHistogram(0.02)
    b = LogBucketHistogram(0.02)
    for v in xs:
        a.observe(v)
    for v in ys:
        b.observe(v)
    a.merge(b)
    assert a.buckets == both.buckets
    assert a.count == both.count and a.sum == pytest.approx(both.sum)
    assert a.min == both.min and a.max == both.max
    with pytest.raises(ValueError):
        a.merge(LogBucketHistogram(0.01))   # mismatched rel_err


def test_log_bucket_empty_summary_and_quantile():
    h = LogBucketHistogram()
    s = h.summary()
    assert s == {"count": 0, "mean": None, "p50": None, "p99": None,
                 "p999": None, "max": None}
    with pytest.raises(ValueError):
        h.quantile(50)


# ---------------------------------------------------------------------------
# windowed histogram
# ---------------------------------------------------------------------------


def test_window_rotation_never_loses_counts():
    w = WindowedHistogram(window=1.0, n_windows=4, rel_err=0.01)
    rng = random.Random(0)
    n = 0
    for _ in range(500):
        t = rng.uniform(0, 40)
        w.observe(t, rng.uniform(0.1, 10))
        n += 1
        live = w.live_count   # lazy expiry may move counts into dropped
        assert w.total.count == w.dropped + live
    assert w.total.count == n


def test_window_live_horizon_and_straggler():
    w = WindowedHistogram(window=1.0, n_windows=2, rel_err=0.01)
    w.observe(0.5, 1.0)
    w.observe(1.5, 2.0)
    assert w.live_count == 2
    w.observe(2.5, 3.0)       # rotates window 0 out (slot reuse)
    assert w.live_count == 2 and w.dropped == 1
    w.observe(0.1, 9.0)       # straggler older than the whole horizon
    assert w.live_count == 2 and w.dropped == 2
    assert w.total.count == 4
    merged = w.merged()
    assert merged.count == 2
    assert w.quantile(100) == pytest.approx(3.0, rel=0.01)


def test_window_summary_shape():
    w = WindowedHistogram(window=0.5, n_windows=4)
    w.observe(0.1, 0.25)
    s = w.summary()
    assert s["count"] == 1 and s["lifetime_count"] == 1
    assert s["window_s"] == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# gauges + plane
# ---------------------------------------------------------------------------


def test_gauge_series_export_downsamples_deterministically():
    g = GaugeSeries("x")
    for i in range(100):
        g.sample(i * 0.1, float(i))
    full = g.export()
    assert full["n_samples"] == 100 and len(full["t"]) == 100
    small = g.export(max_points=10)
    assert len(small["t"]) <= 11 and small["v"][-1] == 99.0
    assert small == g.export(max_points=10)   # deterministic
    assert g.between(1.0, 2.0) == [10.0 + k for k in range(10)]


def test_disabled_plane_allocates_nothing():
    assert not NULL_PLANE.enabled
    NULL_PLANE.sample("tier.x.utilization", 1.0, 0.5)
    NULL_PLANE.observe_latency("lat.t", 1.0, 0.1)
    assert NULL_PLANE.series == {} and NULL_PLANE.latency == {}


def test_plane_prometheus_and_export_are_json_safe():
    p = MetricsPlane(window=0.5, n_windows=4)
    p.counter("slo.breach.premium").inc(2)
    p.sample("tier.nvme.utilization", 0.5, 0.75)
    p.observe_latency("latency.premium", 0.5, 0.004)
    text = p.prometheus_text()
    assert "# TYPE slo_breach_premium counter" in text
    assert "slo_breach_premium 2" in text
    assert "# TYPE tier_nvme_utilization gauge" in text
    assert "latency_premium_bucket" in text and 'le="+Inf"' in text
    assert "latency_premium_count 1" in text
    # export is embeddable in the NaN-refusing bench artifact writer
    blob = json.dumps(p.export(), allow_nan=False)
    back = json.loads(blob)
    assert back["counters"] == {"slo.breach.premium": 2}
    assert back["series"]["tier.nvme.utilization"]["v"] == [0.75]


def test_plane_to_trace_emits_virtual_clock_counters():
    p = MetricsPlane()
    p.sample("tier.nvme.utilization", 0.25, 0.5)
    p.sample("tier.nvme.utilization", 0.75, 1.0)
    tr = Tracer()
    n = p.to_trace(tr)
    assert n == 2
    evs = [e for e in tr.events if e["ph"] == "C"]
    assert [e["ts"] for e in evs] == [0.25e6, 0.75e6]
    assert evs[0]["args"] == {"value": 0.5}


def test_tracer_counter_ts_override():
    tr = Tracer()
    tr.counter("c", {"v": 1.0}, ts=123.0)
    tr.counter("c", {"v": 2.0})
    assert tr.events[0]["ts"] == 123.0
    assert tr.events[1]["ts"] != 123.0


# ---------------------------------------------------------------------------
# registry satellites: empty-histogram summary, summaries(), prometheus_text
# ---------------------------------------------------------------------------


def test_empty_histogram_summary_is_none_valued():
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    assert h.summary() == {"count": 0, "mean": None, "p50": None,
                           "p99": None, "p999": None, "max": None}
    json.dumps(h.summary(), allow_nan=False)   # NaN-free policy


def test_registry_summaries_prefix_snapshot():
    reg = MetricsRegistry()
    reg.histogram("a.x").observe(1.0)
    reg.histogram("a.y")          # empty: must not raise
    reg.histogram("b.z").observe(2.0)
    snap = reg.summaries("a.")
    assert sorted(snap) == ["a.x", "a.y"]
    assert snap["a.x"]["count"] == 1 and snap["a.y"]["count"] == 0


def test_prometheus_text_from_registry():
    reg = MetricsRegistry()
    reg.counter("decode.fallback.fullzip.float-values").inc(3)
    reg.histogram("take.lat").observe_many([1.0, 2.0, 3.0])
    text = prometheus_text(reg)
    assert "# TYPE decode_fallback_fullzip_float_values counter" in text
    assert "decode_fallback_fullzip_float_values 3" in text
    assert 'take_lat{quantile="0.5"} 2.0' in text
    assert "take_lat_count 3" in text


# ---------------------------------------------------------------------------
# Degradation model
# ---------------------------------------------------------------------------


def test_degradation_schedule_and_compounding():
    d1 = Degradation(start=1.0, end=2.0, latency_factor=4.0)
    d2 = Degradation(start=1.5, latency_factor=2.0, throughput_factor=0.5)
    dev = NVME.with_fault(d1).with_fault(d2)
    assert NVME.faults == ()              # frozen base is untouched
    assert dev.latency_factor_at(0.5) == 1.0
    assert dev.latency_factor_at(1.2) == 4.0
    assert dev.latency_factor_at(1.7) == 8.0     # overlap compounds
    assert dev.latency_factor_at(2.5) == 2.0     # d1 expired, d2 open-ended
    assert dev.bandwidth_factor_at(1.7) == 0.5
    with pytest.raises(ValueError):
        Degradation(start=0.0, latency_factor=0.0)
    with pytest.raises(ValueError):
        Degradation(start=2.0, end=1.0)


def test_fault_stretches_interleaved_only_after_start():
    rec = _rec("take", {0: (64, 1 << 20, 0)})
    dev_ok = NVME
    dev_bad = NVME.with_fault(Degradation(start=100.0, latency_factor=50.0,
                                          throughput_factor=0.1))
    job_a = build_job(rec, [dev_ok])
    job_b = build_job(rec, [dev_ok])
    base = EventLoop([dev_ok], queue_depth=8).run([job_a]).makespan
    # fault starts far in the future: timing identical
    pre = EventLoop([dev_bad], queue_depth=8).run([job_b]).makespan
    assert pre == base
    # fault active from t=0: strictly slower
    dev_now = NVME.with_fault(Degradation(start=0.0, latency_factor=50.0,
                                          throughput_factor=0.1))
    job_c = build_job(rec, [dev_ok])
    hot = EventLoop([dev_now], queue_depth=8).run([job_c]).makespan
    assert hot > base
    # serial pricing is fault-blind: identical under both devices
    job_d = build_job(rec, [dev_ok])
    s_ok = EventLoop([dev_ok], queue_depth=8).run([job_d], mode="serial")
    s_bad = EventLoop([dev_now], queue_depth=8).run([job_d], mode="serial")
    assert s_ok.completions == s_bad.completions


# ---------------------------------------------------------------------------
# event-loop sampling: bit-identity + utilization saturation
# ---------------------------------------------------------------------------


def _jobs(n=20, submit_gap=0.001):
    jobs = []
    for i in range(n):
        rec = _rec(f"take#{i}", {0: (16, 256 << 10, 0), 1: (2, 64 << 10, 0)})
        jobs.append(build_job(rec, [NVME, S3], tenant="t",
                              submit=i * submit_gap, seq=i))
    return jobs


def test_plane_sampling_leaves_completions_bit_identical():
    plain = EventLoop([NVME, S3], queue_depth=8).run(_jobs())
    plane = MetricsPlane(window=0.01, n_windows=8)
    slo = SLOMonitor({"t": SLObjective(0.5)}, registry=plane.registry,
                     plane=plane)
    sampled = EventLoop([NVME, S3], queue_depth=8, plane=plane,
                        slo=slo).run(_jobs())
    assert sampled.completions == plain.completions
    assert sampled.tiers == plain.tiers
    # ... and the plane actually collected the documented gauges
    names = set(plane.series)
    assert f"tier.{NVME.name}.utilization" in names
    assert f"tier.{NVME.name}.outstanding" in names
    assert f"tier.{NVME.name}.pipe_backlog" in names
    assert "jobs.in_flight" in names
    assert plane.latency["latency.t"].total.count == len(plain.completions)


def test_degraded_utilization_saturates_and_slo_fires():
    # arrivals spread over ~0.6s so NVMe rounds are still being issued when
    # the fault starts mid-run
    jobs = _jobs(n=60, submit_gap=0.01)
    healthy = EventLoop([NVME, S3], queue_depth=8).run(_jobs(60, 0.01))
    t_deg = 0.2
    bad = NVME.with_fault(Degradation(start=t_deg, latency_factor=300.0,
                                      throughput_factor=0.01))
    plane = MetricsPlane(window=0.05, n_windows=8)
    lat = [c.latency for c in healthy.completions]
    obj = SLObjective(latency_s=max(lat) * 1.1, target=0.99)
    slo = SLOMonitor({"t": obj},
                     windows=(BurnWindow(0.2, 0.025, 2.0),),
                     registry=plane.registry, plane=plane)
    EventLoop([bad, S3], queue_depth=8, plane=plane, slo=slo).run(jobs)
    util = plane.series[f"tier.{NVME.name}.utilization"]
    post = util.between(t_deg, float("inf"))
    assert post and max(post) > 0.9
    alert = slo.first_alert("t")
    assert alert is not None and alert.at >= t_deg
    assert plane.registry.counter("slo.breach.t").value >= 1


# ---------------------------------------------------------------------------
# per-tier queue depths
# ---------------------------------------------------------------------------


def test_per_tier_queue_depth_lone_job_degeneration():
    rec = _rec("take", {0: (64, 1 << 20, 0), 1: (10, 2 << 20, 1)})
    depths = {NVME.name: 4, S3.name: 2}
    job = build_job(rec, [NVME, S3])
    serial = job.serial_time(256, depths)
    lone = EventLoop([NVME, S3], queue_depth=256,
                     queue_depths=depths).run([build_job(rec, [NVME, S3])])
    assert lone.completions[0].done == pytest.approx(serial, rel=1e-12)
    # the override really binds: shallower NVMe depth costs more rounds
    assert serial > job.serial_time(256)


def test_per_tier_depth_falls_back_to_shared():
    rec = _rec("take", {0: (64, 1 << 20, 0)})
    job = build_job(rec, [NVME])
    assert job.serial_time(8, {"some_other_dev": 2}) \
        == job.serial_time(8)
    loop = EventLoop([NVME, S3], queue_depth=8, queue_depths={S3.name: 2})
    assert loop.qd_for(NVME) == 8 and loop.qd_for(S3) == 2


# ---------------------------------------------------------------------------
# SLO monitor semantics
# ---------------------------------------------------------------------------


def test_burn_rate_math_and_rising_edge():
    reg = MetricsRegistry()
    tr = Tracer()
    mon = SLOMonitor({"t": SLObjective(latency_s=0.01, target=0.9)},
                     windows=(BurnWindow(1.0, 0.25, 2.0),),
                     tracer=tr, registry=reg)
    # 10% budget; burn >= 2 needs bad fraction >= 0.2 in both windows
    t = 0.0
    for _ in range(20):
        t += 0.01
        mon.observe("t", t, 0.001)       # all good: no alert
    assert mon.alerts == []
    for _ in range(20):
        t += 0.01
        mon.observe("t", t, 0.05)        # all bad: fires once
    assert len(mon.alerts) == 1
    a = mon.alerts[0]
    assert a.burn_long >= 2.0 and a.burn_short >= 2.0
    assert reg.counter("slo.breach.t").value == 1
    assert any(e["name"] == "slo_breach:t" for e in tr.events)
    # recovery resets the latch; a second incident fires a second alert
    for _ in range(200):
        t += 0.01
        mon.observe("t", t, 0.001)
    for _ in range(40):
        t += 0.01
        mon.observe("t", t, 0.05)
    assert len(mon.alerts) == 2
    assert reg.counter("slo.requests.t").value == 280
    assert reg.counter("slo.bad.t").value == 60


def test_slo_monitor_ignores_tenants_without_objective():
    mon = SLOMonitor({"premium": SLObjective(0.01)})
    mon.observe("standard", 1.0, 99.0)
    assert mon.alerts == [] and mon.table()[0]["requests"] == 0


def test_slo_table_shape():
    mon = SLOMonitor({"p": SLObjective(0.02, 0.95)})
    mon.observe("p", 0.1, 0.001)
    mon.observe("p", 0.2, 0.5)
    (row,) = mon.table()
    assert row["tenant"] == "p" and row["requests"] == 2 and row["bad"] == 1
    assert row["bad_fraction"] == pytest.approx(0.5)
    assert row["objective_ms"] == pytest.approx(20.0)
    json.dumps(mon.table(), allow_nan=False)


# ---------------------------------------------------------------------------
# closed-loop arrivals
# ---------------------------------------------------------------------------


def test_closed_loop_chain_orders_requests_per_client():
    # two chained jobs for one client: the second arrives think after the
    # first completes, in both interleaved and serial pricing
    rec = _rec("take", {0: (16, 256 << 10, 0)})
    a = build_job(rec, [NVME], seq=1)
    b = build_job(rec, [NVME], seq=2)
    b.after, b.think = a, 0.5
    for mode in ("interleaved", "serial"):
        res = EventLoop([NVME], queue_depth=8).run([a, b], mode=mode)
        ca = next(c for c in res.completions if c.submit < 0.5)
        cb = next(c for c in res.completions if c.submit >= 0.5)
        assert cb.submit == pytest.approx(ca.done + 0.5)
        assert cb.latency == pytest.approx(ca.latency)  # no queueing either


def test_closed_loop_dependency_outside_run_is_ignored():
    rec = _rec("take", {0: (4, 4096, 0)})
    ghost = build_job(rec, [NVME], seq=1)
    dep = build_job(rec, [NVME], seq=2)
    dep.after, dep.think = ghost, 99.0
    res = EventLoop([NVME], queue_depth=8).run([dep])
    assert len(res.completions) == 1 and res.completions[0].submit == 0.0


def test_zipf_closed_generation_and_open_bit_identity():
    from repro.serve.workload import TenantSpec, ZipfWorkload
    tenants = [TenantSpec("a", share=1.0), TenantSpec("b", share=1.0)]
    base = ZipfWorkload(1000, tenants, 50, seed=5).generate()
    # new knobs must not perturb the open-loop stream (seed behaviour)
    same = ZipfWorkload(1000, tenants, 50, seed=5, arrival="open",
                        think_time=9.0, clients_per_tenant=7).generate()
    assert [(r.tenant, r.at, r.rows.tolist()) for r in base] \
        == [(r.tenant, r.at, r.rows.tolist()) for r in same]
    assert all(r.client is None for r in base)
    closed = ZipfWorkload(1000, tenants, 50, seed=5, arrival="closed",
                          clients_per_tenant=3).generate()
    assert all(r.at == 0.0 and r.client for r in closed)
    # round-robin client assignment within each tenant
    a_clients = [r.client for r in closed if r.tenant == "a"]
    assert a_clients[:4] == ["a/c0", "a/c1", "a/c2", "a/c0"][:len(a_clients)]
    with pytest.raises(ValueError):
        ZipfWorkload(1000, tenants, 5, arrival="drip")


def test_zipf_slo_objectives_from_tenant_spec():
    from repro.serve.workload import TenantSpec, ZipfWorkload
    tenants = [TenantSpec("p", slo_ms=5.0, slo_target=0.999),
               TenantSpec("s")]
    wl = ZipfWorkload(100, tenants, 5)
    objs = wl.slo_objectives()
    assert set(objs) == {"p"}
    assert objs["p"].latency_s == pytest.approx(0.005)
    assert objs["p"].target == 0.999


# ---------------------------------------------------------------------------
# tools: bench_gate slo strictness
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def bench_gate():
    return _load_module(ROOT / "tools" / "bench_gate.py", "bench_gate_mp")


def test_bench_gate_slo_keys_always_strict(bench_gate):
    base = {"slo": {"degraded": {"requests_per_s": 100}},
            "metrics_plane": {"counters": {"slo.breach.premium": 1}}}
    worse = {"slo": {"degraded": {"requests_per_s": 150}},
             "metrics_plane": {"counters": {"slo.breach.premium": 2}}}
    fails = bench_gate.compare(base, worse)
    # both drift inside slo paths: strict despite the rate-marker name
    assert len(fails) == 2
    # outside an slo path the same key is still rate-skipped
    assert bench_gate.compare({"x": {"requests_per_s": 1}},
                              {"x": {"requests_per_s": 9}}) == []
