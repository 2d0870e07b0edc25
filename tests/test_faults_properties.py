"""Property-based failure/recovery contracts.

Stated over arbitrary drain shapes and fault schedules:

(a) ``TransientErrors(error_prob=0)`` is bit-identical to the healthy run —
    the recovery layer must not perturb timings unless an op can actually
    fail (healthy-path bit-identity, ARCHITECTURE.md contract #8);
(b) retries and failover never change the logical accounting plane: the
    drain records priced into a job are never mutated by a faulted run;
(c) makespan is monotone non-decreasing in ``error_prob`` up to sub-round
    scheduling slack, for an uncontended job — failure draws nest (one
    uniform per (tier, unit, slot, attempt) compared against the
    threshold), so raising the probability only adds failures.  The slack
    and the single-job restriction are load-bearing: requeued slots repack
    rounds and failover re-prices only the surviving slots, so completions
    can shift by a few device slot times either way, and under contention
    a backed-off unit frees round slots for *other* jobs entirely —
    empirically up to ~10% of makespan.  What nests is the failure set,
    not the schedule built from it;
(d) failover never loses or duplicates a request:
    completed + failed + shed == submitted, each label exactly once.
"""

from hypothesis import given, settings, strategies as st

from repro.core.io_sim import NVME, S3, Blackout, TransientErrors
from repro.obs.slo import Shedder, SLObjective, SLOMonitor
from repro.store import EventLoop, RetryPolicy, build_job
from repro.store.stats import DrainRecord

DEVICES = [NVME, S3]

# one tier's slice of a drain: {phase: ops} with plausible byte loads
_PHASE = st.integers(0, 2)
_BUCKET = st.tuples(_PHASE, st.integers(1, 64), st.integers(0, 1 << 20))


def _record(buckets_by_tier):
    tiers = {}
    for tier, buckets in buckets_by_tier.items():
        phase_ops, phase_bytes = {}, {}
        for phase, ops, nbytes in buckets:
            phase_ops[phase] = phase_ops.get(phase, 0) + ops
            phase_bytes[phase] = phase_bytes.get(phase, 0) + nbytes
        if phase_ops:
            tiers[tier] = (phase_ops, phase_bytes)
    return DrainRecord("take:p", 1, tiers)


_JOBS = st.lists(
    st.tuples(st.dictionaries(st.integers(0, 1),
                              st.lists(_BUCKET, min_size=1, max_size=2),
                              min_size=1, max_size=2),
              st.floats(0.0, 0.01)),
    min_size=1, max_size=6)


def _build(jobs_spec, tenant="t"):
    return [build_job(_record(buckets), DEVICES, tenant=tenant, submit=at,
                      seq=i) for i, (buckets, at) in enumerate(jobs_spec)]


@settings(max_examples=100, deadline=None)
@given(jobs_spec=_JOBS, queue_depth=st.integers(1, 64),
       seed=st.integers(0, 2**32))
def test_zero_error_prob_is_bit_identical_to_healthy(jobs_spec, queue_depth,
                                                     seed):
    jobs = _build(jobs_spec)
    healthy = EventLoop(DEVICES, queue_depth).run(jobs)
    dev = [NVME.with_fault(TransientErrors(0.0, error_prob=0.0, seed=seed)),
           S3.with_fault(TransientErrors(0.0, error_prob=0.0, seed=seed))]
    out = EventLoop(dev, queue_depth, retry=RetryPolicy(seed=seed)).run(jobs)
    assert out.completions == healthy.completions
    assert out.counters == {}


@settings(max_examples=100, deadline=None)
@given(jobs_spec=_JOBS, queue_depth=st.integers(1, 64),
       error_prob=st.floats(0.0, 0.5), seed=st.integers(0, 2**32))
def test_retries_never_change_logical_accounting(jobs_spec, queue_depth,
                                                 error_prob, seed):
    jobs = _build(jobs_spec)
    loads = [[(u.tier, u.phase, u.ops, u.nbytes) for u in j.units]
             for j in jobs]
    dev = [NVME.with_fault(TransientErrors(0.0, error_prob=error_prob,
                                           seed=seed)), S3]
    out = EventLoop(dev, queue_depth, retry=RetryPolicy(seed=seed)).run(jobs)
    # the job structures priced from the drain records are untouched: the
    # recovery layer retries *timing*, never logical IOPS/bytes
    assert [[(u.tier, u.phase, u.ops, u.nbytes) for u in j.units]
            for j in jobs] == loads
    assert len(out.completions) == len(jobs)
    # and a replay from the same inputs is bit-identical (determinism)
    again = EventLoop(dev, queue_depth,
                      retry=RetryPolicy(seed=seed)).run(jobs)
    assert again.completions == out.completions
    assert again.counters == out.counters


@settings(max_examples=60, deadline=None)
@given(ops=st.integers(1, 200), nbytes=st.integers(0, 1 << 20),
       phase=_PHASE, queue_depth=st.integers(1, 256),
       probs=st.tuples(st.floats(0.0, 0.9), st.floats(0.0, 0.9)),
       seed=st.integers(0, 2**32))
def test_makespan_monotone_in_error_prob(ops, nbytes, phase, queue_depth,
                                         probs, seed):
    lo, hi = sorted(probs)
    rec = DrainRecord("take:p", 1, {0: ({phase: ops}, {phase: nbytes})})
    jobs = [build_job(rec, DEVICES, seq=0)]

    def run(p):
        dev = [NVME.with_fault(TransientErrors(0.0, error_prob=p,
                                               seed=seed)), S3]
        return EventLoop(dev, queue_depth,
                         retry=RetryPolicy(jitter=0.0)).run(jobs)

    m_lo, m_hi = run(lo).makespan, run(hi).makespan
    assert m_hi >= m_lo * (1 - 1e-3) - 1e-3


@settings(max_examples=60, deadline=None)
@given(jobs_spec=_JOBS, error_prob=st.floats(0.0, 1.0),
       seed=st.integers(0, 2**32), shed_every=st.integers(0, 3),
       failover=st.booleans())
def test_failover_conserves_requests(jobs_spec, error_prob, seed, shed_every,
                                     failover):
    # tenants alternate so a forced shedder can reject a deterministic
    # subset; NVMe takes transient errors, S3 a mid-run blackout — requests
    # may retry, fail over, exhaust or be shed, but each submitted label
    # completes exactly once: completed + failed + shed == submitted
    jobs = []
    for i, (buckets, at) in enumerate(jobs_spec):
        tenant = "shed" if shed_every and i % shed_every == 0 else "keep"
        jobs.append(build_job(_record(buckets), DEVICES, tenant=tenant,
                              submit=at, seq=i))
    dev = [NVME.with_fault(TransientErrors(0.0, error_prob=error_prob,
                                           seed=seed)),
           S3.with_fault(Blackout(0.02, 0.06))]
    mon = SLOMonitor({"keep": SLObjective(1.0)})
    sh = Shedder(mon, protect=("keep",), shed=("shed",), hold_s=1e9)
    sh.active = True  # latched for the whole run by the huge hold-down
    pol = RetryPolicy(max_retries=2, failover=failover, seed=seed)
    out = EventLoop(dev, 32, retry=pol, shedder=sh).run(jobs)
    assert len(out.completions) == len(jobs)
    assert sorted(c.label for c in out.completions) == \
        sorted(j.label for j in jobs)
    done = sum(1 for c in out.completions if c.error is None)
    shed = sum(1 for c in out.completions if c.error == "shed")
    failed = sum(1 for c in out.completions
                 if c.error and c.error.startswith("io:"))
    assert done + shed + failed == len(jobs)
    assert shed == sum(1 for j in jobs if j.tenant == "shed")
    # every error is one of the documented sinks; no other values leak out
    assert all(c.error in (None, "shed", "io:nvme_970evo", "io:s3")
               for c in out.completions)
