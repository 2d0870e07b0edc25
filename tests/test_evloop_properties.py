"""Property-based event-loop contracts.

The lone-batch degeneration property, stated over arbitrary drain shapes:
for ANY drain record (any tier subset, any phase structure, any op/byte
buckets), a job simulated alone through the interleaved event loop
completes in exactly its serial-drain price — the same per-(batch, phase)
arithmetic as ``TierStats.model_time`` restricted to that one drain.  With
a single outstanding batch the event loop IS the old serial pricing; only
concurrency changes timings, and then only by sharing latency rounds.
"""

import math

import pytest

from hypothesis import given, settings, strategies as st

from repro.core.io_sim import DRAM, NVME, S3
from repro.store import EventLoop, build_job
from repro.store.stats import DrainRecord, TierStats

DEVICES = [DRAM, NVME, S3]

# one tier's slice of a drain: {phase: ops} with plausible byte loads
_PHASE = st.integers(0, 3)
_BUCKET = st.tuples(_PHASE, st.integers(1, 500),
                    st.integers(0, 4 << 20))


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


@settings(max_examples=200, deadline=None)
@given(
    buckets_by_tier=st.dictionaries(
        st.integers(0, 2), st.lists(_BUCKET, min_size=1, max_size=4),
        min_size=1, max_size=3),
    queue_depth=st.integers(1, 256),
)
def test_single_outstanding_batch_degenerates_to_serial_drain_price(
        buckets_by_tier, queue_depth):
    rec = _record(buckets_by_tier)
    job = build_job(rec, DEVICES)

    # the reference price: TierStats.model_time over this one drain,
    # reconstructed through the public accounting API
    expect = 0.0
    for tier in sorted(rec.tiers):
        phase_ops, phase_bytes = rec.tiers[tier]
        ts = TierStats(name="t")
        for phase in sorted(phase_ops):
            ts.add_op(phase_bytes.get(phase, 0), phase)
            for _ in range(phase_ops[phase] - 1):
                ts.add_op(0, phase)
        expect += ts.model_time(DEVICES[tier], queue_depth)

    serial = job.serial_time(queue_depth)
    assert serial == pytest.approx(expect, rel=1e-12, abs=1e-15)

    loop = EventLoop(DEVICES, queue_depth)
    inter = loop.run([job], mode="interleaved")
    assert len(inter.completions) == 1
    assert inter.completions[0].done == pytest.approx(serial, rel=1e-12,
                                                      abs=1e-15)
    assert loop.run([job], mode="serial").completions[0].done == serial


@settings(max_examples=60, deadline=None)
@given(
    jobs_spec=st.lists(
        st.tuples(st.dictionaries(st.integers(0, 2),
                                  st.lists(_BUCKET, min_size=1, max_size=2),
                                  min_size=1, max_size=2),
                  st.floats(0.0, 0.01)),
        min_size=1, max_size=8),
    queue_depth=st.integers(1, 64),
)
def test_interleaving_never_worse_than_serial_and_conserves_jobs(
        jobs_spec, queue_depth):
    jobs = [build_job(_record(buckets), DEVICES, submit=at, seq=i)
            for i, (buckets, at) in enumerate(jobs_spec)]
    loop = EventLoop(DEVICES, queue_depth)
    inter = loop.run(jobs, mode="interleaved")
    serial = loop.run(jobs, mode="serial")
    assert len(inter.completions) == len(serial.completions) == len(jobs)
    assert inter.makespan <= serial.makespan * (1 + 1e-9)
    for c in inter.completions:
        assert c.done >= c.submit
        assert not math.isnan(c.latency)
