"""Tiered storage subsystem: block cache, batched scheduler, readahead, the
end-to-end tiered read path through FileReader, and the write path (dirty
blocks, flush policies, durability accounting)."""

import numpy as np
import pytest

from repro.core import arrays as A, types as T
from repro.core.file import FileReader, WriteOptions, write_table
from repro.core.io_sim import NVME, S3, Disk, IOTracker
from repro.store import (
    BlockCache,
    FlushPolicy,
    IOScheduler,
    SequentialReadahead,
    SimulatedCrash,
    TieredStore,
    WorkloadStats,
    make_store,
)


# ---------------------------------------------------------------------------
# BlockCache
# ---------------------------------------------------------------------------


def test_cache_lru_hit_miss_evict():
    c = BlockCache(3 * 4096, policy="lru")
    for b in (0, 1, 2):
        assert not c.lookup(b)
        c.admit(b)
    assert c.lookup(0) and c.lookup(1) and c.lookup(2)
    assert (c.hits, c.misses, c.evictions) == (3, 3, 0)
    c.lookup(0)  # 0 is now MRU; 1 is LRU
    assert not c.lookup(3)
    c.admit(3)   # evicts 1
    assert c.evictions == 1
    assert 1 not in c and 0 in c and 2 in c and 3 in c
    assert c.resident_bytes == 3 * 4096


def test_cache_clock_second_chance():
    c = BlockCache(2 * 4096, policy="clock")
    c.admit(0)
    c.admit(1)
    c.lookup(0)          # ref bit set on 0
    c.admit(2)           # clock must spare 0 (referenced) and evict 1
    assert 0 in c and 2 in c and 1 not in c
    assert c.evictions == 1
    assert len(c) == 2


def test_cache_second_touch_admission():
    c = BlockCache(4 * 4096, admission="second_touch")
    c.lookup(7)
    assert not c.admit(7)   # first touch: ghost only
    assert 7 not in c
    c.lookup(7)
    assert c.admit(7)       # second touch: admitted
    assert 7 in c


def test_second_touch_holds_through_dispatch():
    """Regression: the demand dispatch path must consult the admission
    policy exactly once per miss — a double admit() turned second_touch
    into always-admit (first call ghosts the id, second 'second-touches'
    it)."""
    disk = Disk(np.zeros(64 * 4096, np.uint8))
    store = TieredStore.cached(disk, cache_bytes=16 * 4096,
                               admission="second_touch")
    store.dispatch_extent(0, 4096, phase=0)
    assert len(store.levels[0].cache) == 0   # first touch: ghost only
    store.dispatch_extent(0, 4096, phase=0)
    assert len(store.levels[0].cache) == 1   # second touch: resident
    assert store.backing_stats.n_iops == 2   # both misses paid the backing
    store.dispatch_extent(0, 4096, phase=0)
    assert store.levels[0].cache.hits == 1   # third read is a cache hit
    # prefetch fills that the policy rejects are not billed to the backing
    store.dispatch_extent(8 * 4096, 9 * 4096, phase=0, prefetch=True)
    assert store.backing_stats.prefetch_iops == 0
    assert len(store.levels[0].cache) == 1


def test_cache_rejects_bad_config():
    with pytest.raises(ValueError):
        BlockCache(100, block_bytes=4096)
    with pytest.raises(ValueError):
        BlockCache(1 << 20, policy="marvellous")
    with pytest.raises(ValueError):
        BlockCache(1 << 20, admission="never")


# ---------------------------------------------------------------------------
# workload-driven admission ("auto")
# ---------------------------------------------------------------------------


def test_workload_stats_mix_and_preference():
    ws = WorkloadStats()
    assert ws.preferred_admission() == "always"  # cold-start default
    ws.note_batch("scan:c", prefetch=True, n_ops=4, nbytes=1 << 20)
    assert ws.preferred_admission() == "second_touch"
    assert ws.n_scan_batches == 1 and ws.scan_bytes == 1 << 20
    for _ in range(3):
        ws.note_batch("take:c", prefetch=False, n_ops=100, nbytes=1 << 19)
    assert ws.take_bytes > ws.scan_bytes
    assert ws.preferred_admission() == "always"
    assert 0.0 < ws.scan_fraction < 0.5
    ws.reset()
    assert ws.n_scan_batches == ws.n_take_batches == 0


def test_admission_auto_flips_with_trace():
    """admission="auto" must follow the observed mix: a scan-heavy trace
    flips the active policy to second_touch, a take-heavy one back."""
    disk = Disk(np.zeros(1 << 22, np.uint8))
    store = TieredStore.cached(disk, admission="auto")
    cache = store.levels[0].cache
    sched = IOScheduler(store)
    assert cache.admission == "auto" and cache.active_admission == "always"

    # scan-heavy: one big streaming batch dominates the byte mix
    with sched.batch("scan:c", prefetch=True) as io:
        io.read(0, 1 << 20)
    assert cache.active_admission == "second_touch"
    assert cache.admission_flips == 1
    # ...and the flip applied to that very batch: first-touch blocks were
    # only ghosted, so the single-pass scan did not flood the cache
    assert len(cache) == 0

    # take-heavy: many small random batches overtake the scan bytes
    for i in range(0, 3 << 20, 4096):
        with sched.batch("take:c") as io:
            io.read(i % (1 << 20), 4096)
    assert cache.active_admission == "always"
    assert cache.admission_flips == 2


def test_admission_pinned_policies_do_not_flip():
    c = BlockCache(1 << 20, admission="second_touch")
    c.set_active_admission("always")
    assert c.active_admission == "second_touch"  # pinned by construction
    with pytest.raises(ValueError):
        c.set_active_admission("auto")


def test_make_store_tiered_auto_spec():
    disk = Disk(np.zeros(1 << 16, np.uint8))
    store = make_store("tiered-auto", disk)
    assert store.levels[0].cache.admission == "auto"
    assert store.levels[0].cache.active_admission == "always"


def test_tiered_store_accepts_shared_cache():
    """Satellite: several stores over one address space can share one
    BlockCache instance (one NVMe budget, no re-plumbing)."""
    disk = Disk(np.zeros(64 * 4096, np.uint8))
    cache = BlockCache(16 * 4096)
    s1 = TieredStore.cached(disk, cache=cache)
    s2 = TieredStore.cached(disk, cache=cache)
    assert s1.levels[0].cache is s2.levels[0].cache
    s1.dispatch_extent(0, 4096, phase=0)       # s1 warms block 0
    s2.dispatch_extent(0, 4096, phase=0)       # s2 hits it
    assert cache.hits == 1 and cache.misses == 1
    assert s2.backing_stats.n_iops == 0        # no second backing read
    with pytest.raises(ValueError):            # sector mismatch is rejected
        TieredStore.cached(disk, sector=8192, cache=cache)


# ---------------------------------------------------------------------------
# scheduler vs. legacy accounting
# ---------------------------------------------------------------------------


def _strings(n):
    return A.from_pylist([f"value-{i:06d}" * 3 for i in range(n)], T.Utf8(False))


@pytest.mark.parametrize("enc", ["lance-miniblock", "lance-fullzip", "parquet",
                                 "arrow"])
def test_scheduler_trace_matches_legacy_tracker(enc):
    """The scheduler's logical stats must be bit-identical to replaying the
    same trace through the legacy IOTracker (no accounting regression)."""
    arr = _strings(2000)
    fb = write_table({"c": arr}, WriteOptions(enc))
    fr = FileReader(fb)  # flat single-tier store
    fr.take("c", np.arange(0, 2000, 37))
    fr.scan("c")
    tr = IOTracker(fr.disk)
    for o, sz, p in fr.scheduler.ops:
        tr.read(o, sz, p)
    for gap in (0, 64, 4096):
        a, b = fr.io_stats(gap), tr.stats(gap)
        assert (a.n_iops, a.bytes_read, a.max_phase, a.n_coalesced) == \
               (b.n_iops, b.bytes_read, b.max_phase, b.n_coalesced)


def test_flat_dispatch_count_equals_coalesced():
    """On a single-tier store each per-phase coalesced extent becomes exactly
    one dispatched device op (fixed-width take: no zero-length requests)."""
    arr = A.PrimitiveArray.build(np.arange(4000, dtype=np.int64), nullable=False)
    fr = FileReader(write_table({"c": arr}, WriteOptions("lance-fullzip")))
    fr.take("c", np.random.default_rng(0).choice(4000, 128, replace=False))
    st = fr.io_stats()
    backing = fr.tier_stats()[-1]
    assert backing.n_iops == st.n_coalesced
    # dispatched bytes are sector-aligned, so never less than logical bytes
    assert backing.bytes_read >= st.bytes_read
    assert backing.max_phase == st.max_phase


# ---------------------------------------------------------------------------
# tiered end-to-end
# ---------------------------------------------------------------------------


def test_tiered_take_cold_then_warm():
    arr = _strings(3000)
    fb = write_table({"c": arr}, WriteOptions("lance"))
    rows = np.random.default_rng(1).choice(3000, 200, replace=False)
    want = [A.to_pylist(arr)[i] for i in rows]

    cold = FileReader(fb, store="flat-s3")
    cold.take("c", rows)
    t_cold = cold.modelled_time()

    fr = FileReader(fb, store="tiered")
    assert A.to_pylist(fr.take("c", rows)) == want  # data plane is unchanged
    nvme, s3 = fr.tier_stats()
    assert s3.n_iops > 0 and nvme.misses > 0  # cold pass fills from S3

    fr.reset_io()
    assert A.to_pylist(fr.take("c", rows)) == want
    t_warm = fr.modelled_time()
    nvme, s3 = fr.tier_stats()
    assert s3.n_iops == 0 and nvme.hit_rate == 1.0  # fully warm
    assert t_warm < t_cold  # the acceptance headline

    fr.drop_caches()
    fr.reset_io()
    fr.take("c", rows)
    assert fr.tier_stats()[1].n_iops > 0  # cold again after dropping


def test_tiered_eviction_under_pressure():
    arr = A.PrimitiveArray.build(np.arange(200_000, dtype=np.int64),
                                 nullable=False)
    fb = write_table({"c": arr}, WriteOptions("lance-fullzip"))
    tiny = lambda d: TieredStore.cached(d, cache_bytes=8 * 4096)
    fr = FileReader(fb, store=tiny)
    fr.take("c", np.arange(0, 200_000, 997))  # way beyond 8 blocks
    nvme = fr.tier_stats()[0]
    assert nvme.evictions > 0
    assert len(fr.store.levels[0].cache) <= 8


def test_hot_store_promotes_through_levels():
    arr = _strings(1000)
    fr = FileReader(write_table({"c": arr}, WriteOptions("lance")), store="hot")
    rows = np.arange(0, 1000, 13)
    fr.take("c", rows)
    fr.reset_io()
    fr.take("c", rows)
    ram, nvme, s3 = fr.tier_stats()
    assert s3.n_iops == 0        # warm: nothing reaches S3
    assert ram.hits > 0          # served from the RAM-hot tier
    assert fr.modelled_time() < 1e-3


def test_prefetch_on_full_scan():
    arr = _strings(20_000)
    fb = write_table({"c": arr}, WriteOptions("lance-miniblock"))
    fr = FileReader(fb, store="tiered")
    # small demand chunks so readahead has a stream to get ahead of
    got = fr.scan("c", io_chunk=16 * 1024)
    assert A.to_pylist(got) == A.to_pylist(arr)
    nvme, s3 = fr.tier_stats()
    assert s3.prefetch_iops > 0 and s3.prefetch_bytes > 0
    assert nvme.hits > 0  # demand reads landed on prefetched blocks
    # prefetch fills holes, it never re-reads: total backing bytes stay
    # within one readahead window of the demand footprint
    no_ra = FileReader(fb, store="tiered", readahead=None)
    no_ra.scan("c", io_chunk=16 * 1024)
    s3_no_ra = no_ra.tier_stats()[1]
    assert s3_no_ra.prefetch_iops == 0 and s3_no_ra.hits == 0
    assert s3.bytes_read <= s3_no_ra.bytes_read + (1 << 20)


def test_readahead_policy_unit():
    ra = SequentialReadahead(window_bytes=1 << 16, min_run=2)
    assert ra.observe(0, 4096) is None          # first read: no pattern yet
    pf = ra.observe(4096, 8192)                 # sequential: prefetch ahead
    assert pf == (8192, 8192 + (1 << 16))
    # next sequential read slides the window: only the uncovered tail is new
    assert ra.observe(8192, 12_288) == (8192 + (1 << 16), 12_288 + (1 << 16))
    ra.reset()
    assert ra.observe(0, 4096) is None
    assert ra.observe(1 << 30, (1 << 30) + 4096) is None  # random jump


def test_sequential_batches_each_pay_round_trips():
    """Regression: two sequential takes are two queue drains — the modelled
    latency term must double, not collapse into one phase bucket."""
    arr = A.PrimitiveArray.build(np.arange(4000, dtype=np.int64), nullable=False)
    fb = write_table({"c": arr}, WriteOptions("lance-fullzip"))
    fr = FileReader(fb, store="flat-s3")
    rows = np.arange(0, 4000, 31)
    fr.take("c", rows)
    t1 = fr.modelled_time()
    fr.take("c", rows)  # no reset: same counters, second round trip
    t2 = fr.modelled_time()
    assert t2 > 1.8 * t1  # S3 latency dominates; each take pays its own


def test_tier_stats_snapshots_survive_reset():
    """Regression: tier_stats() must return detached copies, not the live
    counters that reset_io() zeroes in place."""
    arr = _strings(500)
    fr = FileReader(write_table({"c": arr}, WriteOptions("lance")), store="tiered")
    fr.take("c", np.arange(0, 500, 7))
    before = fr.tier_stats()
    assert before[-1].n_iops > 0
    saved = before[-1].n_iops
    fr.reset_io()
    assert before[-1].n_iops == saved  # snapshot unaffected by the reset
    assert fr.tier_stats()[-1].n_iops == 0


def test_make_store_specs():
    disk = Disk(np.zeros(1 << 16, np.uint8))
    assert make_store(None, disk).backing is NVME
    assert make_store("flat-s3", disk).backing is S3
    assert len(make_store("tiered", disk).levels) == 1
    assert len(make_store("hot", disk).levels) == 2
    with pytest.raises(ValueError):
        make_store("warmish", disk)
    with pytest.raises(ValueError):
        make_store(TieredStore.flat(Disk(np.zeros(8, np.uint8))), disk)


def test_batch_rejects_use_after_close():
    disk = Disk(np.zeros(1 << 16, np.uint8))
    sched = IOScheduler(TieredStore.flat(disk))
    with sched.batch("t") as io:
        io.read(0, 16)
    with pytest.raises(RuntimeError):
        io.read(0, 16)
    assert sched.stats().n_iops == 1


@pytest.mark.parametrize("spans", ["uniform", "ragged"])
def test_read_many_accounting_matches_per_span_reads(spans):
    """``read_many`` records the same logical ops, drains and prices as the
    same spans issued one ``read`` at a time, however ``read_gather``
    copies the bytes; ``store.read_window_spans`` counts exactly the spans
    of calls whose spans share one size."""
    from repro.obs import Tracer

    rng = np.random.default_rng(3)
    image = rng.integers(0, 256, 1 << 18, dtype=np.uint8)
    n = 300
    sizes = (np.full(n, 516) if spans == "uniform"
             else rng.integers(0, 900, n))
    offsets = rng.integers(0, len(image) - 900, n)  # unsorted, with repeats

    def run(batched):
        tracer = Tracer()
        store = TieredStore.cached(Disk(image.copy()), cache_bytes=8 * 4096)
        sched = IOScheduler(store, tracer=tracer)
        ops, got = [], []
        for phase in (0, 1):  # the second batch meets a warm cache
            with sched.batch("t") as io:
                if batched:
                    data, offs = io.read_many(offsets, sizes, phase=phase)
                    got += [data[offs[k]:offs[k + 1]] for k in range(n)]
                else:
                    got += [io.read(o, s, phase=phase)
                            for o, s in zip(offsets, sizes)]
            ops.append(list(io.ops))
        return (ops, got, store.drain_log, sched.tier_stats(),
                sched.model_time(), tracer.metrics.counter_values())

    ops, got, drains, tiers, t, counts = run(batched=True)
    ops1, got1, drains1, tiers1, t1, counts1 = run(batched=False)
    assert ops == ops1
    assert all(np.array_equal(a, b) for a, b in zip(got, got1))
    assert drains == drains1 and tiers == tiers1 and t == t1
    assert counts["store.read_spans"] == counts1["store.read_spans"] == 2 * n
    assert counts["store.read_bytes"] == counts1["store.read_bytes"]
    window = 2 * n if spans == "uniform" else 0
    assert counts.get("store.read_window_spans", 0) == window
    assert "store.read_window_spans" not in counts1


# ---------------------------------------------------------------------------
# write path: dirty blocks, flush policies, durability accounting
# ---------------------------------------------------------------------------


def _wb_store(disk, mode="write-back", cache_blocks=16, **kw):
    store = TieredStore.cached(disk, cache_bytes=cache_blocks * 4096)
    store.set_flush_policy(FlushPolicy(mode, **kw))
    return store


def test_cache_dirty_state_and_force_insert():
    c = BlockCache(4 * 4096, admission="second_touch")
    c.mark_dirty(7)          # bypasses the admission filter
    assert 7 in c and c.is_dirty(7)
    assert c.dirty_bytes == 4096 and c.dirty_blocks == [7]
    c.clean(7)
    assert not c.is_dirty(7) and 7 in c  # residency survives the flush
    assert c.dirty_bytes == 0


def test_cache_invalidate_reuses_slot_without_eviction():
    c = BlockCache(2 * 4096, policy="clock")
    c.admit(0)
    c.admit(1)
    assert c.invalidate(0) and 0 not in c and len(c) == 1
    assert not c.invalidate(0)  # already gone
    c.admit(2)                  # must reuse the tombstoned slot
    assert len(c) == 2 and c.evictions == 0
    lru = BlockCache(2 * 4096, policy="lru")
    lru.admit(5)
    assert lru.invalidate(5) and 5 not in lru and lru.evictions == 0


def test_write_back_dirty_accounting_invariants():
    """The core dirty-byte invariants: absorbed bytes become dirty on the
    cache tier (no backing traffic), flushing moves exactly those bytes to
    the backing tier as flush writes, and dirty_bytes returns to zero."""
    disk = Disk(np.zeros(64 * 4096, np.uint8))
    store = _wb_store(disk)
    sched = IOScheduler(store)
    with sched.write_batch("append:0") as wb:
        wb.write(0, b"x" * 10_000)          # 3 sectors
    nvme, s3 = store.tier_stats()
    assert nvme.write_iops == 1 and nvme.bytes_written == 3 * 4096
    assert nvme.dirty_bytes == 3 * 4096
    assert s3.write_iops == 0               # nothing durable yet
    assert store.dirty_extents() == [(0, 3 * 4096)]
    flushed = store.flush_all()
    assert flushed == 3
    nvme, s3 = store.tier_stats()
    assert nvme.dirty_bytes == 0
    assert s3.write_iops == 1 and s3.flush_iops == 1   # one contiguous run
    assert s3.bytes_written == s3.flush_bytes == 3 * 4096
    assert sched.write_stats().n_iops == 1
    assert sched.write_stats().bytes_read == 10_000    # logical write trace


def test_write_through_is_immediately_durable():
    disk = Disk(np.zeros(64 * 4096, np.uint8))
    store = _wb_store(disk, mode="write-through")
    sched = IOScheduler(store)
    with sched.write_batch() as wb:
        wb.write(4096, b"y" * 4096)
    nvme, s3 = store.tier_stats()
    assert s3.write_iops == 1 and s3.flush_iops == 0
    assert nvme.dirty_bytes == 0 and store.dirty_extents() == []
    # the written block was admitted clean: the next read is NVMe-warm
    with sched.batch("take:c") as io:
        io.read(4096, 100)
    assert store.levels[0].cache.hits == 1
    assert store.tier_stats()[1].n_iops == 0  # reads: no S3 traffic


def test_write_through_fill_bypasses_admission_filter():
    """Regression: the write-through fill must force-insert — under
    second_touch (or auto flipped to it) a plain admit() only ghosts the
    block and the writer's own fresh bytes would cold-miss to S3."""
    disk = Disk(np.zeros(64 * 4096, np.uint8))
    store = TieredStore.cached(disk, admission="second_touch")
    store.set_flush_policy(FlushPolicy("write-through"))
    sched = IOScheduler(store)
    with sched.write_batch() as wb:
        wb.write(0, b"w" * 4096)
    assert 0 in store.levels[0].cache     # resident despite second_touch
    with sched.batch("take:c") as io:
        io.read(0, 100)
    assert store.levels[0].cache.hits == 1
    assert store.tier_stats()[1].n_iops == 0  # no S3 read for fresh bytes


def test_unattached_store_defaults_to_write_through():
    disk = Disk(np.zeros(16 * 4096, np.uint8))
    store = TieredStore.cached(disk)  # no flush policy attached
    sched = IOScheduler(store)
    with sched.write_batch() as wb:
        wb.write(0, b"z" * 4096)
    assert store.tier_stats()[1].write_iops == 1
    assert store.dirty_extents() == []


def test_flush_on_evict_writes_back_dirty_victim():
    disk = Disk(np.zeros(64 * 4096, np.uint8))
    store = _wb_store(disk, mode="flush-on-evict", cache_blocks=2)
    sched = IOScheduler(store)
    with sched.write_batch() as wb:
        wb.write(0, b"a" * (3 * 4096))  # 3 dirty blocks into a 2-block cache
    nvme, s3 = store.tier_stats()
    assert s3.flush_iops == 1           # the evicted victim was written back
    assert nvme.dirty_bytes == 2 * 4096
    assert nvme.evictions == 1


def test_write_back_high_watermark_flushes_down():
    disk = Disk(np.zeros(256 * 4096, np.uint8))
    store = _wb_store(disk, cache_blocks=16, high_watermark=0.5,
                      low_watermark=0.25, deadline_batches=1000)
    sched = IOScheduler(store)
    with sched.write_batch() as wb:     # 10 of 16 blocks dirty: > 0.5
        wb.write(0, b"b" * (10 * 4096))
    cache = store.levels[0].cache
    assert cache.dirty_bytes <= int(0.25 * 16 * 4096) + 4096
    assert store.tier_stats()[1].flush_iops >= 1
    assert store.flush_policy.n_flush_events >= 1


def test_write_back_deadline_flushes_aged_blocks():
    disk = Disk(np.zeros(64 * 4096, np.uint8))
    store = _wb_store(disk, deadline_batches=2)
    sched = IOScheduler(store)
    with sched.write_batch() as wb:
        wb.write(0, b"c" * 4096)
    assert store.levels[0].cache.dirty_bytes == 4096
    with sched.batch("take:c") as io:   # read batches tick the deadline too
        io.read(8 * 4096, 64)
    assert store.tier_stats()[1].flush_iops == 1
    assert store.levels[0].cache.dirty_bytes == 0


def test_discard_dirty_counts_lost_bytes():
    disk = Disk(np.zeros(64 * 4096, np.uint8))
    store = _wb_store(disk)
    sched = IOScheduler(store)
    with sched.write_batch() as wb:
        wb.write(0, b"d" * (2 * 4096))
        wb.write(8 * 4096, b"d" * 4096)
    lost = store.discard_dirty()
    assert lost == [(0, 2 * 4096), (8 * 4096, 9 * 4096)]
    nvme, s3 = store.tier_stats()
    assert nvme.lost_bytes == 3 * 4096
    assert nvme.dirty_bytes == 0 and s3.write_iops == 0
    # the discarded blocks are gone from the cache, not 'warm garbage'
    assert len(store.levels[0].cache) == 0


def test_flush_fault_injection_is_a_clean_prefix():
    """An interrupted flush must be a prefix: extents dispatched before the
    crash are durable (clean), the rest stay dirty — never half-flushed
    accounting."""
    disk = Disk(np.zeros(64 * 4096, np.uint8))
    store = _wb_store(disk)
    sched = IOScheduler(store)
    with sched.write_batch() as wb:
        wb.write(0, b"e" * 4096)            # run 1
        wb.write(8 * 4096, b"e" * 4096)     # run 2 (disjoint)
    store.flush_policy.fail_after = 1
    with pytest.raises(SimulatedCrash):
        store.flush_all()
    store.flush_policy.fail_after = None
    cache = store.levels[0].cache
    assert not cache.is_dirty(0)            # first extent made it
    assert cache.is_dirty(8)                # second did not
    assert store.tier_stats()[1].flush_iops == 1


def test_model_time_prices_writes():
    """Write traffic must show up in the modelled wall time (the queue-depth
    drain term prices the flush round trip on the backing device)."""
    disk = Disk(np.zeros(64 * 4096, np.uint8))
    store = _wb_store(disk)
    sched = IOScheduler(store)
    t0 = sched.model_time()
    with sched.write_batch() as wb:
        wb.write(0, b"f" * (4 * 4096))
    t_dirty = sched.model_time()
    assert t_dirty > t0                     # NVMe absorption is priced
    store.flush_all()
    assert sched.model_time() > t_dirty + 0.9 * S3.latency  # S3 drain priced


def test_write_batch_rejects_use_after_close():
    disk = Disk(np.zeros(16 * 4096, np.uint8))
    sched = IOScheduler(TieredStore.flat(disk))
    with sched.write_batch() as wb:
        wb.write(0, b"g" * 16)
    with pytest.raises(RuntimeError):
        wb.write(0, b"g")
    assert sched.n_write_batches == 1
    # reads and writes are separate logical traces
    assert sched.stats().n_iops == 0
    assert sched.write_stats().n_iops == 1


def test_flush_policy_validation():
    with pytest.raises(ValueError):
        FlushPolicy("write-sideways")
    with pytest.raises(ValueError):
        FlushPolicy(high_watermark=0.0)
    with pytest.raises(ValueError):
        FlushPolicy(low_watermark=0.9, high_watermark=0.5)
    with pytest.raises(ValueError):
        FlushPolicy(deadline_batches=0)


def test_disk_write_grow_zero():
    disk = Disk(np.zeros(8, np.uint8))
    disk.write(2, b"\x05\x06")
    assert disk.read(0, 5).tolist() == [0, 0, 5, 6, 0]
    assert disk.grow(8) == 16
    assert disk.read(2, 2).tolist() == [5, 6]  # old bytes survive the grow
    disk.zero(2, 4)
    assert disk.read(2, 2).tolist() == [0, 0]
    with pytest.raises(ValueError):
        disk.write(15, b"ab")
    with pytest.raises(ValueError):
        disk.grow(-1)


def test_retriever_tiered():
    from repro.data import synth
    from repro.serve.engine import Retriever

    emb = synth.scenario("embeddings", 1500)
    fb = write_table({"embedding": emb}, WriteOptions("lance"))
    r = Retriever(fb, "embedding", store="tiered")
    ids = np.array([5, 900, 1400])
    r.fetch(ids)
    cold = r.modelled_time()
    _, st = r.fetch(ids)
    assert st.n_iops == len(ids)  # full-zip fixed width: 1 IOP/row
    assert r.modelled_time() < cold
    assert r.tier_stats()[1].n_iops == 0  # warm: no S3 traffic


# ---------------------------------------------------------------------------
# Admission hysteresis: the auto policy must not thrash on the boundary
# ---------------------------------------------------------------------------


def test_admission_hysteresis_no_thrash_on_alternating_mix():
    """An alternating scan/take workload whose byte mix oscillates around
    the boundary must not flip the preference batch to batch: inside the
    +-10% band the previous decision sticks (each flip resets second-touch
    ghost state, so thrashing is not free)."""
    ws = WorkloadStats()
    # establish a clear scan majority -> one flip to second_touch
    ws.note_batch("scan:c", prefetch=True, n_ops=4, nbytes=150_000)
    assert ws.preferred_admission() == "second_touch"
    # pull the mix back to parity: inside the band the flip does NOT revert
    ws.note_batch("take:c", prefetch=False, n_ops=40, nbytes=145_000)
    assert ws.preferred_admission() == "second_touch"
    # alternate batches that rock the byte majority back and forth across
    # parity while staying inside the +-10% band: a memoryless majority
    # test would flip on every sign change, the hysteresis never does
    prefs = []
    sign_changes = 0
    for i in range(20):
        if i % 2:
            ws.note_batch("scan:c", prefetch=True, n_ops=1, nbytes=10_000)
        else:
            ws.note_batch("take:c", prefetch=False, n_ops=10, nbytes=10_000)
        prefs.append(ws.preferred_admission())
        ratio = ws.scan_bytes / ws.take_bytes
        assert 1.0 / 1.1 <= ratio <= 1.1      # genuinely inside the band
        if (ws.scan_bytes > ws.take_bytes) != (i % 2 == 0):
            sign_changes += 1
    assert sign_changes >= 8                  # the majority really oscillated
    assert set(prefs) == {"second_touch"}     # sticky: zero flips in the band
    # a decisive take majority still flips (hysteresis delays, not disables)
    ws.note_batch("take:c", prefetch=False, n_ops=100, nbytes=300_000)
    assert ws.preferred_admission() == "always"


def test_admission_hysteresis_zero_restores_majority_test():
    ws = WorkloadStats(hysteresis=0.0)
    ws.note_batch("scan:c", prefetch=True, n_ops=1, nbytes=1001)
    ws.note_batch("take:c", prefetch=False, n_ops=1, nbytes=1000)
    assert ws.preferred_admission() == "second_touch"
    ws.note_batch("take:c", prefetch=False, n_ops=1, nbytes=2)
    assert ws.preferred_admission() == "always"
    with pytest.raises(ValueError):
        WorkloadStats(hysteresis=-0.1)


# ---------------------------------------------------------------------------
# Partial-block RMW accounting for sub-sector appends
# ---------------------------------------------------------------------------


def test_rmw_sub_sector_write_charges_backing_read():
    """A write-through append landing mid-sector pays one read-modify-write
    sector read on the backing tier; a second write to the now-resident
    sector is free (the write-through fill made the edge mergeable in
    cache)."""
    disk = Disk(np.zeros(64 * 4096, np.uint8))
    store = _wb_store(disk, mode="write-through")
    sched = IOScheduler(store)
    with sched.write_batch("append:0") as wb:
        wb.write(100, b"z" * 1000)          # head+tail edges in one sector
    nvme, s3 = store.tier_stats()
    assert s3.rmw_iops == 1 and s3.rmw_bytes == 4096
    assert s3.n_iops == 1                   # the RMW read is real read IO
    assert s3.write_iops == 1
    # the logical write trace records the append, not the device artifact
    assert sched.write_stats().n_iops == 1
    assert sched.write_stats().bytes_read == 1000
    assert sched.stats().n_iops == 0        # logical *read* trace untouched
    with sched.write_batch("append:1") as wb:
        wb.write(1100, b"z" * 500)          # same sector, now resident
    assert store.tier_stats()[1].rmw_iops == 1  # no new RMW


def test_rmw_aligned_and_eof_writes_are_free():
    disk = Disk(np.zeros(64 * 4096, np.uint8))
    store = _wb_store(disk, mode="write-through")
    sched = IOScheduler(store)
    with sched.write_batch() as wb:
        wb.write(4096, b"a" * 8192)         # sector-aligned both ends
    assert store.tier_stats()[1].rmw_iops == 0
    with sched.write_batch() as wb:
        wb.write(64 * 4096 - 1000, b"b" * 1000)  # unaligned head, ends at EOF
    s3 = store.tier_stats()[1]
    assert s3.rmw_iops == 1                 # head edge only: no bytes beyond
    assert s3.rmw_bytes == 4096


def test_rmw_write_back_flush_path():
    """Write-back: the RMW charge lands when the flush writes the dirty run
    down, and dirty residency of the edge sector suppresses it."""
    disk = Disk(np.zeros(64 * 4096, np.uint8))
    store = _wb_store(disk)
    sched = IOScheduler(store)
    with sched.write_batch("append:0") as wb:
        wb.write(100, b"z" * 1000)
    nvme, s3 = store.tier_stats()
    # absorb: the edge sector is not resident anywhere yet -> RMW at absorb
    assert s3.rmw_iops == 1 and nvme.dirty_bytes == 4096
    with sched.write_batch("append:1") as wb:
        wb.write(1100, b"z" * 500)          # edge sector resident dirty
    assert store.tier_stats()[1].rmw_iops == 1   # suppressed
    store.flush_all()
    s3 = store.tier_stats()[1]
    assert s3.rmw_iops == 1                 # flush itself never re-charges
    assert s3.flush_iops == 1


def test_rmw_counters_survive_snapshot_and_reset():
    disk = Disk(np.zeros(16 * 4096, np.uint8))
    store = _wb_store(disk, mode="write-through")
    sched = IOScheduler(store)
    with sched.write_batch() as wb:
        wb.write(50, b"q" * 100)
    snap = store.tier_stats()[1]
    assert snap.rmw_iops == 1 and snap.rmw_bytes == 4096
    store.reset_stats()
    live = store.backing_stats
    assert live.rmw_iops == 0 and live.rmw_bytes == 0
    assert snap.rmw_iops == 1               # snapshot is decoupled
