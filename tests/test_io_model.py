"""Device-model behaviour + the paper's S3-vs-NVMe observations."""

import numpy as np
import pytest

from repro.core import arrays as A, types as T
from repro.core.file import FileReader, WriteOptions, write_table
from repro.core.io_sim import HBM, NVME, S3, IOStats, model_time


def test_device_model_shapes():
    """Fig 1 qualitative shape: NVMe wins small random reads; S3 needs
    ~100 KiB reads to amortize; both converge at large sequential."""
    small = IOStats(n_iops=1000, bytes_read=1000 * 4096,
                    useful_bytes=1000 * 4096, max_phase=1)
    big = IOStats(n_iops=1000, bytes_read=1000 * (1 << 20),
                  useful_bytes=1000 * (1 << 20), max_phase=1)
    assert model_time(small, NVME) < model_time(small, S3) / 50
    # at 1 MiB reads both are bandwidth-bound and much closer
    ratio = model_time(big, S3) / model_time(big, NVME)
    assert ratio < 5


def test_phases_hurt_more_on_s3():
    """Paper §6.1.2: the dependent-phase effect 'is more significant in S3,
    where IOPS are far more expensive'.  Arrow's 3-phase List<String> take
    vs Lance full-zip's 2-phase take: the gap widens on S3."""
    vals = [["ab", None, "cd"], None, ["xyz"], []] * 100
    arr = A.from_pylist(vals, T.List(T.utf8()))
    rows = np.arange(0, 400, 13)

    def stats_for(opts):
        fr = FileReader(write_table({"c": arr}, opts))
        fr.reset_io()
        fr.take("c", rows)
        return fr.io_stats()

    st_arrow = stats_for(WriteOptions("arrow"))
    st_lance = stats_for(WriteOptions("lance-fullzip"))
    assert st_arrow.max_phase > st_lance.max_phase
    # the absolute penalty of the extra dependent phase is ~1000x larger on
    # S3 (30 ms round trips) than on NVMe (90 us)
    nvme_extra = model_time(st_arrow, NVME) - model_time(st_lance, NVME)
    s3_extra = model_time(st_arrow, S3) - model_time(st_lance, S3)
    assert s3_extra > 100 * max(nvme_extra, 1e-9)
    assert s3_extra > 0


def test_hbm_model_is_dma_shaped():
    """DESIGN.md §2.1: the TPU translation treats an IOP as a DMA; tiny
    reads cost a full min-granule."""
    tiny = IOStats(n_iops=10_000, bytes_read=10_000 * 8,
                   useful_bytes=10_000 * 8, max_phase=1)
    padded = IOStats(n_iops=10_000, bytes_read=10_000 * 512,
                     useful_bytes=10_000 * 512, max_phase=1)
    assert abs(model_time(tiny, HBM) - model_time(padded, HBM)) / \
        model_time(padded, HBM) < 0.01


def test_coalescing_counter():
    from repro.core.io_sim import Disk, IOTracker

    disk = Disk(np.zeros(10_000, np.uint8))
    tr = IOTracker(disk)
    tr.read(0, 100)
    tr.read(50, 100)   # overlaps -> coalesces
    tr.read(500, 100)  # far -> separate
    st = tr.stats()
    assert st.n_iops == 3
    assert st.n_coalesced == 2


def test_coalescing_is_per_phase():
    """Regression: adjacent reads in *different* dependency phases must not
    merge — a phase-1 read could only be issued after phase 0 returned, so a
    single combined request never existed."""
    from repro.core.io_sim import Disk, IOTracker

    disk = Disk(np.zeros(10_000, np.uint8))
    tr = IOTracker(disk)
    tr.read(0, 100, phase=0)
    tr.read(100, 100, phase=1)  # adjacent but causally later
    st = tr.stats()
    assert st.n_coalesced == 2
    assert st.max_phase == 2
    # within one phase the merge still happens
    tr.reset()
    tr.read(0, 100, phase=1)
    tr.read(100, 100, phase=1)
    assert tr.stats().n_coalesced == 1


def test_empty_trace_stats():
    """Regression: an empty trace has zero phases (not 1) and no coalesced
    ops."""
    from repro.core.io_sim import Disk, IOTracker

    tr = IOTracker(Disk(np.zeros(10, np.uint8)))
    st = tr.stats()
    assert st.n_iops == 0 and st.n_coalesced == 0 and st.max_phase == 0
    assert np.isnan(st.read_amplification)


def test_disk_read_bounds_and_copies(tmp_path):
    """Regression: out-of-range reads raise on both backing paths, and the
    returned arrays are writable copies that never alias the store."""
    from repro.core.io_sim import Disk

    payload = np.arange(64, dtype=np.uint8)
    fpath = tmp_path / "blob.bin"
    fpath.write_bytes(payload.tobytes())
    for disk in (Disk(payload.copy()), Disk(path=str(fpath))):
        with pytest.raises(ValueError):
            disk.read(60, 8)       # crosses the end
        with pytest.raises(ValueError):
            disk.read(64, 1)       # starts at the end
        with pytest.raises(ValueError):
            disk.read(-1, 4)       # negative offset
        with pytest.raises(ValueError):
            disk.read(0, -4)       # negative size
        got = disk.read(8, 8)
        np.testing.assert_array_equal(got, payload[8:16])
        got[:] = 0  # must not corrupt the backing store
        np.testing.assert_array_equal(disk.read(8, 8), payload[8:16])
        assert disk.read(64, 0).size == 0  # empty read at EOF is legal


def _grown_disk():
    """A disk grown past its first buffer: the backing array is larger than
    the logical size, so only ``_size`` bounds a read."""
    from repro.core.io_sim import Disk

    disk = Disk(np.arange(200, dtype=np.uint8))
    disk.grow(56)
    disk.write(200, np.arange(56, dtype=np.uint8) + 100)
    assert len(disk._mem) > len(disk) == 256
    return disk


def _payload_disk():
    from repro.core.io_sim import Disk

    return Disk(np.random.default_rng(7).integers(0, 256, 256, dtype=np.uint8))


# (disk, offsets, sizes, raises): offsets and sizes are within a 256-byte
# image, which the view cases place at a nonzero base of a larger disk
_GATHER_CASES = {
    "uniform": (_payload_disk, [0, 40, 96, 200], [16] * 4, False),
    "ragged": (_payload_disk, [3, 50, 120], [5, 17, 1], False),
    "single": (_payload_disk, [10], [33], False),
    "overlap-dup-uniform": (_payload_disk, [8, 12, 12, 8], [16] * 4, False),
    "overlap-dup-ragged": (_payload_disk, [8, 12, 12, 8], [16, 4, 30, 16],
                           False),
    "unsorted-uniform": (_payload_disk, [200, 5, 130, 64], [24] * 4, False),
    "unsorted-ragged": (_payload_disk, [200, 5, 130], [24, 3, 9], False),
    "last-byte-uniform": (_payload_disk, [240, 0], [16, 16], False),
    "last-byte-ragged": (_payload_disk, [250, 0], [6, 3], False),
    "grown": (_grown_disk, [190, 240, 100], [16] * 3, False),
    "grown-past-size-uniform": (_grown_disk, [0, 250], [8, 8], True),
    "grown-past-size-ragged": (_grown_disk, [0, 250], [4, 8], True),
    "zero-size-uniform": (_payload_disk, [0, 256, 17], [0, 0, 0], False),
    "zero-size-mixed": (_payload_disk, [0, 30, 256], [4, 0, 0], False),
    "negative-offset-uniform": (_payload_disk, [-1, 8], [4, 4], True),
    "empty": (_payload_disk, [], [], False),
}


@pytest.mark.parametrize("case", list(_GATHER_CASES))
def test_read_gather_matches_per_span_reads(case):
    """``read_gather`` on a disk and on a view returns the concatenation of
    per-span ``Disk.read`` results with their offsets, whichever copy serves
    it (row window for spans of one size, byte index otherwise), raises the
    same bounds error, and hands back a writable copy that never aliases
    the image."""
    from repro.core.io_sim import Disk, DiskView

    make, offsets, sizes, raises = _GATHER_CASES[case]
    disk = make()
    image = disk.read(0, len(disk))
    base = 13
    outer = Disk(np.concatenate([np.full(base, 0xEE, np.uint8), image,
                                 np.full(9, 0xDD, np.uint8)]))
    for store, kind in ((disk, "disk"), (DiskView(outer, base, len(disk)),
                                         "view")):
        if raises:
            with pytest.raises(ValueError,
                               match=f"gather read out of bounds for "
                                     f"{len(disk)}-byte {kind}"):
                store.read_gather(offsets, sizes)
            continue
        want = [disk.read(o, s) for o, s in zip(offsets, sizes)]
        data, out_offs = store.read_gather(offsets, sizes)
        assert data.dtype == np.uint8 and data.ndim == 1
        np.testing.assert_array_equal(
            data, np.concatenate(want) if want else np.zeros(0, np.uint8))
        np.testing.assert_array_equal(
            out_offs, np.concatenate([[0], np.cumsum(sizes, dtype=np.int64)]))
        assert data.flags.writeable
        backing = store.disk if kind == "view" else store
        assert not np.shares_memory(data, backing._mem)
        data[:] = 0xAB  # must not corrupt the backing store
        np.testing.assert_array_equal(store.read(0, len(disk)), image)
