"""Row takes across columns: ``DatasetReader.take_columns``.

Whole rows of a fragmented table, read in one scheduler batch with one
grouped mini-block decode, agree with a plain dict-of-rows reference on both
decode routes (the Pallas kernel in interpret mode); ``take`` of one column
is ``take_columns`` of that column; and a 40-column request of Criteo-shaped
rows makes one store drain and one kernel launch per level class.
"""

import numpy as np
import pytest

from repro.core import arrays as A, types as T
from repro.core.file import WriteOptions
from repro.core.miniblock import MiniBlockReader
from repro.dataset import DatasetReader, write_fragments
from repro.obs import Tracer

N_ROWS, N_FRAGMENTS = 2000, 8
MULTI_HOT = {"C1": (3, 40_000_000, 0.0), "C3": (1, 17_295, 0.03),
             "C21": (100, 40_000_000, 0.03), "C22": (27, 40_000_000, 0.76)}


def _ints(gen, n, null_share):
    return A.PrimitiveArray.build(
        np.floor(gen.lognormal(1.0, 2.0, n)).clip(0, 2**24 - 1)
        .astype(np.int64), validity=gen.random(n) >= null_share)


def _multi_hot(gen, n, size, high, null_share):
    """A nullable ``List<int32>``: a valid list holds ``size`` ids, a null
    list none."""
    valid = gen.random(n) >= null_share
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(np.where(valid, size, 0), out=offsets[1:])
    items = A.PrimitiveArray.build(
        gen.integers(0, high, int(offsets[-1]), dtype=np.int32),
        nullable=False)
    return A.ListArray.build(items, offsets, validity=valid)


def _criteo(n, seed=0, n_ints=13, n_lists=26):
    """Criteo-shaped rows: a not-null 0/1 label, nullable int64 counts,
    nullable multi-hot lists (the DLRM-DCNv2 reference's first sizes)."""
    gen = np.random.default_rng(seed)
    sizes = [3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12,
             100, 27, 10, 3, 1, 1]
    table = {"label": A.PrimitiveArray.build(
        (gen.random(n) < 0.034).astype(np.int64), nullable=False)}
    for i in range(n_ints):
        table[f"I{i + 1}"] = _ints(gen, n, 0.45 if i % 2 else 0.0)
    for i in range(n_lists):
        table[f"C{i + 1}"] = _multi_hot(gen, n, sizes[i], 2**(i % 26 + 1),
                                        0.44 if i % 3 == 0 else 0.0)
    return table


def _table(n=N_ROWS, seed=1):
    """Null ints, null lists of length 0, lists of lengths 1 to 100, and a
    vector column wide enough for full-zip."""
    gen = np.random.default_rng(seed)
    table = {"label": A.PrimitiveArray.build(
        (gen.random(n) < 0.034).astype(np.int64), nullable=False),
        "I1": _ints(gen, n, 0.45)}
    for name, (size, high, nulls) in MULTI_HOT.items():
        table[name] = _multi_hot(gen, n, size, high, nulls)
    valid = gen.random(n) >= 0.1
    lens = np.where(valid, gen.integers(1, 101, n), 0)
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(lens, out=offsets[1:])
    table["ragged"] = A.ListArray.build(A.PrimitiveArray.build(
        gen.integers(0, 2**20, int(offsets[-1]), dtype=np.int32),
        nullable=False), offsets, validity=valid)
    table["vec"] = A.FixedSizeListArray.build(
        gen.integers(0, 256, (n, 64)).astype(np.float32))
    return table


@pytest.fixture(scope="module")
def table():
    return _table()


@pytest.fixture(scope="module")
def files(table):
    return write_fragments(table, N_FRAGMENTS, WriteOptions("lance"))


ROWS = {
    "duplicates": np.array([7, 1999, 7, 0, 1000, 7, 1999]),
    "one fragment": np.array([260, 251, 499, 255, 260]),
    "all fragments": np.random.default_rng(5).integers(0, N_ROWS, 300),
    "empty": np.zeros(0, np.int64),
}


@pytest.mark.parametrize("decode", ["numpy", "pallas"])
@pytest.mark.parametrize("case", sorted(ROWS))
def test_take_columns_matches_the_reference(table, files, decode, case):
    rows = ROWS[case]
    ds = DatasetReader(files, store="tiered", decode=decode)
    got = ds.take_columns(list(table), rows)
    assert list(got) == list(table)
    for name, arr in table.items():
        want = A.to_pylist(arr)
        assert A.to_pylist(got[name]) == [want[i] for i in rows], name


def test_the_table_has_every_case(table, files):
    """Null ints, null lists (length 0), lists of 1 to 100 items, rows in
    all 8 fragments, and both structural encodings."""
    ragged = A.to_pylist(table["ragged"])
    lens = {len(x) for x in ragged if x is not None}
    assert None in ragged and min(lens) == 1 and max(lens) == 100
    assert None in A.to_pylist(table["I1"])
    assert not np.diff(table["C21"].offsets)[~table["C21"].validity].any()
    ds = DatasetReader(files)
    encodings = {lm["meta"]["encoding"] for c in ds.columns.values()
                 for lm in c["leaves"]}
    assert encodings == {"miniblock", "fullzip"}
    fi, _ = ds.locate(ROWS["all fragments"])
    assert set(fi) == set(range(N_FRAGMENTS))


@pytest.mark.parametrize("encoding,column", [
    ("lance", "vec"), ("lance", "C21"), ("lance", "I1"),
    ("arrow", "C3"), ("packed", "p")])
def test_take_is_take_columns_of_one_column(encoding, column):
    gen = np.random.default_rng(3)
    table = _table(400)
    table["p"] = A.StructArray.build(
        [("f", A.PrimitiveArray.build(
            gen.integers(0, 1 << 30, 400).astype(np.int64), nullable=False))],
        nullable=False)
    opts = (WriteOptions("lance", packed_columns=("p",))
            if encoding == "packed" else WriteOptions(encoding))
    ds = DatasetReader(write_fragments(table, 3, opts), decode="pallas")
    rows = np.array([399, 3, 3, 200, 0, 133])
    one = ds.take(column, rows)
    assert A.to_pylist(one) == A.to_pylist(ds.take_columns([column], rows)
                                           [column])
    assert A.to_pylist(one) == [A.to_pylist(table[column])[i] for i in rows]


def test_a_40_column_request_is_one_drain_and_a_launch_per_class():
    table = _criteo(1600)
    assert len(table) == 40
    tracer = Tracer()
    ds = DatasetReader(write_fragments(table, N_FRAGMENTS,
                                       WriteOptions("lance")),
                       store="tiered", decode="pallas", tracer=tracer)
    classes = {r.kernel_class for fr in ds.fragments for name in table
               for r in fr._leaf_readers(name)
               if isinstance(r, MiniBlockReader)}
    assert len(classes) == 3  # the label, the counts, the lists
    rows = np.random.default_rng(9).integers(0, 1600, 256)
    drains = len(ds.store.drain_log)
    tracer.reset()
    got = ds.take_columns(list(table), rows)
    assert len(ds.store.drain_log) == drains + 1
    assert ds.scheduler.n_batches == 1
    counts = tracer.metrics.counter_values("miniblock.")
    assert counts["miniblock.decode_launches"] == len(classes)
    per_launch = tracer.metrics.histogram("miniblock.leaves_per_launch")
    assert per_launch.count == len(classes)
    assert per_launch.total == 40 * N_FRAGMENTS
    names = [e["name"] for e in tracer.events if e["ph"] == "X"]
    assert names.count("dataset.take_columns") == 1
    assert names.count("miniblock.decode_batch") == 1
    assert names.count("kernel.launch:miniblock_decode") == len(classes)
    for name, arr in table.items():
        want = A.to_pylist(arr)
        assert A.to_pylist(got[name]) == [want[i] for i in rows], name
