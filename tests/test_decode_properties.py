"""Property-based decode-parity tests.

The row-parallel full-zip decode (frontier walk over row spans, pointer-
doubling entry discovery for scans) must be bit-identical to the retained
sequential per-value walk (``FullZipReader._decode_entries_walk``) over
arbitrary rep/def/null/length shapes.
Example-based equivalents live in ``test_take_pipeline.py``.
"""

import numpy as np

from hypothesis import given, settings, strategies as st

from repro.core import arrays as A, types as T
from repro.core.file import FileReader, WriteOptions, write_table


def _leaf_reader(arr: A.Array, bytes_codec=None):
    opts = WriteOptions("lance-fullzip", bytes_codec=bytes_codec)
    fr = FileReader(write_table({"c": arr}, opts))
    readers = fr._leaf_readers("c")
    return fr, readers


def _walk_eq_rowparallel(fr, reader, n_rows):
    """The oracle check: whole-payload decode, walk vs chain discovery."""
    m = reader.meta
    raw = fr.disk.read(reader.base + m["zip_base"], m["zip_bytes"])
    rw, dw, vw = reader._decode_entries_walk(raw, n_hint=m["n_entries"])
    rp, dp, vp = reader._decode_entries(raw, n_hint=m["n_entries"])
    assert (rw is None) == (rp is None) and (dw is None) == (dp is None)
    if rw is not None:
        np.testing.assert_array_equal(rw, rp)
    if dw is not None:
        np.testing.assert_array_equal(dw, dp)
    if isinstance(vw, A.VarBinaryArray):
        np.testing.assert_array_equal(vw.offsets, vp.offsets)
        np.testing.assert_array_equal(vw.data, vp.data)
    else:
        np.testing.assert_array_equal(vw.values, vp.values)


# -- strategies -------------------------------------------------------------

utf8_rows = st.lists(
    st.one_of(st.none(), st.binary(max_size=40)), min_size=1, max_size=120)

nested_rows = st.lists(
    st.one_of(
        st.none(),
        st.lists(st.one_of(st.none(), st.binary(max_size=24)), max_size=6),
    ),
    min_size=1, max_size=80)


@settings(max_examples=40, deadline=None)
@given(utf8_rows)
def test_flat_var_width_walk_parity(rows):
    arr = A.from_pylist(rows, T.Binary(True))
    fr, readers = _leaf_reader(arr)
    _walk_eq_rowparallel(fr, readers[0], len(rows))


@settings(max_examples=40, deadline=None)
@given(nested_rows, st.randoms(use_true_random=False))
def test_nested_var_width_walk_parity(rows, rnd):
    """Random rep/def/null/length shapes: list<binary> rows (null lists,
    empty lists, null items, empty values) through take and scan must match
    the walk and the pylist oracle."""
    arr = A.from_pylist(rows, T.List(T.Binary(True)))
    fr, readers = _leaf_reader(arr)
    for r in readers:
        _walk_eq_rowparallel(fr, r, len(rows))
    want = A.to_pylist(arr)
    assert A.to_pylist(fr.scan("c")) == want
    # windowed scan with a tail-carrying chunk size
    assert A.to_pylist(fr.scan("c", io_chunk=rnd.randrange(8, 128))) == want
    take = [rnd.randrange(len(rows)) for _ in range(min(16, 2 * len(rows)))]
    got = A.to_pylist(fr.take("c", np.array(take, dtype=np.int64)))
    assert got == [want[i] for i in take]


@settings(max_examples=20, deadline=None)
@given(utf8_rows)
def test_var_width_fsst_walk_parity(rows):
    """Transparent per-value compression (fsst) under the row-parallel
    decode: stored lengths differ from logical lengths, so this exercises
    the length-prefix path with a real codec in the loop."""
    arr = A.from_pylist(rows, T.Utf8(True))
    fr, readers = _leaf_reader(arr, bytes_codec="fsst_lite")
    _walk_eq_rowparallel(fr, readers[0], len(rows))
    assert A.to_pylist(fr.scan("c")) == A.to_pylist(arr)
