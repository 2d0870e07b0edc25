"""The batched random-access (take) pipeline.

Property-style equivalence: for every structural encoding and data shape,
``take(rows)`` must equal ``scan()`` gathered at ``rows`` — including
unsorted and duplicated row ids (the pipeline dedupes before IO and fans
results back out to request order).  Plus the decode-route contract: the
Pallas mini-block decoder (interpret mode on CPU) is bit-identical to the
numpy path, with clean fallback for codecs the kernel doesn't cover.
"""

import numpy as np
import pytest

from repro.core import arrays as A, types as T
from repro.core.file import FileReader, WriteOptions, write_table

rng = np.random.default_rng(123)


def _dataset(kind: str, n: int) -> A.Array:
    if kind == "primitive":
        return A.PrimitiveArray.build(
            rng.integers(0, 1 << 20, n).astype(np.int64), nullable=False)
    if kind == "nullable":
        return A.PrimitiveArray.build(
            rng.integers(0, 1 << 20, n).astype(np.int64),
            validity=rng.random(n) > 0.1)
    if kind == "utf8":
        vals = [None if rng.random() < 0.1 else
                bytes(rng.integers(97, 123, rng.integers(0, 12), dtype=np.uint8))
                for _ in range(n)]
        return A.VarBinaryArray.build(vals, utf8=True)
    if kind == "fixed-size-list":
        return A.FixedSizeListArray.build(
            rng.integers(0, 1 << 10, (n, 4)).astype(np.int32),
            validity=rng.random(n) > 0.1)
    if kind == "nested-list":
        py = []
        for _ in range(n):
            u = rng.random()
            if u < 0.1:
                py.append(None)
            elif u < 0.2:
                py.append([])
            else:
                py.append([None if rng.random() < 0.1 else int(v)
                           for v in rng.integers(0, 1 << 16, rng.integers(1, 6))])
        return A.from_pylist(py, T.List(T.Primitive("int64", nullable=True)))
    raise ValueError(kind)


ENCODINGS = [
    ("lance", WriteOptions("lance")),
    ("lance-miniblock", WriteOptions("lance-miniblock")),
    ("lance-fullzip", WriteOptions("lance-fullzip")),
    ("parquet", WriteOptions("parquet")),
    ("arrow", WriteOptions("arrow")),
]
KINDS = ["primitive", "nullable", "utf8", "fixed-size-list", "nested-list"]


def _messy_rows(n: int, k: int) -> np.ndarray:
    """Unsorted row ids with duplicates (and a reversed tail)."""
    rows = rng.integers(0, n, k)
    rows[: k // 4] = rows[k // 2: k // 2 + k // 4][::-1]  # forced duplicates
    return rows


@pytest.mark.parametrize("encname,opts", ENCODINGS, ids=[e[0] for e in ENCODINGS])
@pytest.mark.parametrize("kind", KINDS)
def test_take_equals_scan_gather(encname, opts, kind):
    # large enough that mini-block rows cross chunk boundaries for lists
    n = 3000 if kind == "nested-list" else 600
    arr = _dataset(kind, n)
    fr = FileReader(write_table({"c": arr}, opts))
    want = A.to_pylist(fr.scan("c"))
    assert want == A.to_pylist(arr)
    rows = _messy_rows(n, 41)
    got = A.to_pylist(fr.take("c", rows))
    assert got == [want[i] for i in rows]


@pytest.mark.parametrize("encname,opts", ENCODINGS[:3], ids=[e[0] for e in ENCODINGS[:3]])
def test_take_reversed_and_empty(encname, opts):
    arr = _dataset("nullable", 500)
    fr = FileReader(write_table({"c": arr}, opts))
    want = A.to_pylist(arr)
    rows = np.arange(499, -1, -7)
    assert A.to_pylist(fr.take("c", rows)) == [want[i] for i in rows]
    assert len(fr.take("c", np.zeros(0, np.int64))) == 0


@pytest.mark.parametrize("enc", ["lance-miniblock", "lance-fullzip"])
def test_take_out_of_range_raises(enc):
    arr = _dataset("primitive", 200)
    fr = FileReader(write_table({"c": arr}, WriteOptions(enc)))
    with pytest.raises(IndexError):
        fr.take("c", np.array([0, 200]))
    with pytest.raises(IndexError):
        fr.take("c", np.array([-1]))


def test_packed_take_out_of_range_raises():
    arr = A.StructArray.build(
        [("f0", A.PrimitiveArray.build(np.arange(100, dtype=np.int64),
                                       nullable=False))], nullable=False)
    fr = FileReader(write_table({"s": arr},
                                WriteOptions("lance", packed_columns=("s",))))
    with pytest.raises(IndexError):
        fr.take("s", np.array([100]))


def test_fullzip_take_dedupes_fixed_width_io():
    """Duplicate rows must not re-read identical spans: 1 IOP per *unique*
    row on the fixed-width (no repetition index) path."""
    arr = A.FixedSizeListArray.build(
        rng.standard_normal((400, 32)).astype(np.float32), nullable=False)
    fr = FileReader(write_table({"c": arr}, WriteOptions("lance-fullzip")))
    rows = np.array([7, 3, 7, 7, 3, 11, 3])
    fr.reset_io()
    got = fr.take("c", rows)
    st = fr.io_stats()
    assert st.n_iops == 3  # unique rows only
    assert st.max_phase == 1
    want = A.to_pylist(arr)
    assert A.to_pylist(got) == [want[i] for i in rows]


def test_fullzip_take_dedupes_rep_index_io():
    """Var-width path: 2 IOPS (index + span) per unique row, duplicates
    fanned out from the decoded result."""
    arr = _dataset("utf8", 400)
    fr = FileReader(write_table({"c": arr}, WriteOptions("lance-fullzip")))
    rows = np.array([5, 2, 5, 2, 9, 5])
    fr.reset_io()
    got = fr.take("c", rows)
    st = fr.io_stats()
    assert st.n_iops == 2 * 3
    assert st.max_phase == 2
    want = A.to_pylist(arr)
    assert A.to_pylist(got) == [want[i] for i in rows]


def test_packed_struct_take_dup_unsorted():
    n = 300
    children = [(f"f{i}", A.PrimitiveArray.build(
        rng.integers(0, 1 << 30, n).astype(np.int64), nullable=False))
        for i in range(3)]
    arr = A.StructArray.build(children, nullable=False)
    fr = FileReader(write_table({"s": arr},
                                WriteOptions("lance", packed_columns=("s",))))
    rows = np.array([250, 3, 250, 17, 3, 250])
    fr.reset_io()
    got = fr.take("s", rows)
    assert fr.io_stats().n_iops == 3  # deduped, one IOP per unique row
    want = A.to_pylist(arr)
    assert A.to_pylist(got) == [want[i] for i in rows]


# ---------------------------------------------------------------------------
# pallas decode route
# ---------------------------------------------------------------------------


def _bit_identical(a: A.Array, b: A.Array):
    assert np.array_equal(a.validity, b.validity)
    if isinstance(a, A.VarBinaryArray):
        assert np.array_equal(a.offsets, b.offsets)
        assert np.array_equal(a.data, b.data)
    elif isinstance(a, A.ListArray):
        assert np.array_equal(a.offsets, b.offsets)
        _bit_identical(a.child, b.child)
    elif isinstance(a, A.StructArray):
        for (_, ca), (_, cb) in zip(a.children, b.children):
            _bit_identical(ca, cb)
    else:
        assert a.values.dtype == b.values.dtype
        assert np.array_equal(a.values, b.values)


@pytest.mark.parametrize("kind", ["primitive", "nullable"])
def test_miniblock_pallas_parity(kind):
    """decode='pallas' (interpret mode) is bit-identical to numpy on the
    bit-packed flat integer path, for take and scan."""
    arr = _dataset(kind, 5000)  # several chunks
    fb = write_table({"c": arr}, WriteOptions("lance-miniblock"))
    fr_np = FileReader(fb, decode="numpy")
    fr_pl = FileReader(fb, decode="pallas")
    rows = _messy_rows(5000, 67)
    _bit_identical(fr_np.take("c", rows), fr_pl.take("c", rows))
    _bit_identical(fr_np.scan("c"), fr_pl.scan("c"))
    # identical logical IO regardless of decode route
    fr_np.reset_io(); fr_np.take("c", rows)
    fr_pl.reset_io(); fr_pl.take("c", rows)
    a, b = fr_np.io_stats(), fr_pl.io_stats()
    assert (a.n_iops, a.bytes_read, a.max_phase) == (b.n_iops, b.bytes_read, b.max_phase)


def test_miniblock_pallas_fallback_codecs():
    """Codecs the kernel doesn't cover (floats/utf8) fall back to numpy and
    still roundtrip under decode='pallas'."""
    for kind in ["utf8", "fixed-size-list"]:
        arr = _dataset(kind, 400)
        fb = write_table({"c": arr}, WriteOptions("lance-miniblock"))
        fr = FileReader(fb, decode="pallas")
        want = A.to_pylist(arr)
        rows = np.array([3, 1, 3, 99, 1])
        assert A.to_pylist(fr.take("c", rows)) == [want[i] for i in rows]


def _struct_nullable(n: int) -> A.Array:
    """Nullable struct with a nullable int field: max_def == 2, so the def
    stream is multi-bit (the widened kernel's nested-null coverage)."""
    inner = A.PrimitiveArray.build(
        rng.integers(0, 1 << 12, n).astype(np.int64),
        validity=rng.random(n) > 0.15)
    return A.StructArray.build([("f", inner)], validity=rng.random(n) > 0.1)


WIDENED = [
    ("fixed-size-list", lambda: _dataset("fixed-size-list", 5000), {}),
    ("nested-list", lambda: _dataset("nested-list", 6000), {}),
    ("bytepack", lambda: A.PrimitiveArray.build(
        (rng.integers(0, 1 << 16, 5000) + 123_456).astype(np.int64),
        validity=rng.random(5000) > 0.1), {"fixed_codec": "bytepack"}),
    ("struct-def2", lambda: _struct_nullable(5000), {}),
]


@pytest.mark.parametrize("name,build,kw", WIDENED, ids=[w[0] for w in WIDENED])
def test_miniblock_pallas_widened_coverage(name, build, kw):
    """Chunk shapes that used to hit the numpy fallback — multi-bit def
    streams, rep streams, FoR bytepack, fixed-size-list values — now decode
    through the kernel bit-identically, with identical logical IO."""
    arr = build()
    n = len(arr)
    fb = write_table({"c": arr}, WriteOptions("lance-miniblock", **kw))
    fr_np = FileReader(fb, decode="numpy")
    fr_pl = FileReader(fb, decode="pallas")
    rows = _messy_rows(n, 67)
    _bit_identical(fr_np.take("c", rows), fr_pl.take("c", rows))
    _bit_identical(fr_np.scan("c"), fr_pl.scan("c"))
    fr_np.reset_io(); fr_np.take("c", rows)
    fr_pl.reset_io(); fr_pl.take("c", rows)
    a, b = fr_np.io_stats(), fr_pl.io_stats()
    assert (a.n_iops, a.bytes_read, a.max_phase) == (b.n_iops, b.bytes_read, b.max_phase)


def test_miniblock_widened_chunks_use_kernel():
    """The widened shapes actually route through the kernel (no silent
    fallback): the batched pallas decode path must claim the chunks."""
    for name, build, kw in WIDENED:
        arr = build()
        fb = write_table({"c": arr}, WriteOptions("lance-miniblock", **kw))
        fr = FileReader(fb, decode="pallas")
        for reader in fr._leaf_readers("c"):
            if not reader._pallas_eligible():
                # the nested-list *values* leaf is int64 -> must be eligible;
                # only non-integer leaves may fall back
                raise AssertionError(f"{name}: column not kernel-eligible")
            n_chunks = len(reader.meta["chunks"])
            kp = [reader._chunk_kernel_params(
                reader.meta["chunks"][c]["bufmeta"][
                    (1 if reader.proto.max_rep else 0)
                    + (1 if reader.proto.max_def else 0)])
                for c in range(n_chunks)]
            assert all(p is not None for p in kp), f"{name}: chunk fell back"


@pytest.mark.parametrize("kind", ["primitive", "nullable", "fixed-size-list"])
def test_fullzip_pallas_gather_route(kind):
    """decode='pallas' routes the fixed-stride full-zip take through the
    fullzip_gather kernel: bit-identical to the host permutation, with
    identical logical IO (duplicates still served from one read)."""
    arr = _dataset(kind, 700)
    fb = write_table({"c": arr}, WriteOptions("lance-fullzip"))
    fr_np = FileReader(fb, decode="numpy")
    fr_pl = FileReader(fb, decode="pallas")
    rows = _messy_rows(700, 53)
    _bit_identical(fr_np.take("c", rows), fr_pl.take("c", rows))
    fr_np.reset_io(); fr_np.take("c", rows)
    fr_pl.reset_io(); fr_pl.take("c", rows)
    a, b = fr_np.io_stats(), fr_pl.io_stats()
    assert (a.n_iops, a.bytes_read, a.useful_bytes, a.max_phase) == \
           (b.n_iops, b.bytes_read, b.useful_bytes, b.max_phase)


def test_fullzip_pallas_var_width_unaffected():
    """The gather route only covers fixed strides; variable-width full-zip
    under decode='pallas' still takes the row-parallel host path."""
    arr = _dataset("utf8", 400)
    fb = write_table({"c": arr}, WriteOptions("lance-fullzip"))
    want = A.to_pylist(arr)
    rows = np.array([7, 1, 7, 390, 1])
    got = A.to_pylist(FileReader(fb, decode="pallas").take("c", rows))
    assert got == [want[i] for i in rows]


# ---------------------------------------------------------------------------
# bounded-memory scan windows
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("encname,opts", ENCODINGS[:4], ids=[e[0] for e in ENCODINGS[:4]])
@pytest.mark.parametrize("kind", ["utf8", "fixed-size-list", "nested-list"])
@pytest.mark.parametrize("io_chunk", [64, 257, 8 << 20])
def test_scan_windows_any_chunk_size(encname, opts, kind, io_chunk):
    """Windowed scans decode at entry/page boundaries and carry tails, so
    any io_chunk (down to a few bytes over the largest header) roundtrips —
    for variable-width, fixed-stride, and repeated leaves alike."""
    arr = _dataset(kind, 500)
    fr = FileReader(write_table({"c": arr}, opts))
    assert A.to_pylist(fr.scan("c", io_chunk=io_chunk)) == A.to_pylist(arr)


def test_decode_knob_in_write_options():
    """WriteOptions(decode=...) is recorded in the footer and picked up as
    the reader default; an explicit reader arg overrides it."""
    arr = _dataset("primitive", 300)
    fb = write_table({"c": arr}, WriteOptions("lance-miniblock", decode="pallas"))
    fr = FileReader(fb)
    assert fr.decode == "pallas"
    assert FileReader(fb, decode="numpy").decode == "numpy"
    want = A.to_pylist(arr)
    assert A.to_pylist(fr.take("c", np.array([5, 0, 5]))) == [want[5], want[0], want[5]]
    with pytest.raises(ValueError):
        WriteOptions("lance-miniblock", decode="gpu")
    with pytest.raises(ValueError):
        FileReader(fb, decode="gpu")
