"""Property-based codec tests.

The example-based equivalents stay in ``test_compression.py``."""

import numpy as np

from hypothesis import given, settings, strategies as st

from repro.core.compression import get_bytes_codec, get_fixed_codec

rng = np.random.default_rng(0)


@settings(max_examples=40, deadline=None)
@given(st.lists(st.integers(0, 2**40), max_size=200))
def test_bytepack_property(xs):
    v = np.array(xs, dtype=np.int64)
    c = get_fixed_codec("bytepack")
    enc = c.encode(v)
    assert (np.asarray(c.decode(enc, len(v))) == v).all()
    # byte-aligned: encoded width is an integer number of bytes
    if len(v):
        assert enc.data.nbytes == c.encoded_width(enc) * len(v)


@settings(max_examples=30, deadline=None)
@given(st.binary(max_size=400), st.integers(1, 7))
def test_fsst_arbitrary_bytes(blob, nvals):
    """FSST-lite must roundtrip arbitrary binary (escape path)."""
    c = get_bytes_codec("fsst_lite")
    cuts = sorted(rng.integers(0, len(blob) + 1, nvals - 1).tolist()) if nvals > 1 else []
    bounds = [0] + cuts + [len(blob)]
    vals = [blob[bounds[i]: bounds[i + 1]] for i in range(len(bounds) - 1)]
    lengths = np.array([len(v) for v in vals], dtype=np.int64)
    data = np.frombuffer(blob, np.uint8) if blob else np.zeros(0, np.uint8)
    enc = c.encode(lengths, data)
    out_lens, out_data = c.decode(enc, enc.out_lengths)
    assert out_data.tobytes() == blob
    assert (out_lens == lengths).all()
