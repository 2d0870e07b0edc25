"""Property-based shredding tests over arbitrary nested types.
Example-based cases stay in ``test_shred.py``."""

import numpy as np

from hypothesis import given, settings, strategies as st

from repro.core import arrays as A
from repro.core import types as T
from repro.core.shred import shred, unshred


def rt(pyvals, typ):
    arr = A.from_pylist(pyvals, typ)
    back = unshred(shred(arr), typ)
    assert A.to_pylist(back) == pyvals


def _type_strategy(depth=2):
    prim = st.sampled_from([T.int64(), T.int32(), T.float64(), T.utf8()])
    if depth == 0:
        return prim
    sub = _type_strategy(depth - 1)
    return st.one_of(
        prim,
        st.builds(lambda c, n: T.List(c, nullable=n), sub, st.booleans()),
        st.builds(lambda c, n: T.Struct((("f", c),), nullable=n), sub, st.booleans()),
    )


def _value_for(typ, draw, size):
    if isinstance(typ, T.Primitive):
        if typ.dtype.startswith("f"):
            gen = st.floats(-100, 100, allow_nan=False).map(lambda x: float(np.float64(x)))
        else:
            gen = st.integers(-1000, 1000)
    elif isinstance(typ, T.Utf8):
        gen = st.text(alphabet="abcXYZ", max_size=6)
    elif isinstance(typ, T.List):
        gen = st.lists(_value_strategy(typ.child), max_size=4)
    elif isinstance(typ, T.Struct):
        gen = st.fixed_dictionaries({n: _value_strategy(f) for n, f in typ.fields})
    else:
        raise TypeError(typ)
    return gen


def _value_strategy(typ):
    base = _value_for(typ, None, None)
    if typ.nullable:
        return st.one_of(st.none(), base)
    return base


@settings(max_examples=60, deadline=None)
@given(data=st.data())
def test_roundtrip_property(data):
    typ = data.draw(_type_strategy())
    n = data.draw(st.integers(0, 12))
    vals = [data.draw(_value_strategy(typ)) for _ in range(n)]
    rt(vals, typ)


@settings(max_examples=30, deadline=None)
@given(data=st.data())
def test_entry_stream_invariants(data):
    """Entries with def==0 exactly equal the number of stored values; every
    top-level row contributes >=1 entry."""
    typ = data.draw(_type_strategy())
    n = data.draw(st.integers(1, 10))
    vals = [data.draw(_value_strategy(typ)) for _ in range(n)]
    arr = A.from_pylist(vals, typ)
    for leaf in shred(arr):
        n_valid = int((leaf.defs == 0).sum()) if leaf.defs is not None else leaf.n_entries
        assert n_valid == len(leaf.values)
        if leaf.max_rep > 0:
            assert int((leaf.rep == leaf.max_rep).sum()) == n
        else:
            assert leaf.n_entries == n
