"""Shredding (Dremel rep/def) correctness: paper examples + case table.

The hypothesis roundtrip properties over arbitrary nested types live in
``test_shred_properties.py``."""

import numpy as np
import pytest

from repro.core import arrays as A
from repro.core import types as T
from repro.core.shred import shred, unshred


def rt(pyvals, typ):
    arr = A.from_pylist(pyvals, typ)
    back = unshred(shred(arr), typ)
    assert A.to_pylist(back) == pyvals


def test_paper_fig6_levels():
    """Struct<List<String>> example from the paper, exact rep/def codes."""
    typ = T.Struct((("x", T.List(T.Utf8())),))
    vals = [{"x": ["AB", "C"]}, {"x": None}, None, {"x": [None]}, {"x": []}]
    leaves = shred(A.from_pylist(vals, typ))
    l = leaves[0]
    assert l.rep.tolist() == [1, 0, 1, 1, 1, 1]
    assert l.defs.tolist() == [0, 0, 3, 4, 1, 2]
    assert l.max_rep == 1 and l.max_def == 4
    # def meanings match fig 6: 1=null item, 2=empty list, 3=null list, 4=null struct
    assert l.def_meanings[1] == "null_item"
    assert l.def_meanings[2].startswith("empty_list")
    assert l.def_meanings[3].startswith("null_list")
    assert l.def_meanings[4].startswith("null_struct")


CASES = [
    ([1, 2, None, 4], T.int64()),
    (["a", None, "bcd"], T.utf8()),
    ([None, [1, 2], [], None, [3]], T.List(T.int32())),
    ([[[1], [2, 3]], None, [[]], [None], []], T.List(T.List(T.int32()))),
    ([{"a": 1, "b": "x"}, None, {"a": None, "b": None}],
     T.Struct((("a", T.int64()), ("b", T.utf8())))),
    ([[1.0, 2.0], None, [3.0, 4.0]],
     T.FixedSizeList(T.Primitive("float32", nullable=False), 2)),
    ([[{"s": ["ab", None], "v": 1.0}, None, {"s": None, "v": None}], None, [],
      [{"s": [], "v": 2.5}]],
     T.List(T.Struct((("s", T.List(T.utf8())), ("v", T.float64()))))),
    ([], T.List(T.int64())),
    ([None, None], T.int32()),
]


@pytest.mark.parametrize("pyvals,typ", CASES)
def test_roundtrip_cases(pyvals, typ):
    rt(pyvals, typ)
