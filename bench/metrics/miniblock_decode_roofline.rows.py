"""miniblock_decode_roofline.rows: ``miniblock_decode_roofline`` in the
row-take cells: the same work count, device events and arithmetic, imported
from that metric's file, recorded under this metric's name."""

from pathlib import Path

from harness.cell import load_module

_base = load_module(Path(__file__).with_name("miniblock_decode_roofline.py"))
WRAP = _base.WRAP
WORK_OF = _base.WORK_OF
EVENTS = _base.EVENTS
work = _base.work


def read(view):
    return view.roofline_pct("miniblock_decode_roofline.rows", EVENTS)
