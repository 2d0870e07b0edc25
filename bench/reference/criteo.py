"""Plain reference for ``take_columns`` of Criteo rows: the requested rows
indexed straight out of the generated columns, which have the ``table``
system's form, so the ``table`` reference serves as it is (it imports
nothing of the program).

``check`` counts the rows of the window's requests whose validity, value or
list items differ from the reference in any column (``mismatched_rows``;
null slots and null lists are not compared).  ``control`` is the reference
in the program's place with every column stored in the nearest lower
integer type that changes its data: int64 counts as int16 (int32 holds
every count below 2^24 and changes nothing), int32 feature ids as int16;
the 0/1 label has no lower type that changes it.
"""

from __future__ import annotations

from pathlib import Path

from harness.cell import load_module

_table = load_module(Path(__file__).with_name("table.py"))
check = _table.check
control = _table.control
