"""Generator of take traffic: each request reads ``columns`` at
``rows_per_request`` row ids drawn uniformly, with replacement, over the
whole table.

Mix parameters: ``columns``, ``rows_per_request``, and ``warmup``:
``{"quiet": q, "max": m}`` (set-up serves warm-up requests, drawn like the
window's from another stream of the seed, until ``q`` in a row load no new
program, and at most ``m``).
"""

from __future__ import annotations

from typing import Iterator

import numpy as np

from harness.gen import rng

WINDOW_STREAM, WARMUP_STREAM = 101, 102


def requests(mix: dict, data: dict, seed: int, warmup: bool = False
             ) -> Iterator[dict]:
    """Endless stream of requests for the window (or for warm-up)."""
    gen = rng(seed, WARMUP_STREAM if warmup else WINDOW_STREAM)
    n, m = data["n_rows"], int(mix["rows_per_request"])
    while True:
        yield {"op": "take", "columns": list(mix["columns"]),
               "rows": gen.integers(0, n, m, dtype=np.int64)}
