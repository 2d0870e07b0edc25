"""Generator of search traffic: the window cycles through the
configuration's query pool (``data["queries"]``) in an order drawn from the
seed, ``queries_per_request`` queries to a request.

Mix parameters: ``query_pool`` (the pool's size, which the system draws),
``queries_per_request``, and ``warmup``: ``{"quiet": q, "max": m,
"postings": p}`` (``p``: the first requests of the window's order whose
posting takes are warmed, more than a window serves).
Warm-up draws from the same pool, from the far end of the window's order
(queries a window of any length reaches last), after the requests the
system picks to reach every padded shape of the pool
(``systems/<system>.py`` ``warm``).
"""

from __future__ import annotations

import itertools
from typing import Iterator, List

import numpy as np

from harness.gen import rng

ORDER_STREAM = 101


def order(data: dict, seed: int) -> np.ndarray:
    """The pool's query indices in the window's order."""
    return rng(seed, ORDER_STREAM).permutation(len(data["queries"]))


def batches(mix: dict, data: dict, idx: np.ndarray) -> List[dict]:
    """``idx`` cut into requests of ``queries_per_request`` queries (the
    last one shorter where the pool does not divide)."""
    b = int(mix["queries_per_request"])
    return [{"op": "search", "queries": idx[i:i + b],
             "vectors": data["queries"][idx[i:i + b]]}
            for i in range(0, len(idx), b)]


def pool(mix: dict, data: dict, seed: int) -> List[dict]:
    """Every request a window can send, in the window's order."""
    return batches(mix, data, order(data, seed))


def requests(mix: dict, data: dict, seed: int, warmup: bool = False
             ) -> Iterator[dict]:
    """Endless stream of requests for the window (or, reversed, for
    warm-up)."""
    reqs = pool(mix, data, seed)
    return itertools.cycle(reqs[::-1] if warmup else reqs)
