"""Seeded data generators, in plain numpy, and the store both systems build.

The generators are copied from ``chip_smoke.py`` (``sift_like``,
``make_table``) so that the yardstick does not move with the program.  They
return numpy arrays only: the reference indexes them directly, and
``systems/*`` turn them into the program's arrays.
"""

from __future__ import annotations

import numpy as np

SIFT_DIM = 128


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator for one use of one seed."""
    return np.random.default_rng([int(seed), int(stream)])


def sift_like(n: int, gen: np.random.Generator, n_modes: int,
              centers: np.ndarray | None = None) -> tuple:
    """``(vectors, centers)``: (n, 128) float32 with SIFT's value shape,
    non-negative integers below 256 drawn from a Gaussian mixture of
    ``n_modes`` modes, rows in random mode order.  Pass ``centers`` to draw
    more rows (queries) from the same mixture."""
    if centers is None:
        centers = gen.uniform(0.0, 100.0, (n_modes, SIFT_DIM)).astype(
            np.float32)
    out = np.empty((n, SIFT_DIM), np.float32)
    step = 1 << 17
    for lo in range(0, n, step):
        m = min(step, n - lo)
        x = centers[gen.integers(0, len(centers), m)]
        x += 20.0 * gen.standard_normal((m, SIFT_DIM), dtype=np.float32)
        out[lo:lo + m] = np.rint(np.clip(x, 0.0, 255.0))
    return out, centers


def nullable_ints(n: int, gen: np.random.Generator, null_share: float,
                  low: int, high: int, dtype) -> tuple:
    """``(validity, values)``; values are drawn for null slots too."""
    valid = gen.random(n) >= null_share
    return valid, gen.integers(low, high, n).astype(dtype)


def nullable_lists(n: int, gen: np.random.Generator, null_share: float,
                   min_len: int, max_len: int, low: int, high: int,
                   dtype) -> tuple:
    """``(validity, offsets, items)`` of a nullable list column; a null
    list keeps the items drawn for it."""
    lengths = gen.integers(min_len, max_len + 1, n)
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(lengths, out=offsets[1:])
    items = gen.integers(low, high, int(offsets[-1])).astype(dtype)
    valid = gen.random(n) >= null_share
    return valid, offsets, items


def store(cfg: dict):
    """The configuration's store spec for the program's writer: ``tiered``
    is an NVMe block cache of ``nvme_cache_bytes`` over S3."""
    if cfg["store"] != "tiered":
        raise ValueError(f"unknown store {cfg['store']!r}")
    from repro.store import TieredStore

    return lambda disk: TieredStore.cached(
        disk, cache_bytes=int(cfg["nvme_cache_bytes"]))
