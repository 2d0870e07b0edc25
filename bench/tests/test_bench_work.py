"""Each kernel's work count, at shapes computed by hand: from the request
and the written data, never from padded tiles."""

import numpy as np
import pytest

import bench_tiny  # noqa: F401
from bench_tiny import BENCH
from harness.cell import load_module


def metric(name):
    return load_module(BENCH / "metrics" / f"{name}.py")


def test_ivf_topk_work():
    m = metric("ivf_topk_roofline")
    q = np.zeros((3, 128), np.float32)
    c = np.zeros((1000, 128), np.float32)
    flops, nbytes = m.work((q, c, np.arange(1000), 10), {}, None)
    assert flops == 2 * 3 * 1000 * 128
    assert nbytes == 4 * (3 * 128 + 1000 * 128 + 1000) + 8 * 3 * 10
    # one query given as a vector
    assert m.work((q[0], c, np.arange(1000), 10), {}, None)[0] == \
        2 * 1000 * 128


def test_fullzip_gather_work():
    m = metric("fullzip_gather_roofline")
    zipped = np.zeros((37, 516), np.uint8)
    rows = np.arange(100, dtype=np.int32) % 37
    assert m.work((zipped, rows), {}, None) == (0.0, 2 * 100 * 516)


def test_miniblock_decode_work():
    m = metric("miniblock_decode_roofline")
    # two chunks and one padding chunk: 10 and 7 entries, 12- and 5-bit
    # values; 1-bit definition levels with 3 and 0 nulls (level 1 = null)
    params = np.array([[10, 12, 0], [7, 5, 0], [0, 0, 0]], np.int32)
    defs = np.zeros((3, 1024), np.int32)
    defs[0, [1, 4, 9]] = 1
    defs[0, 10:] = 1          # past n_entries: not counted
    out = (np.zeros((3, 1024)), defs, np.zeros((3, 1024)))
    flops, nbytes = m.work(
        (None, None, None, params),
        {"rep_bits": 0, "def_bits": 1, "vpe": 1, "tile_entries": 1024}, out)
    enc = (-(-10 // 8)) + (-(-7 * 12 // 8)) + (-(-7 // 8)) + (-(-7 * 5 // 8))
    dec = 4 * ((10 + 7) + (10 - 3 + 7))
    assert flops == 0.0 and nbytes == pytest.approx(enc + dec)


def test_miniblock_work_without_definition_levels():
    m = metric("miniblock_decode_roofline")
    params = np.array([[100, 3, 0]], np.int32)
    flops, nbytes = m.work((None, None, None, params),
                           {"rep_bits": 2, "def_bits": 0, "vpe": 4}, None)
    enc = (-(-100 * 2 // 8)) + (-(-400 * 3 // 8))
    assert nbytes == enc + 4 * (100 + 400)
