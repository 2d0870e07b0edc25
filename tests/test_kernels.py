"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.compression import bitpack
from repro.kernels import ops
from repro.kernels.ivf_topk import cand_tile
from repro.kernels.miniblock_decode import MAX_ENTRIES
from repro.kernels.ref import topk_mismatches, topk_tolerance
from repro.obs import Tracer

rng = np.random.default_rng(0)


@pytest.mark.parametrize("bits", [1, 3, 5, 8, 11, 16, 21, 32])
@pytest.mark.parametrize("n", [1, 100, 8192, 20_000])
def test_bitunpack_sweep(bits, n):
    v = rng.integers(0, 2 ** min(bits, 62), n, dtype=np.uint64)
    words = jnp.asarray(ops.pack_words(bitpack(v, bits)))
    got_pl = np.asarray(ops.bitunpack(words, n, bits))
    got_ref = np.asarray(ops.bitunpack(words, n, bits, use_pallas=False))
    assert (got_pl == v).all()
    assert (got_ref == v).all()


@pytest.mark.parametrize("rep_bits,def_bits", [(0, 0), (0, 1), (0, 2), (1, 2), (2, 3)])
@pytest.mark.parametrize("vpe", [1, 4])
@pytest.mark.parametrize("n_chunks", [1, 4])
def test_miniblock_decode_sweep(rep_bits, def_bits, vpe, n_chunks):
    """Widened kernel coverage: any rep/def level width, values-per-entry
    (fixed-size lists), per-chunk bit width + FoR reference."""
    C = n_chunks
    tile = 1024
    rep_words = np.zeros((C, (tile * rep_bits + 31) // 32 + 1 if rep_bits else 1), np.uint32)
    def_words = np.zeros((C, (tile * def_bits + 31) // 32 + 1 if def_bits else 1), np.uint32)
    val_words = np.zeros((C, (tile * vpe * 24 + 31) // 32 + 1), np.uint32)
    params = np.zeros((C, 3), np.int32)
    want = []
    for c in range(C):
        n = int(rng.integers(50, tile))
        bits = int(rng.integers(1, 24))
        ref = int(rng.integers(-100, 100))
        reps = rng.integers(0, 2 ** rep_bits, n, dtype=np.uint64) if rep_bits else None
        defs = (rng.integers(0, 2 ** def_bits, n, dtype=np.uint64)
                if def_bits else np.zeros(n, np.uint64))
        valid = defs == 0
        vals = rng.integers(0, 2 ** bits, int(valid.sum()) * vpe, dtype=np.uint64)
        if rep_bits:
            w = ops.pack_words(bitpack(reps, rep_bits))
            rep_words[c, : len(w)] = w
        if def_bits:
            w = ops.pack_words(bitpack(defs, def_bits))
            def_words[c, : len(w)] = w
        w = ops.pack_words(bitpack(vals, bits))
        val_words[c, : len(w)] = w
        params[c] = [n, bits, ref]
        er = np.zeros(tile, np.int32)
        if rep_bits:
            er[:n] = reps
        ed = np.zeros(tile, np.int32)
        ed[:n] = defs
        ev = np.zeros(tile * vpe, np.int32)
        ev[: len(vals)] = vals.astype(np.int64) + ref
        want.append((er, ed, ev))
    for use_pallas in [True, False]:
        r, d, v = ops.miniblock_decode(
            jnp.asarray(rep_words), jnp.asarray(def_words),
            jnp.asarray(val_words), jnp.asarray(params),
            rep_bits=rep_bits, def_bits=def_bits, vpe=vpe, tile_entries=tile,
            use_pallas=use_pallas)
        for c, (er, ed, ev) in enumerate(want):
            np.testing.assert_array_equal(np.asarray(r[c]), er)
            np.testing.assert_array_equal(np.asarray(d[c]), ed)
            np.testing.assert_array_equal(np.asarray(v[c]), ev)


@pytest.mark.parametrize("stride", [8, 24, 136, 512])
@pytest.mark.parametrize("n_take", [1, 7, 64])
def test_fullzip_gather_sweep(stride, n_take):
    zipped = rng.integers(0, 256, (300, stride), dtype=np.uint8)
    rows = rng.integers(0, 300, n_take).astype(np.int32)
    for use_pallas in [True, False]:
        got = np.asarray(ops.fullzip_gather(jnp.asarray(zipped), jnp.asarray(rows),
                                            use_pallas=use_pallas))
        np.testing.assert_array_equal(got, zipped[rows])


def test_kernel_matches_host_miniblock_column():
    """Integration: decode a real mini-block-encoded column on device and
    compare against the host reader."""
    from repro.core import arrays as A, types as T
    from repro.core.file import FileReader, WriteOptions, write_table
    from repro.core.compression import min_bits

    n = 9000
    vals = rng.integers(0, 50_000, n).astype(np.int64)
    validity = rng.random(n) < 0.9
    arr = A.PrimitiveArray(T.int64(), validity, vals)
    fb = write_table({"c": arr}, WriteOptions("lance-miniblock", fixed_codec="bitpack"))
    fr = FileReader(fb)
    want = fr.scan("c")

    # re-encode chunk payloads into kernel inputs
    col = fr.columns["c"]["leaves"][0]
    meta = col["meta"]
    C = len(meta["chunks"])
    DW = (MAX_ENTRIES + 31) // 32 + 1
    maxvw = 0
    packed = []
    for ci, cm in enumerate(meta["chunks"]):
        off = meta["chunk_offsets"][ci]
        raw = fr.disk.read(col["base"] + off, cm["words"] * 8)
        from repro.core.miniblock import _parse_chunk

        bufs = _parse_chunk(raw)
        dw = ops.pack_words(bufs[0])
        vw_meta = cm["bufmeta"][1]
        vw = ops.pack_words(bufs[1])
        ref = 0
        bits = vw_meta["bits"]
        packed.append((cm["n_entries"], bits, ref, dw, vw))
        maxvw = max(maxvw, len(vw))
    def_words = np.zeros((C, DW), np.uint32)
    val_words = np.zeros((C, maxvw), np.uint32)
    params = np.zeros((C, 3), np.int32)
    for c, (ne, bits, ref, dw, vw) in enumerate(packed):
        def_words[c, : len(dw)] = dw
        val_words[c, : len(vw)] = vw
        params[c] = [ne, bits, ref]
    _, ds, vs = ops.miniblock_decode(
        jnp.asarray(np.zeros((C, 1), np.uint32)), jnp.asarray(def_words),
        jnp.asarray(val_words), jnp.asarray(params),
        rep_bits=0, def_bits=1)
    got_vals = []
    for c, (ne, *_rest) in enumerate(packed):
        n_valid = int((np.asarray(ds[c][:ne]) == 0).sum())
        got_vals.append(np.asarray(vs[c][:n_valid]))
    got = np.concatenate(got_vals)
    np.testing.assert_array_equal(got, vals[validity])


# ---------------------------------------------------------------------------
# ivf_topk: batched distance + deterministic top-k (the IVF search kernel)
# ---------------------------------------------------------------------------


class _FallbackRecorder(Tracer):
    """A tracer that records the ops layer's fallback calls."""

    def __init__(self):
        super().__init__()
        self.calls = []

    def fallback(self, encoding, reason, **args):
        self.calls.append((encoding, reason))


@pytest.mark.parametrize("dim", [3, 64, 128, 200])
@pytest.mark.parametrize("nq,nc,k", [(1, 7, 3), (5, 300, 10), (9, 129, 1)])
def test_ivf_topk_parity_sweep(dim, nq, nc, k):
    """Both routes agree with the float64 top-k within the stated
    tolerance (the routes sum in different orders, so not bit for bit)."""
    r = np.random.default_rng(dim * 1000 + nq)
    q = r.standard_normal((nq, dim)).astype(np.float32)
    c = r.standard_normal((nc, dim)).astype(np.float32)
    ids = r.permutation(nc).astype(np.int64)
    mask = r.integers(0, 2, (nq, nc)).astype(np.int32)
    for m in (None, mask):
        for use_pallas in (True, False):
            d, w = ops.ivf_topk(q, c, ids, k, mask=m, use_pallas=use_pallas)
            assert np.asarray(d).shape == (nq, k)
            assert topk_mismatches(q, c, ids, k, d, w, mask=m) == []


def test_ivf_topk_tolerance_catches_a_wrong_winner():
    """The contract check is not vacuous: swapping in a far candidate or
    perturbing a distance beyond the tolerance is reported."""
    r = np.random.default_rng(1)
    q = r.standard_normal((2, 128)).astype(np.float32)
    c = r.standard_normal((300, 128)).astype(np.float32)
    ids = np.arange(300)
    d, w = (np.array(a) for a in ops.ivf_topk(q, c, ids, 5))
    assert topk_mismatches(q, c, ids, 5, d, w) == []
    far = int(np.argmax(((c - q[0]) ** 2).sum(1)))
    w_bad = w.copy()
    w_bad[0, 4] = far
    assert topk_mismatches(q, c, ids, 5, d, w_bad)
    d_bad = d.copy()
    d_bad[1, 0] += 10 * topk_tolerance(q, c)[1]
    assert topk_mismatches(q, c, ids, 5, d_bad, w)


def test_ivf_topk_candidate_tiles():
    """More candidates than one VMEM tile: the running top-k merge across
    candidate tiles matches the float64 top-k."""
    r = np.random.default_rng(2)
    n = 2 * cand_tile(1 << 20, 1024) + 37
    q = r.standard_normal((3, 1000)).astype(np.float32)
    c = r.standard_normal((n, 1000)).astype(np.float32)
    ids = r.permutation(n)
    d, w = ops.ivf_topk(q, c, ids, 7)
    assert topk_mismatches(q, c, ids, 7, d, w) == []


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_ivf_topk_matches_brute_force(dtype):
    r = np.random.default_rng(7)
    q = r.standard_normal((4, 24)).astype(dtype)
    c = r.standard_normal((50, 24)).astype(dtype)
    ids = np.arange(100, 150, dtype=np.int64)
    d, w = ops.ivf_topk(q, c, ids, 5)
    brute = ((c[None] - q[:, None]) ** 2).sum(-1).argsort(axis=1)[:, :5]
    np.testing.assert_array_equal(np.asarray(w), ids[brute])


def test_ivf_topk_tie_break_by_row_id():
    """Equal-distance candidates win in ascending row-id order, regardless
    of their position in the candidate matrix."""
    q = np.zeros((1, 8), np.float32)
    c = np.zeros((6, 8), np.float32)  # all distance 0: pure tie
    ids = np.array([40, 5, 99, 17, 3, 60], np.int64)
    for use_pallas in (True, False):
        _, w = ops.ivf_topk(q, c, ids, 4, use_pallas=use_pallas)
        np.testing.assert_array_equal(np.asarray(w)[0], [3, 5, 17, 40])


def test_ivf_topk_exhaustion_sentinels():
    """k beyond the eligible count pads with (inf, sentinel -> caller)."""
    r = np.random.default_rng(3)
    q = r.standard_normal((2, 16)).astype(np.float32)
    c = r.standard_normal((3, 16)).astype(np.float32)
    for use_pallas in (True, False):
        d, w = ops.ivf_topk(q, c, np.arange(3), 6, use_pallas=use_pallas)
        d, w = np.asarray(d), np.asarray(w)
        assert (w[:, 3:] == ops.IVF_ID_SENTINEL).all()
        assert np.isinf(d[:, 3:]).all()
        assert (w[:, :3] != ops.IVF_ID_SENTINEL).all()


def test_ivf_topk_no_silent_fallback():
    """Eligible input on the Pallas route must NOT emit a fallback."""
    tr = _FallbackRecorder()
    r = np.random.default_rng(0)
    q = r.standard_normal((2, 32)).astype(np.float32)
    c = r.standard_normal((20, 32)).astype(np.float32)
    ops.ivf_topk(q, c, np.arange(20), 4, use_pallas=True, tracer=tr)
    assert tr.calls == []


def test_ivf_topk_fallback_reasons():
    tr = _FallbackRecorder()
    r = np.random.default_rng(0)
    q64 = r.standard_normal((2, 8))
    c64 = r.standard_normal((10, 8))
    q32, c32 = q64.astype(np.float32), c64.astype(np.float32)
    ops.ivf_topk(q64, c64, np.arange(10), 3, tracer=tr)
    ops.ivf_topk(q32, np.zeros((0, 8), np.float32), np.zeros(0, np.int64),
                 3, tracer=tr)
    ops.ivf_topk(q32, c32, np.arange(10, dtype=np.int64) + (1 << 31), 3,
                 tracer=tr)
    assert tr.calls == [("ivf", "non-float32"), ("ivf", "no-candidates"),
                        ("ivf", ">31-bit-ids")]
    # the fallback route still answers correctly (wide ids kept intact)
    d, w = ops.ivf_topk(q32, c32, np.arange(10, dtype=np.int64) + (1 << 31), 3)
    brute = ((c32[None] - q32[:, None]) ** 2).sum(-1).argsort(axis=1)[:, :3]
    np.testing.assert_array_equal(np.asarray(w), brute + (1 << 31))


def test_ivf_topk_telemetry_counter():
    """The structured reason lands as a decode.fallback.ivf.* counter and a
    pallas_fallback instant — same contract as the decode kernels."""
    from repro.obs import Tracer

    tr = Tracer()
    r = np.random.default_rng(0)
    ops.ivf_topk(r.standard_normal((1, 8)), r.standard_normal((4, 8)),
                 np.arange(4), 2, tracer=tr)
    assert tr.metrics.counter_values("decode.fallback") == \
        {"decode.fallback.ivf.non-float32": 1}
    evs = [e for e in tr.events if e["name"] == "pallas_fallback"]
    assert evs and evs[0]["args"]["reason"] == "non-float32"
