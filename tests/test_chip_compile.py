"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers a kernel at the widths ``chip_smoke.py``
drives (SIFT1M-shaped search over 1024 partitions, posting lists and
nullable columns through the mini-block reader) and asks the TPU compiler
to accept it.  This catches Mosaic refusals (unaligned blocks, unsupported
gathers, VMEM overflow) that interpret mode on the CPU cannot see.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bitunpack, fullzip_gather, ivf_topk, miniblock_decode


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    desc = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip can be written to the persistent cache
    # but not read back without one: keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("rep_bits,def_bits,words", [
    (1, 0, 4096),   # posting lists: List<int64>, 20-bit row ids
    (0, 1, 4096),   # nullable int column, up to 31-bit values
    (1, 2, 1024),   # nullable List<int32> with nullable items
])
def test_miniblock_decode_compiles(spec, rep_bits, def_bits, words):
    C, tile = 64, miniblock_decode.MAX_ENTRIES
    _compile(
        lambda r, d, v, p: miniblock_decode.miniblock_decode_pallas(
            r, d, v, p, rep_bits=rep_bits, def_bits=def_bits,
            tile_entries=tile, interpret=False),
        spec((C, 256), jnp.uint32), spec((C, 256), jnp.uint32),
        spec((C, words), jnp.uint32), spec((C, 3), jnp.int32))


@pytest.mark.parametrize("C,rep_bits,def_bits,rep_words,def_words", [
    (16384, 1, 2, 256, 512),  # the 26 nullable List<int32> columns
    (8192, 0, 1, 1, 256),     # the 13 nullable int64 columns
    (256, 0, 0, 1, 1),        # the not-null label
])
def test_miniblock_decode_compiles_for_a_row_take(spec, C, rep_bits, def_bits,
                                                  rep_words, def_words):
    """One launch per level class of a 1024-row take of every column of
    the Criteo rows at 1M rows: thousands of chunks in one call, their
    parameters in scalar memory."""
    tile = miniblock_decode.MAX_ENTRIES
    _compile(
        lambda r, d, v, p: miniblock_decode.miniblock_decode_pallas(
            r, d, v, p, rep_bits=rep_bits, def_bits=def_bits,
            tile_entries=tile, interpret=False),
        spec((C, rep_words), jnp.uint32), spec((C, def_words), jnp.uint32),
        spec((C, tile), jnp.uint32), spec((C, 3), jnp.int32))


@pytest.mark.parametrize("n_rows,n_take", [(4096, 3000), (32768, 32768)])
def test_fullzip_gather_compiles(spec, n_rows, n_take):
    # 128-d float32 vectors: 512 value bytes + 4-byte control word per row,
    # padded to two 128-word tiles
    words = 2 * fullzip_gather.ROW_WORDS
    _compile(lambda z, r: fullzip_gather.fullzip_gather_pallas(
        z, r, interpret=False),
        spec((n_rows, words), jnp.uint32), spec((n_take,), jnp.int32))


@pytest.mark.parametrize("n_queries,n_cands,k", [
    (16, 1024, 32),      # centroid probe: 1024 partitions, nprobe 32
    (16, 32768, 10),     # candidates at nprobe 32: above one VMEM block
])
def test_ivf_topk_compiles(spec, n_queries, n_cands, k):
    d = 128
    tn = ivf_topk.cand_tile(n_cands, d)
    n = -(-n_cands // tn) * tn
    _compile(lambda q, c, i, m: ivf_topk.ivf_topk_pallas(
        q, c, i, m, k=k, interpret=False),
        spec((n_queries, d), jnp.float32), spec((n, d), jnp.float32),
        spec((1, n), jnp.int32), spec((n_queries, n), jnp.int32))


@pytest.mark.parametrize("bits", [1, 11, 32])
def test_bitunpack_compiles(spec, bits):
    wpb = bitunpack.VALS_PER_BLOCK * bits // 32
    _compile(lambda w: bitunpack.bitunpack_pallas(w, bits, interpret=False),
             spec((8 * wpb,), jnp.uint32))
