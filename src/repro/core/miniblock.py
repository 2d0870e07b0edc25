"""The mini-block structural encoding (paper §4.2).

Small data types are chunked into compressed mini-blocks of 1–2 disk sectors
(4–8 KiB target, hard ceiling 32 KiB from the 12-bit word count), each chunk
holding bit-packed repetition levels, definition levels and value buffers.
Whole chunks are decoded at once, so opaque compression is allowed; random
access pays chunk-sized read amplification plus decode work — the trade the
paper accepts for small types.

Chunk rules implemented exactly as §4.2.1/4.2.2:
* power-of-two number of entries per chunk (last chunk may be ragged),
  at most 4096;
* chunk payload padded to 8-byte words; on-disk chunk meta is 2 bytes
  (12-bit word count, 4-bit log2(num values));
* chunk = [u16 n_buffers][u16 size x n_buffers][8-aligned buffers...];
* buffers: [rep][def][values...] (absent streams are skipped);
* a repetition index with N+1 = 2 counters per chunk supports one level of
  random access (§4.2.3), handling rows that split across chunks.

Search cache (§4.2.4): 24 in-memory bytes per chunk without a repetition
index, 41 with — we model exactly those numbers.

Random access runs as a batched decode-once pipeline (see
:class:`MiniBlockReader`): one vectorized repetition-index lookup for all
rows, one phase-grouped ``read_many`` IO dispatch, each chunk decoded
exactly once (optionally on-device via the ``decode='pallas'`` knob — the
power-of-two/8-aligned chunk rules make the kernel's static BlockSpec
tiling possible), and a single segment-id permutation back to request
order.  The logical IOPS/byte trace is identical to the historical per-row
reader.

A take is traced on the batch handle's tracer as three ``miniblock.take``
spans, one per step, whose host steps are child spans: the read
(``miniblock.ranges``, the repetition-index lookup and the chunk cover, the
store's ``store.read`` and ``miniblock.parse``, splitting the fetched bytes
into chunk buffers), the decode (inside a ``miniblock.decode_batch`` span:
the decode kernel's ``kernel.*`` spans and ``miniblock.decode``, host decode
of the chunks the kernel does not take) and the select (``miniblock.select``,
row extraction and the request-order permutation).  A multi-column take
runs the read and the select per leaf and one decode for all its leaves.
"""

from __future__ import annotations

import struct
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from ..obs import NULL_TRACER
from . import arrays as A
from . import types as T
from .compression import Encoded, get_bytes_codec, get_fixed_codec, min_bits
from .encodings_base import (
    ColumnReader,
    EncodedColumn,
    empty_leaf,
    empty_values,
    leaf_slice,
    pad_to,
    reorder_leaf_rows,
    value_bytes,
)
from .rdlevels import level_bits, pack_levels, unpack_levels
from .shred import ShreddedLeaf

__all__ = ["encode_miniblock", "MiniBlockReader", "ChunkPlan", "decode_plans"]

SPAN_TAKE = "miniblock.take"
SPAN_RANGES = "miniblock.ranges"
SPAN_PARSE = "miniblock.parse"
SPAN_DECODE = "miniblock.decode"
SPAN_SELECT = "miniblock.select"
SPAN_DECODE_BATCH = "miniblock.decode_batch"

MAX_CHUNK_VALUES = 4096
TARGET_CHUNK_BYTES = 8 * 1024  # 1-2 disk sectors compressed
MAX_CHUNK_WORDS = (1 << 12) - 1  # 12-bit word count
MIN_CHUNK_VALUES = 32

# in-memory search-cache cost model from the paper (sec 4.2.4)
CACHE_BYTES_PER_CHUNK = 24
CACHE_BYTES_PER_CHUNK_WITH_REP = 41


def _default_fixed_codec(values: A.Array) -> str:
    dt = values.values.dtype if not isinstance(values, A.VarBinaryArray) else None
    if dt is not None and dt.kind in ("i", "u"):
        return "bitpack"
    return "plain"


def _encode_chunk_values(
    leaf_type: T.DataType,
    values: A.Array,
    fixed_codec: str,
    bytes_codec: str,
) -> List[Encoded]:
    """Encode the (sparse) values of one chunk into 1-2 buffers."""
    if isinstance(leaf_type, (T.Utf8, T.Binary)):
        lengths = (values.offsets[1:] - values.offsets[:-1]).astype(np.uint64)
        bc = get_bytes_codec(bytes_codec)
        enc_data = bc.encode(lengths, values.data)
        stored = enc_data.out_lengths if enc_data.out_lengths is not None else lengths
        enc_lens = get_fixed_codec(fixed_codec if fixed_codec != "plain" else "bitpack").encode(
            np.asarray(stored, dtype=np.uint64)
        )
        return [enc_lens, enc_data]
    if isinstance(leaf_type, T.FixedSizeList):
        flat = values.values.reshape(-1)
        codec = get_fixed_codec("plain" if flat.dtype.kind == "f" else fixed_codec)
        enc = codec.encode(flat)
        enc.meta["fsl"] = leaf_type.size
        enc.meta["codec"] = codec.name
        return [enc]
    codec = get_fixed_codec("plain" if values.values.dtype.kind == "f" else fixed_codec)
    enc = codec.encode(values.values)
    enc.meta["codec"] = codec.name
    return [enc]


def _decode_chunk_values(
    leaf_type: T.DataType,
    bufs: List[np.ndarray],
    metas: List[Dict],
    n_values: int,
    fixed_codec: str,
    bytes_codec: str,
) -> A.Array:
    if isinstance(leaf_type, (T.Utf8, T.Binary)):
        lens_codec = get_fixed_codec(metas[0].get("codec", "bitpack"))
        stored = lens_codec.decode(Encoded(bufs[0], metas[0]), n_values).astype(np.int64)
        bc = get_bytes_codec(bytes_codec)
        out_lens, out_data = bc.decode(Encoded(bufs[1], metas[1]), stored)
        offsets = np.zeros(n_values + 1, dtype=np.int64)
        np.cumsum(out_lens, out=offsets[1:])
        return A.VarBinaryArray(
            leaf_type.with_nullable(False), np.ones(n_values, bool), offsets, out_data
        )
    codec = get_fixed_codec(metas[0]["codec"])
    if isinstance(leaf_type, T.FixedSizeList):
        flat = codec.decode(Encoded(bufs[0], metas[0]), n_values * leaf_type.size)
        return A.FixedSizeListArray(
            leaf_type.with_nullable(False),
            np.ones(n_values, bool),
            np.asarray(flat).reshape(n_values, leaf_type.size),
        )
    vals = codec.decode(Encoded(bufs[0], metas[0]), n_values)
    return A.PrimitiveArray(
        leaf_type.with_nullable(False), np.ones(n_values, bool), np.asarray(vals)
    )


def _serialize_chunk(buffers: List[bytes]) -> bytes:
    """[u16 n_buffers][u16 size each][8-aligned buffer bytes ...] padded to 8."""
    for b in buffers:
        if len(b) > 0xFFFF:
            raise ValueError("buffer exceeds u16 size field")
    head = struct.pack("<H", len(buffers)) + b"".join(
        struct.pack("<H", len(b)) for b in buffers
    )
    out = pad_to(head)
    for b in buffers:
        out += pad_to(b)
    return pad_to(out)


def _parse_chunk(raw: np.ndarray) -> List[np.ndarray]:
    data = raw.tobytes()
    (nb,) = struct.unpack_from("<H", data, 0)
    sizes = struct.unpack_from(f"<{nb}H", data, 2)
    pos = (2 + 2 * nb + 7) & ~7
    bufs = []
    for s in sizes:
        bufs.append(raw[pos : pos + s])
        pos = (pos + s + 7) & ~7
    return bufs


def encode_miniblock(
    leaf: ShreddedLeaf,
    fixed_codec: Optional[str] = None,
    bytes_codec: str = "zstd_chunk",
) -> EncodedColumn:
    fixed_codec = fixed_codec or _default_fixed_codec(leaf.values)
    n_entries = leaf.n_entries

    # map each entry to its value slot (sparse values: def==0 entries only)
    valid_mask = (leaf.defs == 0) if leaf.defs is not None else np.ones(n_entries, bool)
    value_slot = np.cumsum(valid_mask) - 1

    # rows: entries that start a top-level row
    if leaf.max_rep > 0:
        row_start = leaf.rep == leaf.max_rep
    else:
        row_start = np.ones(n_entries, dtype=bool)

    chunks: List[bytes] = []
    chunk_meta: List[Dict] = []
    rep_index: List[tuple] = []  # (rows_started_before_chunk, first_entry_is_row_start)
    payload_offsets: List[int] = []
    pos = 0
    start = 0
    rows_before = 0
    while start < n_entries or (n_entries == 0 and not chunks):
        k = min(MAX_CHUNK_VALUES, n_entries - start) if n_entries else 0
        if k > 0:
            # round down to power of two unless it's the ragged tail
            if start + k < n_entries:
                k = 1 << (k.bit_length() - 1)
        while True:
            end = start + k
            e_rep = leaf.rep[start:end] if leaf.rep is not None else None
            e_def = leaf.defs[start:end] if leaf.defs is not None else None
            vm = valid_mask[start:end]
            vals = leaf.values.take(value_slot[start:end][vm])
            bufs: List[bytes] = []
            metas: List[Dict] = []
            if e_rep is not None:
                bufs.append(pack_levels(e_rep, leaf.max_rep).tobytes())
                metas.append({"stream": "rep"})
            if e_def is not None:
                bufs.append(pack_levels(e_def, leaf.max_def).tobytes())
                metas.append({"stream": "def"})
            encs = _encode_chunk_values(leaf.leaf_type, vals, fixed_codec, bytes_codec)
            for enc in encs:
                bufs.append(enc.data.tobytes())
                metas.append(enc.meta)
            try:
                blob = _serialize_chunk(bufs)
            except ValueError:
                blob = None
            if (
                blob is not None
                and (len(blob) <= TARGET_CHUNK_BYTES or k <= MIN_CHUNK_VALUES)
                and len(blob) // 8 <= MAX_CHUNK_WORDS
            ):
                break
            if k <= 1:
                raise ValueError("single value exceeds miniblock limits; "
                                 "use full-zip for large types")
            k = max(1, k // 2)
        n_vals = int(vm.sum())
        chunks.append(blob)
        chunk_meta.append(
            {
                "n_entries": k,
                "n_values": n_vals,
                "words": len(blob) // 8,
                "bufmeta": metas,
            }
        )
        rep_index.append((rows_before, bool(row_start[start]) if k else True))
        rows_before += int(row_start[start:end].sum())
        payload_offsets.append(pos)
        pos += len(blob)
        start = end
        if n_entries == 0:
            break

    payload = b"".join(chunks)
    has_rep = leaf.max_rep > 0
    per_chunk = CACHE_BYTES_PER_CHUNK_WITH_REP if has_rep else CACHE_BYTES_PER_CHUNK
    meta = {
        "encoding": "miniblock",
        "fixed_codec": fixed_codec,
        "bytes_codec": bytes_codec,
        "chunks": chunk_meta,
        "chunk_offsets": payload_offsets,
        "rep_index": rep_index,
        "n_rows": leaf.n_rows,
        "n_entries": n_entries,
    }
    return EncodedColumn(
        encoding="miniblock",
        payload=payload,
        meta=meta,
        search_cache_bytes=per_chunk * len(chunks),
    )


@dataclass
class ChunkPlan:
    """One mini-block leaf's chunks between their read and their decode.

    ``chunk_ids`` and their parsed buffers ``bufs``; ``kernel``: the
    decode kernel's per-chunk ``(bits, ref)``, None for a chunk the host
    decodes (``kernel`` itself is None when no chunk is on the kernel
    route); ``lookup``: a take's ``(rows_before, urows, inv)`` for
    :meth:`MiniBlockReader.select` (None for an empty take).
    :func:`decode_plans` fills ``decoded`` with each chunk's
    ``(rep, defs, values)``.
    """

    reader: "MiniBlockReader"
    chunk_ids: np.ndarray
    bufs: List[List[np.ndarray]]
    kernel: Optional[List[Optional[tuple]]]
    lookup: Optional[tuple] = None
    decoded: Optional[List[tuple]] = None


class MiniBlockReader(ColumnReader):
    """Mini-block random access + scan.

    ``take`` runs as a batched, decode-once pipeline in three steps, which
    a multi-column take (``DatasetReader.take_columns``) runs for many
    leaves at once: :meth:`plan_take` (one vectorized ``searchsorted`` maps
    all requested rows to chunk ranges, every needed chunk is fetched in a
    single phase-0 :meth:`~repro.store.ReadBatch.read_many` dispatch and
    parsed), :func:`decode_plans` (each chunk decoded exactly once) and
    :meth:`select` (row extraction as a single segment-id/gather
    permutation over the concatenated entry streams, fanned back out to
    request order with one :func:`~repro.core.encodings_base.reorder_leaf_rows`
    pass).

    ``decode`` selects the chunk decoder: ``"numpy"`` (host) or ``"pallas"``
    (the `repro.kernels` mini-block kernel; bit-packed and FoR byte-packed
    integer chunks are batch-decoded in one ``pallas_call`` per level class,
    other codecs fall back to numpy per chunk).
    """

    def __init__(self, meta: Dict, base: int, leaf_proto: ShreddedLeaf,
                 decode: str = "numpy"):
        super().__init__(meta, base, leaf_proto)
        if decode not in ("numpy", "pallas"):
            raise ValueError(f"decode must be 'numpy'|'pallas', got {decode!r}")
        self.decode = decode

    def _decode_chunk(self, ci: int, bufs: List[np.ndarray]):
        cm = self.meta["chunks"][ci]
        k = cm["n_entries"]
        bi = 0
        rep = defs = None
        if self.proto.max_rep > 0:
            rep = unpack_levels(bufs[bi], k, self.proto.max_rep)
            bi += 1
        if self.proto.max_def > 0:
            defs = unpack_levels(bufs[bi], k, self.proto.max_def)
            bi += 1
        vals = _decode_chunk_values(
            self.proto.leaf_type,
            bufs[bi:],
            cm["bufmeta"][bi:],
            cm["n_values"],
            self.meta["fixed_codec"],
            self.meta["bytes_codec"],
        )
        return rep, defs, vals

    # ------------------------------------------------------------------
    def _chunk_ranges_for_rows(self, urows: np.ndarray):
        """Vectorized §4.2.3 repetition-index lookup: sorted unique row ids ->
        per-row inclusive chunk ranges ``(c0, c1)``, one ``searchsorted``
        over all rows instead of one per row."""
        ri = self.meta["rep_index"]
        rows_before = np.array([r[0] for r in ri], dtype=np.int64)
        first_is_start = np.array([r[1] for r in ri], dtype=bool)
        n_chunks = len(ri)
        c0 = np.searchsorted(rows_before, urows, side="right") - 1
        # chunk where row r+1 starts; if that chunk *begins* with row r+1,
        # row r ends in the previous chunk
        c1 = np.searchsorted(rows_before, urows + 1, side="right") - 1
        back = (c1 > c0) & (rows_before[c1] == urows + 1) & first_is_start[c1]
        c1 = np.minimum(c1 - back, n_chunks - 1)
        return c0, c1, rows_before

    def take(self, rows: np.ndarray, io) -> ShreddedLeaf:
        plan = self.plan_take(rows, io)
        decode_plans([plan], getattr(io, "tracer", NULL_TRACER))
        return self.select(plan, io)

    def plan_take(self, rows: np.ndarray, io) -> ChunkPlan:
        """A take's read step: the chunks that hold ``rows``, read in one
        phase-0 dispatch and parsed, not yet decoded."""
        tracer = getattr(io, "tracer", NULL_TRACER)
        rows = np.asarray(rows, dtype=np.int64)
        with tracer.span(SPAN_TAKE):
            if len(rows) == 0:
                return ChunkPlan(self, np.zeros(0, np.int64), [], None)
            with tracer.span(SPAN_RANGES):
                urows, inv = np.unique(rows, return_inverse=True)
                if urows[0] < 0 or urows[-1] >= self.meta["n_rows"]:
                    raise IndexError(
                        f"take rows out of bounds for {self.meta['n_rows']}-"
                        "row column")
                c0, c1, rows_before = self._chunk_ranges_for_rows(urows)
                n_chunks = len(rows_before)
                # union of the [c0, c1] ranges via a coverage diff
                # (O(chunks + rows))
                cover = np.zeros(n_chunks + 1, dtype=np.int64)
                np.add.at(cover, c0, 1)
                np.add.at(cover, c1 + 1, -1)
                needed = np.nonzero(np.cumsum(cover[:-1]) > 0)[0]
                offs = np.asarray(self.meta["chunk_offsets"], dtype=np.int64)
                sizes = np.array(
                    [self.meta["chunks"][c]["words"] * 8 for c in needed],
                    dtype=np.int64)

            # IO: every needed chunk exactly once, one phase-0 batch dispatch
            data, doffs = io.read_many(self.base + offs[needed], sizes,
                                       phase=0)
            with tracer.span(SPAN_PARSE):
                bufs = [_parse_chunk(data[doffs[i]: doffs[i + 1]])
                        for i in range(len(needed))]
            return ChunkPlan(self, needed, bufs,
                             self._kernel_params(needed, tracer),
                             lookup=(rows_before, urows, inv))

    def select(self, plan: ChunkPlan, io) -> ShreddedLeaf:
        """A take's select step: the requested rows out of the plan's
        decoded chunks, in request order."""
        tracer = getattr(io, "tracer", NULL_TRACER)
        with tracer.span(SPAN_TAKE):
            if plan.lookup is None:
                return empty_leaf(self.proto)
            with tracer.span(SPAN_SELECT):
                rows_before, urows, inv = plan.lookup
                return self._select(rows_before, plan.chunk_ids, urows, inv,
                                    plan.decoded, io)

    def _select(self, rows_before, needed, urows, inv, decoded,
                io) -> ShreddedLeaf:
        """The requested rows' entries out of the decoded chunks, fanned out
        to request order."""
        lens = np.array([self.meta["chunks"][c]["n_entries"] for c in needed],
                        dtype=np.int64)
        reps = [d[0] for d in decoded]
        dfs = [d[1] for d in decoded]
        rep_all = np.concatenate(reps) if reps and reps[0] is not None else None
        def_all = np.concatenate(dfs) if dfs and dfs[0] is not None else None
        vals_all = A.concat([d[2] for d in decoded])
        total = int(lens.sum())

        # global row id per entry: per-chunk cumsum over row starts, offset by
        # the repetition index's rows-started-before counter (entries before a
        # chunk's first start continue row rows_before - 1)
        if self.proto.max_rep > 0:
            starts = rep_all == self.proto.max_rep
        else:
            starts = np.ones(total, dtype=bool)
        cs = np.cumsum(starts)
        chunk_off = np.zeros(len(needed) + 1, dtype=np.int64)
        np.cumsum(lens, out=chunk_off[1:])
        cs_pre = np.concatenate([[0], cs])[chunk_off[:-1]]
        row_id = cs - 1 - np.repeat(cs_pre, lens) + np.repeat(rows_before[needed], lens)

        # select the entries of all requested rows in one pass
        pos = np.searchsorted(urows, row_id)
        pos_c = np.minimum(pos, len(urows) - 1)
        sel = urows[pos_c] == row_id
        vmask = (def_all == 0) if def_all is not None else np.ones(total, bool)
        vslot = np.cumsum(vmask) - 1
        rep_sel = rep_all[sel] if rep_all is not None else None
        def_sel = def_all[sel] if def_all is not None else None
        val_sel = vals_all.take(vslot[sel & vmask])
        dec = leaf_slice(self.proto, rep_sel, def_sel, val_sel, len(urows))
        # useful bytes are counted over *unique* rows: duplicates are served
        # from the decoded result, not re-read, so amplification stays >= 1
        io.note_useful(value_bytes(dec.values))
        return reorder_leaf_rows(dec, inv)  # fan out to request order

    # ------------------------------------------------------------------
    _PALLAS_MAX_TILE_VALUES = 1 << 17  # VMEM cap on tile_entries * vpe

    @property
    def kernel_class(self) -> tuple:
        """The decode kernel's static shape class of this leaf:
        ``(rep_bits, def_bits, vpe)``.  Leaves of one class share a launch."""
        lt = self.proto.leaf_type
        return (level_bits(self.proto.max_rep), level_bits(self.proto.max_def),
                lt.size if isinstance(lt, T.FixedSizeList) else 1)

    def _pallas_eligible(self) -> bool:
        """Column-level kernel coverage: integer primitives and fixed-size
        lists of integers, with any (column-constant) rep/def level widths.
        Per-chunk value codecs are checked in :meth:`_chunk_kernel_params`.
        """
        return self._pallas_ineligible_reason() is None

    def _pallas_ineligible_reason(self) -> Optional[str]:
        """Column-level fallback reason (None = eligible).  The slugs are the
        stable vocabulary the ROADMAP's "close the fallback shapes" item
        tracks: ``variable-width-leaf`` (utf8/binary/list offsets),
        ``float-values``, ``non-integer-values``, ``tile-over-vmem``."""
        lt = self.proto.leaf_type
        if isinstance(lt, T.Primitive):
            vpe = 1
            kind = np.dtype(lt.dtype).kind
        elif isinstance(lt, T.FixedSizeList):
            vpe = lt.size
            kind = np.dtype(lt.child.dtype).kind
        else:
            return "variable-width-leaf"
        if kind == "f":
            return "float-values"
        if kind not in "iu":
            return "non-integer-values"
        if MAX_CHUNK_VALUES * vpe > self._PALLAS_MAX_TILE_VALUES:
            return "tile-over-vmem"
        return None

    @staticmethod
    def _chunk_kernel_params(bufmeta: Dict) -> Optional[tuple]:
        """Per-chunk value-codec eligibility: ``(bits, ref)`` when the
        kernel's int32 extract covers this chunk, else None.  ``bitpack`` is
        a dense bit stream (ref 0); ``bytepack`` is byte-aligned FoR whose
        reference must keep the int32 arithmetic exact."""
        codec = bufmeta.get("codec")
        if codec == "bitpack":
            return (bufmeta["bits"], 0) if bufmeta["bits"] <= 31 else None
        if codec == "bytepack":
            ref = bufmeta.get("ref")
            if ref is None:  # float payload stored as raw bytes
                return None
            bits = 8 * bufmeta["width"]
            if bits > 31:
                return None
            if ref < -(1 << 31) or ref + (1 << bits) - 1 > (1 << 31) - 1:
                return None
            return (bits, ref)
        return None

    @staticmethod
    def _chunk_fallback_reason(bufmeta: Dict) -> str:
        """Why :meth:`_chunk_kernel_params` rejected this chunk's value
        codec (only called when it did)."""
        codec = bufmeta.get("codec")
        if codec == "bitpack":
            return ">31-bit"
        if codec == "bytepack":
            if bufmeta.get("ref") is None:
                return "float-bytes"
            if 8 * bufmeta["width"] > 31:
                return ">31-bit"
            return "ref-overflow"
        return f"opaque-codec:{codec}"

    def _kernel_params(self, chunk_ids,
                       tracer=NULL_TRACER) -> Optional[List[Optional[tuple]]]:
        """Each chunk's kernel ``(bits, ref)`` under ``decode='pallas'``
        (None for a chunk the host decodes), or None when no chunk goes to
        the kernel.  ``tracer`` receives a structured fallback-reason event
        for every chunk that routes back to numpy.  Metadata only: no chunk
        bytes are looked at."""
        if self.decode != "pallas" or not len(chunk_ids):
            return None
        note = tracer.enabled
        col_reason = self._pallas_ineligible_reason()
        if col_reason is not None:
            if note:
                tracer.fallback("miniblock", col_reason,
                                n_chunks=len(chunk_ids))
            return None
        vbi = sum(1 for b in self.kernel_class[:2] if b)
        metas = [self.meta["chunks"][c]["bufmeta"][vbi] for c in chunk_ids]
        kp = [self._chunk_kernel_params(m) for m in metas]
        if note:
            reasons: Dict[str, int] = {}
            for m, p in zip(metas, kp):
                if p is None:
                    r = self._chunk_fallback_reason(m)
                    reasons[r] = reasons.get(r, 0) + 1
            for r in sorted(reasons):
                tracer.fallback("miniblock", r, n_chunks=reasons[r])
        return kp if any(p is not None for p in kp) else None

    def _from_kernel(self, rep_row, def_row, val_row, k: int) -> tuple:
        """One chunk's ``(rep, defs, values)`` out of its rows of the
        kernel's int32 outputs."""
        rep_bits, def_bits, vpe = self.kernel_class
        lt = self.proto.leaf_type
        fsl = isinstance(lt, T.FixedSizeList)
        dt = np.dtype(lt.child.dtype if fsl else lt.dtype)
        rep = rep_row[:k].astype(np.uint8) if rep_bits else None
        defs = def_row[:k].astype(np.uint8) if def_bits else None
        n_valid = int((defs == 0).sum()) if defs is not None else k
        dense = val_row[: n_valid * vpe].astype(dt)
        if fsl:
            vals = A.FixedSizeListArray(
                lt.with_nullable(False), np.ones(n_valid, bool),
                dense.reshape(n_valid, vpe))
        else:
            vals = A.PrimitiveArray(
                lt.with_nullable(False), np.ones(n_valid, bool), dense)
        return rep, defs, vals

    def scan(self, io, io_chunk: int = 8 << 20) -> ShreddedLeaf:
        offs = self.meta["chunk_offsets"]
        total = (offs[-1] + self.meta["chunks"][-1]["words"] * 8) if offs else 0
        raw_parts = []
        for p in range(0, total, io_chunk):
            raw_parts.append(io.read(self.base + p, min(io_chunk, total - p), phase=0))
        raw = np.concatenate(raw_parts) if raw_parts else np.zeros(0, np.uint8)
        n_chunks = len(offs)
        ids = np.arange(n_chunks)
        tracer = getattr(io, "tracer", NULL_TRACER)
        plan = ChunkPlan(self, ids, [
            _parse_chunk(raw[offs[ci]: offs[ci] + self.meta["chunks"][ci]["words"] * 8])
            for ci in ids], self._kernel_params(ids, tracer))
        decode_plans([plan], tracer)
        reps = [d[0] for d in plan.decoded]
        dfs = [d[1] for d in plan.decoded]
        vals = [d[2] for d in plan.decoded]
        rep = np.concatenate(reps) if reps and reps[0] is not None else None
        defs = np.concatenate(dfs) if dfs and dfs[0] is not None else None
        if vals:
            values = A.concat(vals)
        else:
            values = empty_values(self.proto.leaf_type)
        return leaf_slice(self.proto, rep, defs, values, self.meta["n_rows"])


def decode_plans(plans: List[ChunkPlan], tracer=NULL_TRACER) -> None:
    """Decode every chunk of ``plans`` exactly once, filling each plan's
    ``decoded``.

    The kernel's chunks of all plans whose leaves share a static class
    ``(rep_bits, def_bits, vpe)`` go to the device together, in one
    ``ops.miniblock_decode`` launch per class; the other chunks are decoded
    on the host.  One ``miniblock.decode_batch`` span holds it, and in it
    one ``miniblock.take`` span (the leaf readers' work).  An enabled
    ``tracer`` counts ``miniblock.decode_launches`` and
    ``miniblock.chunks_decoded`` and observes each launch's leaf count in
    the ``miniblock.leaves_per_launch`` histogram.
    """
    for p in plans:
        p.decoded = [None] * len(p.chunk_ids)
    plans = [p for p in plans if len(p.chunk_ids)]
    if not plans:
        return
    with tracer.span(SPAN_DECODE_BATCH, n_leaves=len(plans)), \
            tracer.span(SPAN_TAKE):
        classes: Dict[tuple, List[ChunkPlan]] = {}
        for p in plans:
            if p.kernel is not None:
                classes.setdefault(p.reader.kernel_class, []).append(p)
        for cls, members in classes.items():
            _decode_class(cls, members, tracer)
        rest = [(p, i) for p in plans for i, d in enumerate(p.decoded)
                if d is None]
        if rest:
            with tracer.span(SPAN_DECODE):
                for p, i in rest:
                    p.decoded[i] = p.reader._decode_chunk(p.chunk_ids[i],
                                                          p.bufs[i])
        if tracer.enabled:
            tracer.count("miniblock.chunks_decoded",
                         sum(len(p.chunk_ids) for p in plans))


def _decode_class(cls: tuple, plans: List[ChunkPlan], tracer) -> None:
    """One ``miniblock_decode`` launch over the kernel's chunks of
    ``plans``, all of level class ``cls``."""
    import jax  # lazy: keep numpy-only readers jax-free

    from ..kernels import ops
    from ..kernels.miniblock_decode import MIN_TILE

    rep_bits, def_bits, vpe = cls
    vbi = (1 if rep_bits else 0) + (1 if def_bits else 0)
    jobs = [(p, i) for p in plans for i, k in enumerate(p.kernel)
            if k is not None]
    sp = ops.MINIBLOCK_DECODE
    with tracer.span(sp.pack):
        entries = [p.reader.meta["chunks"][p.chunk_ids[i]]["n_entries"]
                   for p, i in jobs]
        # power-of-two tiles of >= 1024 entries: whole (8, 128) vregs,
        # and a handful of kernel shapes across batches
        tile = max(MIN_TILE, ops.pow2(max(entries)))
        # chunk count and stream widths round up to powers of two (padding
        # chunks decode to nothing), so batches share compiled shapes
        n_pad = ops.pow2(len(jobs))
        params = np.zeros((n_pad, 3), dtype=np.int32)
        streams = []  # (rep_words, def_words, val_words) ragged rows
        for j, (p, i) in enumerate(jobs):
            bufs = p.bufs[i]
            rw = ops.pack_words(bufs[0], pad_words=1) if rep_bits else None
            dw = (ops.pack_words(bufs[1 if rep_bits else 0], pad_words=1)
                  if def_bits else None)
            vw = ops.pack_words(bufs[vbi], pad_words=1)
            streams.append((rw, dw, vw))
            params[j] = (entries[j], *p.kernel[i])

        def stack(rows, active):
            if not active:
                return np.zeros((n_pad, 1), dtype=np.uint32)
            width = ops.pow2(max(len(r) for r in rows))
            out = np.zeros((n_pad, width), dtype=np.uint32)
            for j, r in enumerate(rows):
                out[j, : len(r)] = r
            return out

        stacked = [stack([s[0] for s in streams], rep_bits),
                   stack([s[1] for s in streams], def_bits),
                   stack([s[2] for s in streams], True)]
    outs = ops.miniblock_decode(
        *stacked, params, rep_bits=rep_bits, def_bits=def_bits, vpe=vpe,
        tile_entries=tile, tracer=tracer)
    with tracer.span(sp.wait):
        outs = jax.block_until_ready(outs)
    with tracer.span(sp.d2h):
        rep_np, def_np, vals_np = (np.asarray(a) for a in outs)
    if tracer.enabled:
        sp.count(tracer,
                 d2h=rep_np.nbytes + def_np.nbytes + vals_np.nbytes,
                 true=_decode_true_bytes(params, def_np, rep_bits,
                                         def_bits, vpe))
        tracer.count("miniblock.decode_launches")
        tracer.metrics.histogram("miniblock.leaves_per_launch").observe(
            len(plans))
    with tracer.span(sp.unpack):
        for j, (p, i) in enumerate(jobs):
            p.decoded[i] = p.reader._from_kernel(rep_np[j], def_np[j],
                                                 vals_np[j], entries[j])


def _decode_true_bytes(params: np.ndarray, defs: np.ndarray, rep_bits: int,
                       def_bits: int, vpe: int) -> int:
    """The true bytes of one decode call: each chunk's encoded streams at
    their packed widths plus the int32 levels and values decoded (padding
    chunks have no entries and count nothing)."""
    n, vbits = params[:, 0].astype(np.int64), params[:, 1].astype(np.int64)
    if def_bits:
        live = np.arange(defs.shape[1])[None, :] < n[:, None]
        nv = vpe * ((defs == 0) & live).sum(1)
    else:
        nv = vpe * n
    enc = (-(-n * rep_bits // 8) - (-n * def_bits // 8)
           - (-nv * vbits // 8)).sum()
    dec = 4 * (n * (rep_bits > 0) + n * (def_bits > 0) + nv).sum()
    return int(enc + dec)


# retained as the historical entry points; the implementations are the shared
# helpers in encodings_base
_reorder_rows = reorder_leaf_rows
_empty_values = empty_values
