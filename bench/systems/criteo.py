"""Criteo 1TB rows with the DLRM-DCNv2 reference's multi-hot features,
served as shuffled training batches (configurations with
``"system": "criteo"``).

The table is made in plain numpy from the configuration's ``data_seed``
(``generate``): a 0/1 label, nullable int64 counts and nullable
``List<int32>`` multi-hot features, a valid list holding its feature's
``size`` items uniform below its ``range`` and a null list none.  The
column specs come from the configuration's published lists
(``columns``).  It is
written, ingested and served like a ``table`` system's (``build``,
``plain``), and each request is one ``DatasetReader.take_columns`` of every
column at the request's rows (``serve``).
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

from harness.cell import load_module
from harness.gen import rng, store

DATA_STREAM = 1
_table = load_module(Path(__file__).with_name("table.py"))
plain = _table.plain


def columns(cfg: dict) -> dict:
    """``{name: spec}`` of the 40 columns, in table order, out of the
    configuration's lists: ``label``, ``I1``-``I13`` and ``C1``-``C26``."""
    out = {"label": {"kind": "label",
                     "positive_share": cfg["label_positive_share"]}}
    for i, share in enumerate(cfg["dense_null_shares"], 1):
        out[f"I{i}"] = {"kind": "int", "null_share": share,
                        "below": cfg["dense_below"]}
    for i, (size, top, share) in enumerate(zip(
            cfg["multi_hot_sizes"], cfg["num_embeddings_per_feature"],
            cfg["multi_hot_null_shares"]), 1):
        out[f"C{i}"] = {"kind": "multi_hot", "size": size, "range": top,
                        "null_share": share}
    return out


def generate(cfg: dict, mix: dict, seed: int) -> dict:
    """Columns as plain numpy, in the ``table`` system's form; the data
    comes from the configuration's ``data_seed``, not from ``seed``."""
    n = int(cfg["rows"])
    gen = rng(int(cfg["data_seed"]), DATA_STREAM)
    cols = {}
    for name, spec in columns(cfg).items():
        kind = spec["kind"]
        if kind == "label":
            values = (gen.random(n) < spec["positive_share"]).astype(np.int64)
            cols[name] = {"kind": "int", "validity": np.ones(n, bool),
                          "values": values}
        elif kind == "int":
            valid = gen.random(n) >= spec["null_share"]
            counts = np.floor(gen.lognormal(1.0, 2.0, n))
            values = np.minimum(counts, spec["below"] - 1).astype(np.int64)
            cols[name] = {"kind": "int", "validity": valid,
                          "values": np.where(valid, values, 0)}
        elif kind == "multi_hot":
            valid = gen.random(n) >= spec["null_share"]
            offsets = np.zeros(n + 1, np.int64)
            np.cumsum(np.where(valid, int(spec["size"]), 0), out=offsets[1:])
            items = gen.integers(0, int(spec["range"]), int(offsets[-1]),
                                 dtype=np.int32)
            cols[name] = {"kind": "list", "validity": valid,
                          "offsets": offsets, "items": items}
        else:
            raise ValueError(f"unknown column kind {kind!r}")
    return {"n_rows": n, "columns": cols}


def program_table(data: dict) -> dict:
    """The generated columns as the program's arrays: the label not
    nullable, the rest nullable."""
    from repro.core import arrays as A, types as T

    out = _table.program_table(data)
    values = data["columns"]["label"]["values"]
    out["label"] = A.PrimitiveArray(T.Primitive("int64", False),
                                    np.ones(len(values), bool), values)
    return out


def build(cfg: dict, data: dict, seed: int, tracer=None):
    """Write, ingest and open the table; returns the reader requests use."""
    from repro.core.file import WriteOptions
    from repro.dataset import DatasetReader, DatasetWriter, write_fragments

    if not hasattr(DatasetReader, "take_columns"):
        # a program without the row take cannot serve this system: fail
        # before the minutes of writing, not after them
        raise RuntimeError("the program has no DatasetReader.take_columns")
    files = write_fragments(program_table(data), int(cfg["fragments"]),
                            WriteOptions(cfg["encoding"]))
    writer = DatasetWriter(files=files, store=store(cfg), tracer=tracer)
    return writer.reader(decode=cfg["decode"])


def serve(reader, req: dict) -> tuple:
    """One ``take_columns`` of every column; returns ``(result, rows)``."""
    rows = req["rows"]
    return reader.take_columns(req["columns"], rows), len(rows)
