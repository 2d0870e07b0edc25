"""A Lance table served by random ``take`` (configurations with
``"system": "table"``).

The data is made from the seed in plain numpy (``generate``), written as
fragments with the program's writer and ingested onto its tiered store
(``build``), and each request is one ``DatasetReader.take`` per column on
the configuration's decode route (``serve``).
"""

from __future__ import annotations

import numpy as np

from harness.gen import (nullable_ints, nullable_lists, rng, sift_like,
                         store)

DATA_STREAM = 1


def generate(cfg: dict, mix: dict, seed: int) -> dict:
    """Columns as plain numpy: ``{"n_rows", "columns": {name: {...}}}``."""
    n = int(cfg["rows"])
    gen = rng(seed, DATA_STREAM)
    cols = {}
    for name, spec in cfg["columns"].items():
        kind = spec["kind"]
        if kind == "sift_like":
            vecs, _ = sift_like(n, gen, int(spec["modes"]))
            cols[name] = {"kind": kind, "values": vecs}
        elif kind == "int":
            valid, values = nullable_ints(n, gen, spec["null_share"],
                                          *spec["range"], spec["dtype"])
            cols[name] = {"kind": kind, "validity": valid, "values": values}
        elif kind == "list":
            valid, offsets, items = nullable_lists(
                n, gen, spec["null_share"], *spec["length"], *spec["range"],
                spec["dtype"])
            cols[name] = {"kind": kind, "validity": valid,
                          "offsets": offsets, "items": items}
        else:
            raise ValueError(f"unknown column kind {kind!r}")
    return {"n_rows": n, "columns": cols}


def program_table(data: dict) -> dict:
    """The generated columns as the program's arrays."""
    from repro.core import arrays as A, types as T

    out = {}
    for name, c in data["columns"].items():
        if c["kind"] == "sift_like":
            out[name] = A.FixedSizeListArray.build(c["values"])
        elif c["kind"] == "int":
            out[name] = A.PrimitiveArray(T.Primitive(c["values"].dtype.name),
                                         c["validity"], c["values"])
        else:
            items = A.PrimitiveArray.build(c["items"], nullable=False)
            out[name] = A.ListArray.build(items, c["offsets"],
                                          validity=c["validity"])
    return out


def build(cfg: dict, data: dict, seed: int, tracer=None):
    """Write, ingest and open the table; returns the reader requests use."""
    from repro.core.file import WriteOptions
    from repro.dataset import DatasetWriter, write_fragments

    files = write_fragments(program_table(data), int(cfg["fragments"]),
                            WriteOptions(cfg["encoding"]))
    writer = DatasetWriter(files=files, store=store(cfg), tracer=tracer)
    return writer.reader(decode=cfg["decode"])


def serve(reader, req: dict) -> tuple:
    """One take per column; returns ``(result, rows returned)``."""
    rows = req["rows"]
    return {c: reader.take(c, rows) for c in req["columns"]}, len(rows)


def plain(result: dict) -> dict:
    """A request's result as the plain fields the reference compares."""
    out = {}
    for col, arr in result.items():
        c = {"validity": np.asarray(arr.validity, bool)}
        if hasattr(arr, "offsets"):
            c["offsets"] = np.asarray(arr.offsets, np.int64)
            c["items"] = np.asarray(arr.child.values)
        else:
            c["values"] = np.asarray(arr.values)
        out[col] = c
    return out
