"""Plain reference for ``take`` on a table: the requested rows indexed
straight out of the generated columns.  It uses neither the reader nor the
kernels, and imports nothing of the program.

``compare`` counts the rows of a request whose validity, value, or list
items differ from the reference; a null slot's content is not compared
(Arrow leaves it undefined).  ``answer`` is the reference in the program's
place, and with ``lower=True`` its control: every column stored in the
nearest lower type of its ladder that changes this column's data
(float32 -> bfloat16 -> float8_e4m3fn, int64 -> int32 -> int16,
int32 -> int16 -> int8); a step that changes nothing is the same answer,
not a lower precision.
"""

from __future__ import annotations

import numpy as np

LADDER = {"float32": ("bfloat16", "float8_e4m3fn"),
          "int64": ("int32", "int16"), "int32": ("int16", "int8")}


def ragged(starts: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    """Indices ``starts[i] + 0 .. lengths[i]-1``, concatenated."""
    lengths = np.asarray(lengths, np.int64)
    if lengths.sum() == 0:
        return np.zeros(0, np.int64)
    first = np.repeat(np.asarray(starts, np.int64) - np.cumsum(
        np.concatenate([[0], lengths[:-1]])), lengths)
    return first + np.arange(int(lengths.sum()), dtype=np.int64)


def lossy(values: np.ndarray) -> np.ndarray:
    """``values`` round-tripped through the first lower type of its ladder
    that changes them (unchanged if none does)."""
    import ml_dtypes  # noqa: F401  (registers bfloat16 and float8 dtypes)

    for t in LADDER.get(values.dtype.name, ()):
        dt = np.dtype(getattr(ml_dtypes, t, None) or t)
        back = values.astype(dt).astype(values.dtype)
        if not np.array_equal(back, values):
            return back
    return values


def answer(data: dict, req: dict, lower: bool = False) -> dict:
    rows = req["rows"]
    out = {}
    for name in req["columns"]:
        c = data["columns"][name]
        if c["kind"] == "list":
            off = c["offsets"]
            lens = off[rows + 1] - off[rows]
            new = np.zeros(len(rows) + 1, np.int64)
            np.cumsum(lens, out=new[1:])
            items = c["items"][ragged(off[rows], lens)]
            out[name] = {"validity": c["validity"][rows], "offsets": new,
                         "items": lossy(items) if lower else items}
        else:
            vals = c["values"][rows]
            valid = (c["validity"][rows] if "validity" in c
                     else np.ones(len(rows), bool))
            out[name] = {"validity": valid,
                         "values": lossy(vals) if lower else vals}
    return out


def compare(data: dict, req: dict, got: dict) -> dict:
    """``{"mismatched_rows": n, "rows_checked": m}`` for one request."""
    want = answer(data, req)
    bad = checked = 0
    for name, w in want.items():
        g = got.get(name)
        n = len(w["validity"])
        checked += n
        if g is None or len(g["validity"]) != n:
            bad += n
            continue
        wrong = g["validity"] != w["validity"]
        valid = w["validity"]
        if "offsets" in w:
            wl, gl = np.diff(w["offsets"]), np.diff(g["offsets"])
            wrong |= valid & (wl != gl)
            same = np.flatnonzero(valid & (wl == gl))
            wi = ragged(w["offsets"][same], wl[same])
            gi = ragged(g["offsets"][same], wl[same])
            if len(gi) and gi.max() >= len(g["items"]):
                wrong[same] = True
            else:
                diff = w["items"][wi] != g["items"][gi]
                owner = np.repeat(same, wl[same])
                wrong[owner[diff]] = True
        else:
            gv, wv = g["values"], w["values"]
            if gv.shape != wv.shape:
                wrong |= True
            else:
                d = gv != wv
                wrong |= valid & (d.reshape(n, -1).any(1))
        bad += int(wrong.sum())
    return {"mismatched_rows": bad, "rows_checked": checked}


def check(data: dict, reqs: list, got: list) -> dict:
    """The numbers a run compares, over all its requests."""
    per_request = [compare(data, r, g) for r, g in zip(reqs, got)]
    return {"mismatched_rows": sum(r["mismatched_rows"] for r in per_request),
            "rows_checked": sum(r["rows_checked"] for r in per_request)}


def control(data: dict, reqs: list) -> list:
    """The reference in the program's place at the lower types of
    :func:`lossy`."""
    return [answer(data, r, lower=True) for r in reqs]
