"""miniblock_decode_roofline: the least time the chip needs for the window's
``miniblock_decode`` calls (bytes over peak HBM bandwidth), over the summed
device time of the kernel's jitted program (``EVENTS``: executions on the
trace's ``XLA Modules`` line, with the row-window gathers of the streams), in
percent.

Bytes are the encoded streams of the chunks decoded (each chunk's
repetition and definition levels and its values at their packed bit widths,
without padding) plus what decoding produces: the int32 levels and values.
A chunk's value count is its entries whose definition level is 0 (all of
them without a definition stream), times the values per entry.
"""

import numpy as np

WRAP = {"ops.miniblock_decode": "repro.kernels.ops:miniblock_decode"}
WORK_OF = "repro.kernels.ops:miniblock_decode"
EVENTS = ("jit_miniblock_decode_pallas",)


def work(args, kwargs, out):
    params = np.asarray(args[3] if len(args) > 3 else kwargs["params"])
    rb, db = int(kwargs["rep_bits"]), int(kwargs["def_bits"])
    vpe = int(kwargs.get("vpe", 1))
    n, vbits = params[:, 0].astype(np.int64), params[:, 1].astype(np.int64)
    if db:
        defs = np.asarray(out[1])
        live = np.arange(defs.shape[1])[None, :] < n[:, None]
        nv = vpe * ((defs == 0) & live).sum(1)
    else:
        nv = vpe * n
    enc = (-(-n * rb // 8) - (-n * db // 8) - (-nv * vbits // 8)).sum()
    dec = 4 * (n * (rb > 0) + n * (db > 0) + nv).sum()
    return 0.0, float(enc + dec)


def read(view):
    return view.roofline_pct("miniblock_decode_roofline", EVENTS)
