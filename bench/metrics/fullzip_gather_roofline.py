"""fullzip_gather_roofline: the least time the chip needs to move the rows of
the window's ``fullzip_gather`` calls (bytes over peak HBM bandwidth), over
the summed device time of the kernel's jitted program (``EVENTS``: executions
on the trace's ``XLA Modules`` line, with the reshape into 128-word tiles and
the copy out), in percent.

Each row taken counts its written bytes once read and once written (516 B
each for a 128 x float32 row with its control word); the 1024 B padded tile
the kernel moves does not count.
"""

import numpy as np

WRAP = {"ops.fullzip_gather": "repro.kernels.ops:fullzip_gather"}
WORK_OF = "repro.kernels.ops:fullzip_gather"
EVENTS = ("jit_fullzip_gather_pallas",)


def work(args, kwargs, out):
    zipped = np.asarray(args[0])
    rows = np.asarray(args[1] if len(args) > 1 else kwargs["rows"])
    row_bytes = zipped.nbytes // max(1, len(zipped))
    return 0.0, 2.0 * len(rows) * row_bytes


def read(view):
    return view.roofline_pct("fullzip_gather_roofline", EVENTS)
