"""ivf_topk_roofline: the least time the chip needs for the distance and
top-k work of the window's ``ivf_topk`` calls (the larger of operations over
peak FLOP/s and bytes over peak HBM bandwidth, per call), over the summed
device time of the kernel's jitted program (``EVENTS``: executions on the
trace's ``XLA Modules`` line; the padding inside it included), in percent.

Work is counted from the call's true shapes, never the padded tiles: the
probe is Q x P x D and the scoring Q x N x D (N candidates), 2 operations a
multiply-add; bytes are float32 queries and candidates at the true D, the
candidate ids, and the (Q, k) distances and ids returned; the mask is not
counted.  The operation peak is the bf16 one, which only lowers the share.
"""

import numpy as np

WRAP = {"ops.ivf_topk": "repro.kernels.ops:ivf_topk"}
WORK_OF = "repro.kernels.ops:ivf_topk"
EVENTS = ("jit_ivf_topk_pallas",)


def work(args, kwargs, out):
    q = np.atleast_2d(np.asarray(args[0]))
    c = np.atleast_2d(np.asarray(args[1]))
    k = int(args[3] if len(args) > 3 else kwargs["k"])
    nq, d = q.shape
    n = c.shape[0]
    return 2.0 * nq * n * d, 4.0 * (nq * d + n * d + n) + 8.0 * nq * k


def read(view):
    return view.roofline_pct("ivf_topk_roofline", EVENTS)
