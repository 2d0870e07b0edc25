"""dispatch_ms.search: host milliseconds per request inside the spans the
harness opens around the ``repro.kernels.ops`` entry points (padding,
packing, host-device transfers and the wait for the device)."""

WRAP = {"ops.ivf_topk": "repro.kernels.ops:ivf_topk",
        "ops.miniblock_decode": "repro.kernels.ops:miniblock_decode",
        "ops.fullzip_gather": "repro.kernels.ops:fullzip_gather",
        "ops.bitunpack": "repro.kernels.ops:bitunpack"}


def read(view):
    return view.per_request_ms(view.span_s(["ops."]))
