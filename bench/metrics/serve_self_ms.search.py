"""serve_self_ms.search: host milliseconds per query inside the program's
``search`` span (``Retriever.search``) that no ``dataset.take:*`` span and no
kernel-dispatch span covers: the probe set-up, the dense mask, and the
assembly of results."""

WRAP = {"ops.ivf_topk": "repro.kernels.ops:ivf_topk",
        "ops.miniblock_decode": "repro.kernels.ops:miniblock_decode",
        "ops.fullzip_gather": "repro.kernels.ops:fullzip_gather",
        "ops.bitunpack": "repro.kernels.ops:bitunpack"}


def read(view):
    return view.per_request_ms(
        view.self_s(["search"], ["dataset.take:", "ops."]))
