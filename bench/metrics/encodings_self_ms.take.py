"""encodings_self_ms.take: host milliseconds per request inside the spans the
harness opens around the structural encodings' take
(``MiniBlockReader.take``, ``FullZipReader.take``) that no kernel-dispatch
span covers: chunk reads and parsing, stream packing, row windows and host
decode."""

WRAP = {
    "encodings:MiniBlockReader.take":
        "repro.core.miniblock:MiniBlockReader.take",
    "encodings:FullZipReader.take": "repro.core.fullzip:FullZipReader.take",
    "ops.ivf_topk": "repro.kernels.ops:ivf_topk",
    "ops.miniblock_decode": "repro.kernels.ops:miniblock_decode",
    "ops.fullzip_gather": "repro.kernels.ops:fullzip_gather",
    "ops.bitunpack": "repro.kernels.ops:bitunpack",
}


def read(view):
    return view.per_request_ms(view.self_s(["encodings:"], ["ops."]))
