"""store_read_ms.take: host milliseconds per request inside the program's
``store.read`` spans (``ReadBatch.read``/``read_many``): recording the
logical reads and copying their bytes out of the disk image.  Host work of
the simulated store, not modelled IO."""


def read(view):
    if not view.matching(["store.read"]):
        return None  # spans the program does not open
    return view.per_request_ms(view.span_s(["store.read"]))
