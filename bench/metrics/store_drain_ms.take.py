"""store_drain_ms.take: host milliseconds per request inside the program's
``drain:*`` spans (``IOScheduler`` batch close: coalescing and the
bookkeeping of the simulated store; no modelled IO time)."""


def read(view):
    return view.per_request_ms(view.span_s(["drain:"]))
