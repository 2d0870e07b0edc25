"""transfer_ms.take: host milliseconds per request inside the program's
host-device transfer spans: ``kernel.h2d:<kernel>`` (the kernels' inputs
put on the device) and ``kernel.d2h:<kernel>`` (their outputs copied back,
after an explicit wait for the device in ``kernel.wait:<kernel>``)."""


def read(view):
    if not view.matching(["kernel.h2d:", "kernel.d2h:"]):
        return None  # spans the program does not open
    return view.per_request_ms(view.span_s(["kernel.h2d:", "kernel.d2h:"]))
