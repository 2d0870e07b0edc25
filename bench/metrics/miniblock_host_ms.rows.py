"""miniblock_host_ms.rows: host milliseconds per request inside the
mini-block reader's host steps: ``miniblock.ranges`` (repetition-index
lookup and chunk cover), ``miniblock.parse`` (chunk buffers),
``miniblock.select`` (row extraction and request order),
``miniblock.decode`` (host decode of chunks the kernel does not take) and
the decode kernel's host packing and unpacking
(``kernel.pack:miniblock_decode``, ``kernel.unpack:miniblock_decode``)."""

from harness.trace import union_length

SPANS = {"miniblock.ranges", "miniblock.parse", "miniblock.select",
         "miniblock.decode", "kernel.pack:miniblock_decode",
         "kernel.unpack:miniblock_decode"}


def read(view):
    if not view.matching(["miniblock.ranges"]):
        return None  # spans the program does not open
    # names matched whole: miniblock.decode_batch holds the kernel's
    # transfers and the wait for the device
    return view.per_request_ms(union_length(
        [(a, b) for n, a, b in view.spans if n in SPANS]))
