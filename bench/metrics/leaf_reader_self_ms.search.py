"""leaf_reader_self_ms.search: host milliseconds per query inside the
program's leaf reader spans (``fullzip.take``, ``miniblock.take``) that no
``store.read`` span and no kernel dispatch span (``kernel.*``) covers:
deduplication, repetition-index lookups, chunk parsing, entry decode and the
codec (``fullzip.unzip``, which counts here), row selection and fan-out."""


def read(view):
    if not view.matching(["fullzip.take", "miniblock.take"]):
        return None  # spans the program does not open
    return view.per_request_ms(view.self_s(
        ["fullzip.take", "miniblock.take"], ["store.read", "kernel."]))
