"""dataset_self_ms.take: host milliseconds per request inside the program's
``dataset.take:<column>`` spans (``DatasetReader.take``) that no leaf
reader's span (``fullzip.take``, ``miniblock.take``) and no ``drain:*``
span covers: fragment routing (``dataset.locate``), stitching, request
order and unshredding (``dataset.assemble``)."""


def read(view):
    if not view.matching(["fullzip.take", "miniblock.take"]):
        return None  # spans the program does not open
    return view.per_request_ms(view.self_s(
        ["dataset.take:"], ["fullzip.take", "miniblock.take", "drain:"]))
