"""dataset_take_ms.rows: host milliseconds per request inside the program's
``dataset.take_columns`` spans (``DatasetReader.take_columns``, everything
below it included: routing, the reads, the grouped mini-block decode, the
drain and the assembly of every column)."""


def read(view):
    if not view.matching(["dataset.take_columns"]):
        return None  # spans the program does not open
    return view.per_request_ms(view.span_s(["dataset.take_columns"]))
