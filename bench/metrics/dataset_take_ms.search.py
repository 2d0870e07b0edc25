"""dataset_take_ms.search: host milliseconds per request inside the
program's ``dataset.take:<column>`` spans (``DatasetReader.take``,
everything below it included)."""


def read(view):
    return view.per_request_ms(view.span_s(["dataset.take:"]))
