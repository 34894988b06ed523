"""Delta codec (wire): milliseconds per commit in ``delta.encode_full``, the
``tobytes`` copies and msgpack pack of the save's full payload."""

from bench import span_tree


def read(w):
    return span_tree.per_commit_ms(w.spans, "delta.encode_full")
