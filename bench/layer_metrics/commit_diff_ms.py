"""Delta codec (diff): milliseconds per commit in ``delta.encode_delta``:
both trees uploaded, ``changed_block_mask`` and its count, ``_compact`` and
the downloads, the delta's msgpack pack."""

from bench import span_tree


def read(w):
    return span_tree.per_commit_ms(w.spans, "delta.encode_delta")
