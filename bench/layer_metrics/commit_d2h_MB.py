"""Host-device: megabytes downloaded per commit (``d2h_bytes`` of the spans
under ``store.commit``: each leaf's changed count, ``idx[:n]`` and
``blocks[:n]``, and any chain applied to decode the parent)."""

from bench import span_tree


def read(w):
    v = span_tree.per_commit_attr(w.spans, "d2h_bytes")
    return v / 1e6 if v is not None else None
