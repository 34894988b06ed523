"""Host-device: megabytes uploaded per commit (``h2d_bytes`` of the spans
under ``store.commit``: both trees' leaves in 4 KiB blocks for the diff,
and any chain applied to decode the parent)."""

from bench import span_tree


def read(w):
    v = span_tree.per_commit_attr(w.spans, "h2d_bytes")
    return v / 1e6 if v is not None else None
