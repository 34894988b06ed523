"""Object store (zstd): milliseconds per commit in ``objects.compress``."""

from bench import span_tree


def read(w):
    return span_tree.per_commit_ms(w.spans, "objects.compress")
