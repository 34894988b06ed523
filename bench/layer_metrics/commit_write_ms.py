"""Object store and metadata: milliseconds per commit in ``objects.write``
(the object's file written and renamed) and ``store.save_meta`` (the
metadata packed and rewritten)."""

from bench import span_tree


def read(w):
    return span_tree.per_commit_ms(w.spans, "objects.write", "store.save_meta")
