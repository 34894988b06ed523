"""Materializer (parent decode): percent of commits whose parent came from
the materialization cache (``cache_hits`` on the ``mat.checkout_many`` span
under ``store.parent``, per commit), not decoded from disk."""

from bench import span_tree


def read(w):
    v = span_tree.per_commit_attr(w.spans, "cache_hits")
    return 100.0 * v if v is not None else None
