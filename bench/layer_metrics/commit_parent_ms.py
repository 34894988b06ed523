"""Materializer (parent decode): milliseconds per commit in ``store.parent``,
the checkout of the parent the save is diffed against (read, decompress,
decode, on a cache miss)."""

from bench import span_tree


def read(w):
    return span_tree.per_commit_ms(w.spans, "store.parent")
