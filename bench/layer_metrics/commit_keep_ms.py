"""Store commit: milliseconds per commit in ``store.keep``, the copy of the
committed tree into the materialization cache for the next save's parent."""

from bench import span_tree


def read(w):
    return span_tree.per_commit_ms(w.spans, "store.keep")
