"""Content addressing: milliseconds per commit in ``hash.sha256``, the
object key and the content fingerprint."""

from bench import span_tree


def read(w):
    return span_tree.per_commit_ms(w.spans, "hash.sha256")
