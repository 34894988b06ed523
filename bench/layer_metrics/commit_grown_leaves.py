"""Delta codec (diff): leaves diffed on the device after growing since the
parent (``grown_leaves`` of the ``delta.encode_delta`` span under
``store.commit``, per commit)."""

from bench import span_tree


def read(w):
    commits, below = span_tree.commit_tree(w.spans)
    v = [s.attrs["grown_leaves"] for s in below
         if s.name == "delta.encode_delta" and "grown_leaves" in s.attrs]
    return sum(v) / len(commits) if v else None
