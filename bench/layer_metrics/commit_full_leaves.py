"""Delta codec (diff): leaves a commit's delta stores whole, new or
reshaped since the parent (``full_leaves`` of the ``delta.encode_delta``
span under ``store.commit``, per commit)."""

from bench import span_tree


def read(w):
    commits, below = span_tree.commit_tree(w.spans)
    v = [s.attrs["full_leaves"] for s in below
         if s.name == "delta.encode_delta" and "full_leaves" in s.attrs]
    return sum(v) / len(commits) if v else None
