"""Tradeoff monitor: milliseconds per commit in the ``tradeoff.sample``
spans taken after a commit (``event`` "commit"), the O(n) walk over the
version graph that runs inside the save but outside ``store.commit``."""


def read(w):
    commits = sum(s.name == "store.commit" for s in w.spans)
    d = [s.duration for s in w.spans
         if s.name == "tradeoff.sample" and s.attrs.get("event") == "commit"]
    return sum(d) / commits * 1e3 if d and commits else None
