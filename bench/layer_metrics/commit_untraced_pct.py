"""Store commit: percent of ``store.commit`` time that no direct child span
covers -- what the commit's layer metrics leave unexplained."""

from bench import span_tree


def read(w):
    return span_tree.untraced_pct(w.spans)
