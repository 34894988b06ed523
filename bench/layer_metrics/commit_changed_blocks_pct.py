"""Delta codec (diff): percent of the blocks diffed that changed,
100 x the sum of ``changed_blocks`` over the sum of ``total_blocks`` (blocks
diffed, padding excluded) of the ``delta.encode_delta`` spans under
``store.commit``: the share of the table a save rewrites."""

from bench import span_tree


def read(w):
    _, below = span_tree.commit_tree(w.spans)
    diffs = [s.attrs for s in below
             if s.name == "delta.encode_delta" and "total_blocks" in s.attrs]
    total = sum(a["total_blocks"] for a in diffs)
    return 100.0 * sum(a["changed_blocks"] for a in diffs) / total if total else None
