"""Object store (zstd): percent of the bytes compressed under a commit that
went through zstd's workers, 100 x the sum of ``in_bytes`` of the
``objects.compress`` spans under ``store.commit`` with ``workers`` above 0,
over the sum of ``in_bytes`` of them all."""

from bench import span_tree


def read(w):
    _, below = span_tree.commit_tree(w.spans)
    runs = [s.attrs for s in below
            if s.name == "objects.compress" and "in_bytes" in s.attrs]
    total = sum(a["in_bytes"] for a in runs)
    mt = sum(a["in_bytes"] for a in runs if a["workers"] > 0)
    return 100.0 * mt / total if total else None
