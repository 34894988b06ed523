"""Per-commit sums over the program's ``repro.obs`` spans in a traced window.

The spans of one window form trees by ``parent_id``.  "Per commit" is the
sum over the spans that descend from a ``store.commit`` span, divided by
the number of ``store.commit`` spans.  Each function returns None where
the program records none of the spans or counters it reads, as a program
from before them does.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from bench import trace

COMMIT = "store.commit"
#: the spans the commit path opens directly under ``store.commit``
COMMIT_CHILDREN = frozenset({
    "delta.encode_full", "store.parent", "delta.encode_delta", "hash.sha256",
    "objects.compress", "objects.write", "store.save_meta"})


def commit_tree(spans: Sequence) -> Tuple[list, list]:
    """The ``store.commit`` spans, and every span that descends from one."""
    by_id = {s.span_id: s for s in spans}
    root_of = {}  # span id -> id of its store.commit, or None

    def root(s) -> Optional[int]:
        chain = []
        while s is not None and s.span_id not in root_of:
            if s.name == COMMIT:
                root_of[s.span_id] = s.span_id
                break
            chain.append(s.span_id)
            s = by_id.get(s.parent_id)
        found = None if s is None else root_of[s.span_id]
        for sid in chain:
            root_of[sid] = found
        return found

    commits = [s for s in spans if s.name == COMMIT]
    below = [s for s in spans if s.name != COMMIT and root(s) is not None]
    return commits, below


def per_commit_ms(spans: Sequence, *names: str) -> Optional[float]:
    """Milliseconds per commit in the spans named ``names`` under it."""
    commits, below = commit_tree(spans)
    d = [s.duration for s in below if s.name in names]
    return sum(d) / len(commits) * 1e3 if d else None


def per_commit_attr(spans: Sequence, attr: str) -> Optional[float]:
    """The counter ``attr`` summed over the spans under a commit, per
    commit."""
    commits, below = commit_tree(spans)
    v = [s.attrs[attr] for s in below if attr in s.attrs]
    return sum(v) / len(commits) if v else None


def untraced_pct(spans: Sequence) -> Optional[float]:
    """Percent of the commits' time that none of their direct children
    covers (children may overlap: their union counts once)."""
    commits = [s for s in spans if s.name == COMMIT]
    children: dict = {c.span_id: [] for c in commits}
    named = False
    for s in spans:
        if s.parent_id in children:
            children[s.parent_id].append((s.t0, s.t1))
            named = named or s.name in COMMIT_CHILDREN
    total = sum(c.duration for c in commits)
    if not named or total <= 0:
        return None
    covered = 0.0
    for c in commits:
        inside: List[Tuple[float, float]] = trace.clip(children[c.span_id], c.t0, c.t1)
        covered += sum(e - s for s, e in trace.union(inside))
    return 100.0 * (total - covered) / total
