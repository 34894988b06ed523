"""The readers of the parent-cache spans and counters, on windows of
hand-made spans: ``commit_parent_hit_pct`` from ``cache_hits`` on the
parent's ``mat.checkout_many``, ``commit_keep_ms`` from ``store.keep``."""

from __future__ import annotations

from types import SimpleNamespace

import pytest

from bench import harness


class Spans:
    def __init__(self):
        self.spans = []

    def add(self, name, t0, t1, parent=None, **attrs):
        sp = SimpleNamespace(name=name, span_id=len(self.spans) + 1,
                             parent_id=parent.span_id if parent else None,
                             t0=t0, t1=t1, duration=t1 - t0, attrs=attrs)
        self.spans.append(sp)
        return sp


def _commit(s: Spans, at: float, hit: int, keep_s=None):
    """One 1 s commit at ``at``: its parent's checkout hit (1) or missed (0)
    the cache; a program with ``store.keep`` spends ``keep_s`` in it."""
    c = s.add("store.commit", at, at + 1.0)
    s.add("delta.encode_full", at, at + 0.05, c)
    p = s.add("store.parent", at + 0.05, at + 0.25, c)
    m = s.add("mat.checkout_many", at + 0.06, at + 0.24, p,
              vids=1, decode_steps=1 - hit, cache_hits=hit)
    s.add("mat.plan", at + 0.06, at + 0.07, m, steps=1 - hit, from_cache=hit)
    s.add("store.save_meta", at + 0.90, at + 0.95, c)
    if keep_s is not None:
        s.add("store.keep", at + 0.95, at + 0.95 + keep_s, c, kept=int(keep_s > 0))


def _window(spans):
    return harness.Window(requests=[], seconds=2.0, setup_s=0.0, stored_bytes=0,
                          peaks={"hbm_bytes_per_s": 819e9}, spans=spans, trace=None)


def read(metric, w):
    return harness.reader("layer_metrics", metric)(w)


@pytest.mark.parametrize("hits,want", [((1, 1, 1), 100.0), ((0, 1, 1, 0), 50.0),
                                       ((0, 0), 0.0)])
def test_bench_parent_hit_pct(hits, want):
    s = Spans()
    for i, h in enumerate(hits):
        _commit(s, float(i), h, keep_s=0.1)
    # a checkout outside any commit (a reader's) is not a parent's
    s.add("mat.checkout_many", 50.0, 51.0, vids=4, decode_steps=0, cache_hits=4)
    assert read("commit_parent_hit_pct", _window(s.spans)) == pytest.approx(want)


def test_bench_keep_ms_per_commit():
    s = Spans()
    _commit(s, 0.0, 1, keep_s=0.080)
    _commit(s, 1.0, 1, keep_s=0.120)
    _commit(s, 2.0, 0, keep_s=0.0)          # a tree over budget: kept 0
    s.add("store.keep", 9.0, 10.0)          # outside any commit: not read
    assert read("commit_keep_ms", _window(s.spans)) == pytest.approx(200.0 / 3)


@pytest.mark.parametrize("metric,want", [("commit_parent_hit_pct", 0.0),
                                         ("commit_keep_ms", None)])
def test_bench_keep_readers_on_the_parent_program(metric, want):
    """A program without ``store.keep``, whose parents all decode from disk:
    the hit share reads 0 and the keep time reads nothing."""
    s = Spans()
    _commit(s, 0.0, 0)
    _commit(s, 1.0, 0)
    assert read(metric, _window(s.spans)) == want


@pytest.mark.parametrize("metric", ["commit_parent_hit_pct", "commit_keep_ms"])
def test_bench_keep_readers_find_nothing_without_spans(metric):
    s = Spans()
    c = s.add("store.commit", 0.0, 1.0)
    s.add("delta.encode_full", 0.0, 0.5, c)
    assert read(metric, _window(s.spans)) is None
    assert read(metric, _window([])) is None
