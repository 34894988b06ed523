"""The reader of the zstd workers' counters, on windows of hand-made spans:
``commit_compress_mt_pct`` from ``workers`` and ``in_bytes`` on the
``objects.compress`` spans under ``store.commit``."""

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


def _window(spans):
    return harness.Window(requests=[], seconds=2.0, setup_s=0.0, stored_bytes=0,
                          peaks={"hbm_bytes_per_s": 819e9}, spans=spans, trace=None)


def read(metric, w):
    return harness.reader("layer_metrics", metric)(w)


@pytest.mark.parametrize("runs,want", [
    ([(12, 200_000_000)], 100.0),                 # a version stored whole
    ([(0, 3_500_000), (0, 1_000)], 0.0),          # deltas, one-threaded
    ([(8, 30_000_000), (0, 10_000_000)], 75.0),   # one of each, by bytes
    (None, None),                                 # no attributes: an older program
])
def test_bench_commit_compress_mt_pct(runs, want):
    """Bytes through zstd's workers over all bytes compressed under a commit;
    a compress outside any commit (a repack's measurement) counts for none."""
    s = Spans()
    c = s.add("store.commit", 0.0, 1.0)
    for i, (workers, n) in enumerate(runs or [(None, None)]):
        attrs = {} if runs is None else {"workers": workers, "in_bytes": n}
        s.add("objects.compress", 0.1 * i, 0.1 * i + 0.05, c, **attrs)
    s.add("objects.compress", 2.0, 3.0, workers=16, in_bytes=10**9)
    got = read("commit_compress_mt_pct", _window(s.spans))
    assert got == (None if want is None else pytest.approx(want, rel=1e-12))


def test_bench_compress_mt_reader_finds_nothing_in_an_older_program():
    """A program whose commit opens no ``objects.compress`` span (it decodes
    the parent straight under ``store.commit``), and a window with no spans:
    the reader returns None and raises nothing."""
    s = Spans()
    c = s.add("store.commit", 0.0, 1.0)
    s.add("mat.checkout_many", 0.1, 0.3, c)
    assert read("commit_compress_mt_pct", _window(s.spans)) is None
    assert read("commit_compress_mt_pct", _window([])) is None
