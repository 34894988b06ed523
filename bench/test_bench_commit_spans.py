"""The readers of the commit path's spans and counters, on a window of
hand-made spans: two commits with known children, a stray span outside any
commit, and a stub trace summary."""

from __future__ import annotations

from types import SimpleNamespace

import pytest

from bench import harness, trace

PEAKS = {"hbm_bytes_per_s": 819e9}
COMPACT_S = 0.004


class Spans:
    def __init__(self):
        self.spans = []

    def add(self, name, t0, t1, parent=None, **attrs):
        sp = SimpleNamespace(name=name, span_id=len(self.spans) + 1,
                             parent_id=parent.span_id if parent else None,
                             t0=t0, t1=t1, duration=t1 - t0, attrs=attrs)
        self.spans.append(sp)
        return sp


def _commit(s: Spans, at: float):
    """One 1 s commit at ``at`` inside its request: children as the program
    opens them, 10 ms of it covered by none, then the tradeoff sample."""
    r = s.add("svc.commit", at, at + 1.1)
    c = s.add("store.commit", at, at + 1.0, r)
    s.add("delta.encode_full", at + 0.00, at + 0.05, c)
    p = s.add("store.parent", at + 0.05, at + 0.25, c)
    m = s.add("mat.checkout_many", at + 0.06, at + 0.24, p)
    s.add("objects.decompress", at + 0.07, at + 0.20, m)
    s.add("delta.decode_full", at + 0.20, at + 0.23, m)
    d = s.add("delta.encode_delta", at + 0.25, at + 0.55, c,
              h2d_bytes=2_000_000, d2h_bytes=1_000_500, changed_blocks=100)
    s.add("delta.upload", at + 0.26, at + 0.30, d)
    s.add("delta.count", at + 0.30, at + 0.40, d)
    s.add("delta.fetch", at + 0.40, at + 0.50, d)
    s.add("hash.sha256", at + 0.55, at + 0.60, c)
    s.add("objects.compress", at + 0.60, at + 0.90, c)
    s.add("objects.write", at + 0.90, at + 0.95, c)
    s.add("hash.sha256", at + 0.95, at + 0.97, c)
    s.add("store.save_meta", at + 0.97, at + 0.99, c)
    s.add("tradeoff.sample", at + 1.0, at + 1.004, r, event="commit")
    return c


def _window(spans, summary=None):
    return harness.Window(requests=[], seconds=2.0, setup_s=0.0, stored_bytes=0,
                          peaks=PEAKS, spans=spans, trace=summary)


def _summary(compact_s=COMPACT_S):
    return trace.TraceSummary(window_s=2.0, busy_s=compact_s, chips=1,
                              programs={"jit__compact": compact_s},
                              program_calls={"jit__compact": 2}, ops=[], idle_gaps=[])


@pytest.fixture
def window():
    s = Spans()
    _commit(s, 10.0)
    _commit(s, 11.0)
    # outside any commit (a repack's measurement): read by none
    s.add("objects.compress", 12.0, 15.0)
    s.add("delta.encode_delta", 15.0, 16.0, h2d_bytes=9e9, d2h_bytes=9e9,
          changed_blocks=0)
    s.add("tradeoff.sample", 16.0, 16.5, event="sweep")
    return _window(s.spans, _summary())


def read(metric, w):
    return harness.reader("layer_metrics", metric)(w)


@pytest.mark.parametrize("metric,want", [
    ("commit_encode_ms", 50.0),
    ("commit_hash_ms", 70.0),
    ("commit_parent_ms", 200.0),
    ("commit_diff_ms", 300.0),
    ("commit_compress_ms", 300.0),
    ("commit_write_ms", 50.0 + 20.0),   # objects.write + store.save_meta
    ("commit_h2d_MB", 2.0),
    ("commit_d2h_MB", 1.0005),
    # 10 ms uncovered in each 1 s commit
    ("commit_untraced_pct", 1.0),
    # 2 x 4 KiB x 200 changed blocks at 819 GB/s, over 4 ms of _compact
    ("compact_roofline", 100.0 * 2 * 4096 * 200 / 819e9 / COMPACT_S),
    # the samples after a commit; the sweeper's is not one
    ("commit_sample_ms", 4.0),
])
def test_bench_commit_span_readers(window, metric, want):
    assert read(metric, window) == pytest.approx(want, rel=1e-9)


def test_bench_untraced_counts_overlapping_children_once():
    s = Spans()
    c = s.add("store.commit", 0.0, 1.0)
    s.add("objects.compress", 0.1, 0.6, c)
    s.add("objects.write", 0.4, 0.8, c)       # overlaps the compress
    s.add("store.save_meta", 0.9, 1.2, c)     # runs past the commit: clipped
    # a grandchild outside its parent covers nothing of the commit
    s.add("hash.sha256", 0.85, 0.88, s.spans[1])
    assert read("commit_untraced_pct", _window(s.spans)) == pytest.approx(20.0)


@pytest.mark.parametrize("metric", [
    "commit_encode_ms", "commit_hash_ms", "commit_parent_ms", "commit_diff_ms",
    "commit_compress_ms", "commit_write_ms", "commit_untraced_pct",
    "commit_h2d_MB", "commit_d2h_MB", "compact_roofline", "commit_sample_ms"])
def test_bench_commit_readers_find_nothing_in_an_older_program(metric):
    """A program whose commit opens none of these spans (it decodes the
    parent straight under ``store.commit``), and a window with no spans:
    each reader returns None and raises nothing."""
    s = Spans()
    c = s.add("store.commit", 0.0, 1.0)
    s.add("mat.checkout_many", 0.1, 0.3, c)
    assert read(metric, _window(s.spans, _summary())) is None
    assert read(metric, _window([], _summary())) is None


def test_bench_compact_roofline_needs_the_trace_and_the_program(window):
    assert read("compact_roofline", _window(window.spans)) is None
    assert read("compact_roofline", _window(window.spans, _summary(0.0))) is None
