"""Spans and host-device byte counters inside ``VersionStore.commit``.

* the span tree of a delta commit: every layer's span nests under
  ``store.commit``, and the counters on ``delta.encode_delta`` equal the
  bytes of the arrays that crossed (both leaves' blocks up; each changed
  count, ``idx[:n]`` and ``blocks[:n]`` down);
* a disabled tracer records nothing and the commit's result is the same;
* under ``jax.profiler.trace`` the ``with`` spans land on the profiler's
  ``/host:CPU`` plane with the tracer's durations and nesting, and spans
  opened inside an asyncio task do not.
"""

import asyncio
from pathlib import Path

import numpy as np

from repro import obs
from repro.kernels import ops
from repro.kernels.ref import BLOCK_BYTES
from repro.store.delta import decode_delta_wire, encode_delta
from repro.store.version_store import VersionStore

COMMIT_CHILDREN = {"delta.encode_full", "store.parent", "delta.encode_delta",
                   "hash.sha256", "objects.compress", "objects.write",
                   "store.save_meta", "store.keep"}
DIFF_CHILDREN = {"delta.upload", "delta.count", "delta.fetch", "delta.pack"}


def _trees():
    rng = np.random.RandomState(7)
    base = {
        "w": rng.randn(96, 128).astype(np.float32),   # 12 blocks
        "b": rng.randn(1500).astype(np.float32),      # 2 blocks, padded
        "step": np.arange(3, dtype=np.int64),         # 1 block, padded
    }
    new = dict(base)
    w = base["w"].copy()
    w[0, :4] += 1.0      # block 0
    w[40, :4] += 1.0     # block 5
    new["w"] = w
    new["step"] = base["step"] + 1
    return base, new


def _padded(tree):
    return sum(-(-a.nbytes // BLOCK_BYTES) * BLOCK_BYTES for a in tree.values())


def _commit_pair(root, tracer):
    """Commit the base, then the new tree with ``tracer`` as the global
    tracer; returns (store, vid, tracer)."""
    base, new = _trees()
    store = VersionStore(root, cache_budget_bytes=0)
    v1 = store.commit(base)
    old = obs.set_tracer(tracer)
    try:
        vid = store.commit(new, parents=[v1])
    finally:
        obs.set_tracer(old)
    return store, vid, tracer


def _ancestors(spans):
    by_id = {s.span_id: s for s in spans}

    def ancestors(s):
        out = []
        while s.parent_id in by_id:
            s = by_id[s.parent_id]
            out.append(s.name)
        return out

    return ancestors


def test_commit_span_tree_and_byte_counters(tmp_path, monkeypatch):
    uploaded, counts = [], []

    def to_blocks(x, _real=ops.to_blocks):
        blocks, meta = _real(x)
        uploaded.append(blocks.nbytes)
        return blocks, meta

    def count_changed(a, b, _real=ops.count_changed):
        mask, n = _real(a, b)
        counts.append(n)
        return mask, n

    base, new = _trees()
    store = VersionStore(tmp_path, cache_budget_bytes=0)
    v1 = store.commit(base)
    monkeypatch.setattr(ops, "to_blocks", to_blocks)
    monkeypatch.setattr(ops, "count_changed", count_changed)
    tracer = obs.Tracer(enabled=True)
    old = obs.set_tracer(tracer)
    try:
        vid = store.commit(new, parents=[v1])
    finally:
        obs.set_tracer(old)
    spans = tracer.spans()
    ancestors = _ancestors(spans)
    (commit,) = [s for s in spans if s.name == "store.commit"]
    # every span of the commit nests under store.commit
    assert all(ancestors(s)[-1:] == ["store.commit"] for s in spans if s is not commit)
    direct = {s.name for s in spans if s.parent_id == commit.span_id}
    assert direct == COMMIT_CHILDREN
    (keep,) = [s for s in spans if s.name == "store.keep"]
    assert keep.attrs["kept"] == 0  # a zero budget caches nothing
    assert sum(s.name == "hash.sha256" for s in spans) == 2  # key and fingerprint
    (diff,) = [s for s in spans if s.name == "delta.encode_delta"]
    assert {s.name for s in spans if s.parent_id == diff.span_id} == DIFF_CHILDREN
    # the parent is decoded from disk under store.parent
    for name in ("objects.read", "objects.decompress", "delta.decode_full"):
        assert "store.parent" in ancestors(next(s for s in spans if s.name == name))

    meta = store.versions[vid]
    assert meta.stored_base is not None  # stored as a delta
    wire = decode_delta_wire(store.objects.get(meta.object_key))
    # what was fetched is what the delta holds
    fetched = sum(d.idx.nbytes + d.blocks.nbytes for d in wire.sparse.values())
    # the parent decodes from disk, so the blocks uploaded are the diff's
    assert len(uploaded) == 2 * len(new)
    assert diff.attrs["h2d_bytes"] == sum(uploaded) == 2 * _padded(new)
    assert diff.attrs["d2h_bytes"] == 4 * len(counts) + fetched
    assert diff.attrs["changed_blocks"] == sum(counts) == 3
    _, stats = encode_delta(base, new)
    assert diff.attrs["changed_blocks"] == stats["changed_blocks"]
    # the commit's direct children lie inside it, one after another
    kids = sorted((s.t0, s.t1) for s in spans if s.parent_id == commit.span_id)
    assert commit.t0 <= kids[0][0] and kids[-1][1] <= commit.t1
    assert all(a[1] <= b[0] for a, b in zip(kids, kids[1:]))


def test_disabled_tracer_records_nothing_and_changes_nothing(tmp_path):
    off = obs.Tracer(enabled=False)
    a, vid_a, _ = _commit_pair(tmp_path / "off", off)
    b, vid_b, on = _commit_pair(tmp_path / "on", obs.Tracer(enabled=True))
    assert len(off) == 0 and len(on) > 0
    assert vid_a == vid_b
    ma, mb = a.versions[vid_a], b.versions[vid_b]
    assert (ma.object_key, ma.stored_bytes, ma.content_fp) == \
        (mb.object_key, mb.stored_bytes, mb.content_fp)
    assert a.objects.get(ma.object_key) == b.objects.get(mb.object_key)


def _host_events(trace_dir: Path):
    from jax.profiler import ProfileData

    (path,) = sorted(trace_dir.glob("**/*.xplane.pb"))
    plane = next(p for p in ProfileData.from_file(str(path)).planes
                 if p.name == "/host:CPU")
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for line in plane.lines for e in line.events]


def test_with_spans_land_on_the_profiler_clock(tmp_path):
    import jax

    tracer = obs.Tracer(enabled=True)
    base, new = _trees()
    store = VersionStore(tmp_path / "store", cache_budget_bytes=0)
    v1 = store.commit(base)
    v2 = store.commit(new, parents=[v1])  # compiles the diff outside the trace
    newer = {**new, "step": new["step"] + 1}  # a new object: compressed

    async def in_task():
        with obs.span("task.span"):
            await asyncio.sleep(0)

    old = obs.set_tracer(tracer)
    try:
        with jax.profiler.trace(str(tmp_path / "trace")):
            store.commit(newer, parents=[v2])
            asyncio.run(in_task())
    finally:
        obs.set_tracer(old)

    events = _host_events(tmp_path / "trace")
    names = ("store.commit", "delta.encode_delta", "objects.compress")
    got = {n: [(s, e) for m, s, e in events if m == n] for n in names}
    spans = {n: [s for s in tracer.spans() if s.name == n] for n in names}
    for n in names:
        assert len(got[n]) == len(spans[n]) == 1, n
        (s, e), (sp,) = got[n][0], spans[n]
        assert abs((e - s) * 1e-9 - sp.duration) < 1e-3, n
    # nested as the tracer nests them
    (c0, c1), = got["store.commit"]
    for n in ("delta.encode_delta", "objects.compress"):
        (s, e), = got[n]
        assert c0 <= s and e <= c1, n
    # a span opened inside an asyncio task stays on the tracer clock only
    assert any(s.name == "task.span" for s in tracer.spans())
    assert not any(m == "task.span" for m, _, _ in events)
