"""VersionStore thread-safety: concurrent checkout/commit interleavings.

The service tier runs checkouts on a reader thread pool concurrent with a
single writer thread; the store guarantees that depends on:

* cache insert/evict/get under the cache's own lock — concurrent readers
  hammering overlapping vids while the LRU evicts never corrupt the byte
  accounting or serve a torn tree;
* access-count bumps, vid allocation, metadata insertion and the atomic
  ``_save_meta`` under the store lock — counts reconcile exactly and the
  metadata file reloads cleanly after any interleaving;
* commits are pure appends: readers racing a committer always see either
  a complete old graph or a complete new one, and every tree served is
  bit-identical to its committed payload;
* the store's one codec compresses and decompresses on many threads at
  once.

Threads + barriers only (no service layer here): this pins the *store*
contract the service builds on, tier-1 fast.
"""

import threading

import numpy as np

from repro.store import VersionStore
from repro.store.objectstore import Codec


def payload(seed: int, shape=(64, 48)):
    rng = np.random.RandomState(seed)
    return {"w": rng.randn(*shape).astype(np.float32)}


def perturbed(base, seed: int):
    rng = np.random.RandomState(seed)
    out = {"w": base["w"].copy()}
    out["w"][:2] += rng.randn(2, base["w"].shape[1]).astype(np.float32)
    return out


def build_chain(tmp_path, n=8, **kwargs):
    store = VersionStore(tmp_path, **kwargs)
    trees = {}
    tree = payload(0)
    vid = store.commit(tree, message="root")
    trees[vid] = tree
    for i in range(n - 1):
        tree = perturbed(tree, i + 1)
        vid = store.commit(tree, parents=[vid], message=f"c{i}")
        trees[vid] = tree
    return store, trees


class TestConcurrentReadsDuringCommits:
    def test_readers_race_committer_trees_stay_correct(self, tmp_path):
        store, trees = build_chain(tmp_path, n=8)
        hot = sorted(trees)
        errors = []
        stop = threading.Event()
        barrier = threading.Barrier(5)

        def reader(seed):
            rng = np.random.RandomState(seed)
            barrier.wait()
            while not stop.is_set():
                v = int(hot[rng.randint(0, len(hot))])
                t = store.checkout(v)
                if not np.array_equal(t["w"], trees[v]["w"]):
                    errors.append(("torn tree", v))
                    return

        readers = [
            threading.Thread(target=reader, args=(i,)) for i in range(4)
        ]
        for r in readers:
            r.start()
        barrier.wait()
        tip, tree = hot[-1], trees[hot[-1]]
        new = {}
        for i in range(30):  # writer: 30 appends racing the 4 readers
            tree = perturbed(tree, 1000 + i)
            tip = store.commit(tree, parents=[tip], message=f"race {i}")
            new[tip] = tree
        stop.set()
        for r in readers:
            r.join(timeout=30)
            assert not r.is_alive()
        assert not errors, errors
        # every racing commit landed intact too
        for v, want in new.items():
            assert np.array_equal(store.checkout(v)["w"], want["w"])
        assert store.materializer.stats()["invalidations"] == 0

    def test_concurrent_checkout_many_batches(self, tmp_path):
        store, trees = build_chain(tmp_path, n=10)
        hot = sorted(trees)
        errors = []
        barrier = threading.Barrier(4)

        def batch_reader(seed):
            rng = np.random.RandomState(seed)
            barrier.wait()
            for _ in range(15):
                batch = [
                    int(hot[j])
                    for j in rng.randint(0, len(hot), size=4)
                ]
                out = store.checkout_many(batch)
                for t, v in zip(out, batch):
                    if not np.array_equal(t["w"], trees[v]["w"]):
                        errors.append(("torn batch tree", v))
                        return

        threads = [
            threading.Thread(target=batch_reader, args=(i,))
            for i in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
        assert not errors, errors


class TestAccessCountConsistency:
    def test_counts_reconcile_exactly_across_threads(self, tmp_path):
        reads_per_thread, nthreads = 40, 4
        store, trees = build_chain(
            tmp_path, n=6, access_flush_every=1 << 30
        )
        hot = sorted(trees)
        base = {v: store.versions[v].access_count for v in hot}
        barrier = threading.Barrier(nthreads)

        def reader(seed):
            rng = np.random.RandomState(seed)
            barrier.wait()
            for _ in range(reads_per_thread):
                store.checkout(int(hot[rng.randint(0, len(hot))]))

        threads = [
            threading.Thread(target=reader, args=(i,))
            for i in range(nthreads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        total = sum(
            store.versions[v].access_count - base[v] for v in hot
        )
        assert total == reads_per_thread * nthreads  # no lost updates
        store.flush_access_counts()
        reopened = VersionStore(tmp_path)
        assert (
            sum(reopened.versions[v].access_count - base[v] for v in hot)
            == total
        )

    def test_concurrent_flush_and_reads(self, tmp_path):
        store, trees = build_chain(tmp_path, n=6, access_flush_every=3)
        hot = sorted(trees)
        errors = []
        barrier = threading.Barrier(4)

        def reader(seed):
            rng = np.random.RandomState(seed)
            barrier.wait()
            try:
                for _ in range(25):  # flush_every=3 -> flushes mid-race
                    store.checkout(int(hot[rng.randint(0, len(hot))]))
            except Exception as e:  # pragma: no cover - the failure mode
                errors.append(e)

        threads = [
            threading.Thread(target=reader, args=(i,)) for i in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors
        # the metadata written by racing flushes reloads cleanly
        reopened = VersionStore(tmp_path)
        assert sorted(reopened.versions) == hot


class TestMetaReloadsCleanAfterRace:
    def test_meta_consistent_after_racing_commits_and_reads(self, tmp_path):
        store, trees = build_chain(tmp_path, n=4)
        hot = sorted(trees)
        committed = {}
        lock = threading.Lock()
        barrier = threading.Barrier(3)

        def committer(seed):
            rng = np.random.RandomState(seed)
            barrier.wait()
            tree = trees[hot[-1]]
            for i in range(15):
                tree = perturbed(tree, seed * 1000 + i)
                parent = int(hot[rng.randint(0, len(hot))])
                vid = store.commit(
                    tree, parents=[parent], message=f"t{seed}-{i}"
                )
                with lock:
                    committed[vid] = tree

        # two committer threads: vid allocation and meta writes must not
        # collide even though the service tier serializes writers itself
        threads = [
            threading.Thread(target=committer, args=(i,)) for i in range(2)
        ]

        def reader():
            barrier.wait()
            for _ in range(40):
                store.checkout(int(hot[0]))

        threads.append(threading.Thread(target=reader))
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()

        assert len(committed) == 30  # no vid collisions swallowed a commit
        for v, want in committed.items():
            assert np.array_equal(store.checkout(v)["w"], want["w"])
        store.flush_access_counts()
        reopened = VersionStore(tmp_path)
        assert sorted(reopened.versions) == sorted(store.versions)
        for v, want in committed.items():
            assert np.array_equal(reopened.checkout(v)["w"], want["w"])


class TestCacheUnderContention:
    def test_tiny_budget_evictions_race_reads(self, tmp_path):
        one_entry = 64 * 48 * 4
        store, trees = build_chain(
            tmp_path, n=8, cache_budget_bytes=int(one_entry * 2.5)
        )
        hot = sorted(trees)
        errors = []
        barrier = threading.Barrier(4)

        def reader(seed):
            rng = np.random.RandomState(seed)
            barrier.wait()
            for _ in range(20):
                v = int(hot[rng.randint(0, len(hot))])
                t = store.checkout(v)
                if not np.array_equal(t["w"], trees[v]["w"]):
                    errors.append(("torn under eviction", v))
                    return

        threads = [
            threading.Thread(target=reader, args=(i,)) for i in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors
        s = store.materializer.stats()
        assert s["current_bytes"] <= int(one_entry * 2.5)
        assert s["current_bytes"] >= 0  # accounting never went negative


class TestCodecSharedAcrossThreads:
    def test_round_trips_from_eight_threads_at_once(self):
        # zstd contexts are not thread-safe: one shared by every thread
        # fails with ZstdError or crashes the process
        codec = Codec(backend="zstd")
        errors = []
        barrier = threading.Barrier(8)

        def worker(seed):
            rng = np.random.default_rng(seed)
            barrier.wait()
            for _ in range(100):
                blob = rng.integers(0, 4, 100_000, dtype=np.uint8).tobytes()
                try:
                    if codec.decompress(codec.compress(blob)) != blob:
                        errors.append("round trip changed the bytes")
                except Exception as e:  # noqa: BLE001 - collected, asserted below
                    errors.append(repr(e))

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
        assert errors == []
