"""DatasetService tests: coalescing, batching, write coordination, sweeps.

* **coalescing** — N concurrent checkouts of one ref perform exactly one
  materialization (counter-asserted: ``checkout.coalesced == N-1`` and one
  full decode at the store);
* **batching** — distinct refs inside one batching window fold into a
  single ``checkout_many`` dispatch; ``max_batch`` forces early dispatch;
* **commit visibility** — a commit through the service is immediately
  resolvable and checkout-able, and concurrent reads of old refs during
  commits return correct trees;
* **write exclusion** — repack drains readers (RW lock) and subsequent
  checkouts still verify bit-identical; cancellation while acquiring the
  write lock must not leak the writer flag (regression: stop() deadlock);
* **fsck sweep** — periodic and on-demand sweeps record metrics and keep
  the last report; error paths set error counters and propagate.

No pytest-asyncio in the image: each test drives its own event loop via
``asyncio.run``.
"""

import asyncio
import threading

import numpy as np
import pytest

from repro.core import OptimizeSpec
from repro.service import DatasetService, ServiceMetrics, percentile
from repro.service.service import _AsyncRWLock
from repro.store import Repository


def payload(seed: int, shape=(32, 24)):
    rng = np.random.RandomState(seed)
    return {"w": rng.randn(*shape).astype(np.float32)}


def build_repo(tmp_path, versions=6):
    repo = Repository(tmp_path)
    trees = {}
    for i in range(versions):
        vid = repo.commit(payload(i), message=f"v{i}")
        trees[vid] = payload(i)
    return repo, trees


class TestCoalescing:
    def test_concurrent_same_ref_single_materialization(self, tmp_path):
        repo, trees = build_repo(tmp_path)
        # a commit caches its own tree: reopen so the tip starts cold
        repo.close()
        repo = Repository(tmp_path)
        tip = repo.resolve("main")

        async def go():
            async with repo.serve(readers=4) as svc:
                out = await asyncio.gather(
                    *(svc.checkout("main") for _ in range(10))
                )
                return out, svc.stats()

        out, stats = asyncio.run(go())
        for t in out:
            assert np.array_equal(t["w"], trees[tip]["w"])
        c = stats["counters"]
        # 10 requests, 9 coalesced onto the first's in-flight future
        assert c["requests.checkout"] == 10
        assert c["checkout.coalesced"] == 9
        assert c["checkout.batched_refs"] == 1
        # the store decoded the chain exactly once
        assert stats["store"]["full_decodes"] + stats["store"]["misses"] >= 1
        assert stats["store"]["hits"] == 0

    def test_each_request_gets_private_tree_dict(self, tmp_path):
        repo, _ = build_repo(tmp_path)

        async def go():
            async with repo.serve() as svc:
                a, b = await asyncio.gather(
                    svc.checkout("main"), svc.checkout("main")
                )
                return a, b

        a, b = asyncio.run(go())
        assert a is not b  # top-level dict is per-request
        a["extra"] = 1
        assert "extra" not in b

    def test_coalescing_keyed_by_vid_not_ref_spelling(self, tmp_path):
        repo, _ = build_repo(tmp_path)
        tip = repo.resolve("main")
        repo.tag("rel", at=tip)

        async def go():
            async with repo.serve() as svc:
                await asyncio.gather(
                    svc.checkout("main"), svc.checkout("rel"),
                    svc.checkout(tip),
                )
                return svc.stats()["counters"]

        c = asyncio.run(go())
        # three spellings of one vid: one materialization, two coalesced
        assert c["checkout.coalesced"] == 2
        assert c["checkout.batched_refs"] == 1


class TestBatching:
    def test_window_folds_distinct_refs_into_one_dispatch(self, tmp_path):
        repo, trees = build_repo(tmp_path, versions=8)
        vids = sorted(trees)[:6]

        async def go():
            async with repo.serve(batch_window_s=0.05) as svc:
                out = await svc.checkout_many(vids)
                return out, svc.stats()["counters"]

        out, c = asyncio.run(go())
        for t, v in zip(out, vids):
            assert np.array_equal(t["w"], trees[v]["w"])
        assert c["checkout.batches"] == 1
        assert c["checkout.batched_refs"] == len(vids)

    def test_max_batch_forces_early_dispatch(self, tmp_path):
        repo, trees = build_repo(tmp_path, versions=8)
        vids = sorted(trees)

        async def go():
            # window far longer than the test: only max_batch can dispatch
            async with repo.serve(batch_window_s=30.0, max_batch=4) as svc:
                await svc.checkout_many(vids[:4])
                return svc.stats()["counters"]

        c = asyncio.run(go())
        assert c["checkout.batches"] == 1
        assert c["checkout.batched_refs"] == 4

    def test_unknown_ref_rejects_without_poisoning_batch(self, tmp_path):
        repo, trees = build_repo(tmp_path)
        good = sorted(trees)[0]

        async def go():
            async with repo.serve(batch_window_s=0.02) as svc:
                ok, bad = await asyncio.gather(
                    svc.checkout(good),
                    svc.checkout("no-such-branch"),
                    return_exceptions=True,
                )
                return ok, bad, svc.stats()["counters"]

        ok, bad, c = asyncio.run(go())
        assert np.array_equal(ok["w"], trees[good]["w"])
        assert isinstance(bad, ValueError)
        assert c["errors.checkout"] == 1


class TestWriteCoordination:
    def test_commit_visible_to_subsequent_checkout(self, tmp_path):
        repo, _ = build_repo(tmp_path)
        fresh = payload(99)

        async def go():
            async with repo.serve() as svc:
                vid = await svc.commit(fresh, message="via service")
                tree = await svc.checkout(vid)
                tip = await svc.checkout("main")
                return vid, tree, tip

        vid, tree, tip = asyncio.run(go())
        assert np.array_equal(tree["w"], fresh["w"])
        assert np.array_equal(tip["w"], fresh["w"])
        assert repo.resolve("main") == vid

    def test_reads_interleaved_with_commits_stay_correct(self, tmp_path):
        repo, trees = build_repo(tmp_path)
        hot = sorted(trees)

        async def go():
            async with repo.serve(readers=3) as svc:
                for i in range(4):
                    out = await asyncio.gather(
                        svc.commit(payload(100 + i), message=f"a{i}"),
                        *(svc.checkout(v) for v in hot),
                    )
                    for t, v in zip(out[1:], hot):
                        assert np.array_equal(t["w"], trees[v]["w"])
                return svc.stats()["counters"]

        c = asyncio.run(go())
        assert c["requests.commit"] == 4
        assert c["requests.checkout"] == 4 * len(hot)
        assert c.get("errors.checkout", 0) == 0

    def test_repack_quiesces_and_trees_survive(self, tmp_path):
        repo, trees = build_repo(tmp_path, versions=8)
        vids = sorted(trees)

        async def go():
            async with repo.serve(readers=3) as svc:
                await svc.checkout_many(vids)  # warm
                stats = await svc.repack(OptimizeSpec.problem(2))
                out = await svc.checkout_many(vids)
                return stats, out, svc.stats()["store"]["purges"]

        stats, out, purges = asyncio.run(go())
        assert purges >= 1  # repack rewrites chains -> wholesale purge
        for t, v in zip(out, vids):
            assert np.array_equal(t["w"], trees[v]["w"])

    def test_log_and_diff_through_service(self, tmp_path):
        repo, trees = build_repo(tmp_path)
        vids = sorted(trees)

        async def go():
            async with repo.serve() as svc:
                lg = await svc.log("main")
                d = await svc.diff(vids[0], vids[-1])
                return lg, d

        lg, d = asyncio.run(go())
        assert [m.vid for m in lg][0] == vids[-1]
        assert "w" in d.changed

    def test_requests_refuse_before_start_and_after_stop(self, tmp_path):
        repo, _ = build_repo(tmp_path)
        svc = DatasetService(repo)

        async def before():
            with pytest.raises(RuntimeError, match="not started"):
                await svc.checkout("main")

        asyncio.run(before())

        async def after():
            async with repo.serve() as svc2:
                pass
            with pytest.raises(RuntimeError, match="not started"):
                await svc2.checkout("main")

        asyncio.run(after())

    def test_stop_right_after_a_coalesced_batch_returns(self, tmp_path):
        # the batch's dispatch task can finish before its done callback
        # (which drops it from the task set) runs; stop() must not spin on
        # it.  A spinning stop never yields, so the bound is a thread join
        repo, trees = build_repo(tmp_path, versions=3)

        async def go():
            async with repo.serve() as svc:
                return await svc.checkout_many(["main", 1, 2, 3])

        out = []
        t = threading.Thread(target=lambda: out.append(asyncio.run(go())),
                             daemon=True)
        t.start()
        t.join(timeout=60)
        assert not t.is_alive(), "stop() did not return"
        assert np.array_equal(out[0][0]["w"], trees[3]["w"])


class TestCancellation:
    def test_cancelled_waiter_does_not_poison_coalesced_future(
        self, tmp_path
    ):
        """Regression: waiters awaited the shared in-flight future
        directly, so cancelling one coalesced request cancelled the future
        under every other waiter (and left a cancelled future in _inflight
        for later arrivals)."""
        repo, trees = build_repo(tmp_path)
        tip = repo.resolve("main")

        async def go():
            async with repo.serve(batch_window_s=0.05) as svc:
                tasks = [
                    asyncio.create_task(svc.checkout("main"))
                    for _ in range(4)
                ]
                await asyncio.sleep(0.01)  # all coalesced onto one future
                tasks[0].cancel()
                done = await asyncio.gather(*tasks, return_exceptions=True)
                # a new request for the same vid must still be servable
                late = await svc.checkout("main")
                return done, late, svc.stats()["counters"]

        done, late, c = asyncio.run(go())
        assert isinstance(done[0], asyncio.CancelledError)
        for t in done[1:]:
            assert not isinstance(t, BaseException)
            assert np.array_equal(t["w"], trees[tip]["w"])
        assert np.array_equal(late["w"], trees[tip]["w"])
        assert c.get("errors.checkout", 0) == 0

    def test_cancelled_requester_does_not_let_repack_race_batch(
        self, tmp_path
    ):
        """Regression: a cancelled requester released its read claim while
        its _PendingCheckout stayed queued, so a repack could rewrite the
        storage graph concurrently with the window-timer dispatch.  The
        batch now parks one claim per entry until it settles: by the time
        the repack body runs, the orphaned batch must already be done."""
        repo, trees = build_repo(tmp_path, versions=8)
        vid = sorted(trees)[0]

        async def go():
            async with repo.serve(batch_window_s=0.05) as svc:
                t = asyncio.create_task(svc.checkout(vid))
                await asyncio.sleep(0.01)  # enqueued; claim parked on batch
                pend = list(svc._pending)
                assert pend and pend[0].vid == vid
                t.cancel()
                with pytest.raises(asyncio.CancelledError):
                    await t

                orig_repack = repo.repack
                settled_at_repack = {}

                def spying_repack(spec, **kw):
                    # runs on the writer thread once the write lock is held
                    settled_at_repack["done"] = pend[0].future.done()
                    return orig_repack(spec, **kw)

                repo.repack = spying_repack
                try:
                    await svc.repack(OptimizeSpec.problem(2))
                finally:
                    repo.repack = orig_repack
                tree = await svc.checkout(vid)
                return settled_at_repack, pend[0], tree

        settled, pend0, tree = asyncio.run(go())
        assert settled["done"] is True  # batch drained before the rewrite
        assert not pend0.future.cancelled()
        assert np.array_equal(tree["w"], trees[vid]["w"])


class TestRWLock:
    def test_writer_excludes_readers_and_vice_versa(self):
        async def go():
            lock = _AsyncRWLock()
            order = []

            async def reader(i):
                async with lock.read():
                    order.append(f"r{i}")
                    await asyncio.sleep(0.01)

            async def writer():
                async with lock.write():
                    order.append("w")

            # readers overlap each other; writer runs after both drain
            await asyncio.gather(reader(0), reader(1), writer())
            return order

        order = asyncio.run(go())
        assert order[-1] == "w"

    def test_cancel_during_write_acquire_releases_claim(self):
        """Regression: a writer cancelled while waiting for readers to
        drain must drop the writer flag, or every later acquire hangs."""

        async def go():
            lock = _AsyncRWLock()
            release = asyncio.Event()

            async def reader():
                async with lock.read():
                    await release.wait()

            r = asyncio.create_task(reader())
            await asyncio.sleep(0)  # reader holds the lock

            async def writer():
                async with lock.write():
                    pass

            w = asyncio.create_task(writer())
            await asyncio.sleep(0.01)  # writer now waiting on readers==0
            w.cancel()
            with pytest.raises(asyncio.CancelledError):
                await w
            release.set()
            await r
            # the lock must still be acquirable in both modes
            async with asyncio.timeout(1):
                async with lock.write():
                    pass
                async with lock.read():
                    pass

        try:
            asyncio.timeout  # py3.11+
        except AttributeError:
            pytest.skip("asyncio.timeout unavailable")
        asyncio.run(go())

    def test_cancel_during_write_acquire_releases_claim_py310(self):
        """Same regression without asyncio.timeout (runs on 3.10)."""

        async def go():
            lock = _AsyncRWLock()
            release = asyncio.Event()

            async def reader():
                async with lock.read():
                    await release.wait()

            r = asyncio.create_task(reader())
            await asyncio.sleep(0)

            async def writer():
                async with lock.write():
                    pass

            w = asyncio.create_task(writer())
            await asyncio.sleep(0.01)
            w.cancel()
            with pytest.raises(asyncio.CancelledError):
                await w
            release.set()
            await r

            async def reacquire():
                async with lock.write():
                    pass
                async with lock.read():
                    pass
                return True

            return await asyncio.wait_for(reacquire(), timeout=2.0)

        assert asyncio.run(go()) is True


class TestFsckSweep:
    def test_periodic_sweep_records_metrics(self, tmp_path):
        repo, _ = build_repo(tmp_path)

        async def go():
            async with repo.serve(fsck_interval_s=0.03) as svc:
                # wait on the counter, not wall time: CI boxes stall
                for _ in range(100):
                    if svc.metrics.counter("fsck.sweeps") >= 2:
                        break
                    await asyncio.sleep(0.02)
                return svc.stats(), svc.last_fsck

        stats, report = asyncio.run(go())
        c = stats["counters"]
        assert c["fsck.sweeps"] >= 2
        assert report is not None and not report.findings
        assert stats["fsck"]["checked"] > 0

    def test_on_demand_fsck(self, tmp_path):
        repo, _ = build_repo(tmp_path)

        async def go():
            async with repo.serve() as svc:  # no periodic sweeper
                report = await svc.fsck()
                return report, svc.stats()["counters"]

        report, c = asyncio.run(go())
        assert not report.findings
        assert c["fsck.sweeps"] == 1
        assert c["fsck.findings"] == 0

    def test_sweep_overlapping_traffic(self, tmp_path):
        repo, trees = build_repo(tmp_path)
        hot = sorted(trees)

        async def go():
            async with repo.serve(readers=3, fsck_interval_s=0.02) as svc:
                for _ in range(6):
                    out = await svc.checkout_many(hot)
                    for t, v in zip(out, hot):
                        assert np.array_equal(t["w"], trees[v]["w"])
                    await asyncio.sleep(0.01)
                return svc.stats()["counters"]

        c = asyncio.run(go())
        assert c["fsck.sweeps"] >= 1
        assert c.get("errors.fsck", 0) == 0


class TestMetrics:
    def test_percentile_nearest_rank(self):
        xs = list(range(1, 101))
        assert percentile(xs, 50) in (50, 51)
        assert percentile(xs, 99) in (99, 100)
        assert percentile([7.0], 99) == 7.0
        with pytest.raises(ValueError):
            percentile([], 50)

    def test_track_window_bounded_but_totals_lifetime(self):
        m = ServiceMetrics(track_cap=10)
        for i in range(100):
            m.observe("lat", 0.001 * (i + 1))
        s = m.track("lat")
        assert s["count"] == 100  # lifetime
        # window holds only the last 10 samples (91ms..100ms)
        assert s["p50_ms"] >= 90.0
        assert s["max_ms"] == 100.0

    def test_snapshot_includes_counters_and_tracks(self):
        m = ServiceMetrics()
        m.inc("a")
        m.inc("a", 2)
        m.observe("t", 0.5)
        snap = m.snapshot()
        assert snap["counters"]["a"] == 3
        assert snap["tracks"]["t"]["count"] == 1

    def test_service_latency_tracks_populated(self, tmp_path):
        repo, _ = build_repo(tmp_path)

        async def go():
            async with repo.serve() as svc:
                await svc.checkout("main")
                await svc.commit(payload(50), message="x")
                return svc.stats()["tracks"]

        tracks = asyncio.run(go())
        assert tracks["latency.checkout"]["count"] == 1
        assert tracks["latency.commit"]["count"] == 1
        assert tracks["queue_wait"]["count"] == 1
        assert tracks["decode"]["count"] == 1
        assert tracks["latency.checkout"]["p99_ms"] > 0

    def test_stop_flushes_access_counts(self, tmp_path):
        repo, trees = build_repo(tmp_path)
        vids = sorted(trees)

        async def go():
            async with repo.serve() as svc:
                await svc.checkout_many(vids)

        asyncio.run(go())
        store = repo.store
        assert sum(store.versions[v].access_count for v in vids) >= len(vids)
