"""Materialization-subsystem tests: planner/cache correctness, batch ≡
sequential checkouts, fingerprint invalidation on commit/repack, access-count
persistence, cycle guards, and repack gc accounting."""

import numpy as np
import pytest

from repro.store import VersionStore, flatten_payload
from repro.store.materializer import (
    CheckoutPlanner,
    MaterializationCache,
    tree_nbytes,
)

from reference_decode import checkout_ref
from test_store import build_linear_history, make_payload, perturb


def build_branching_store(tmp_path, *, n=12, branch_every=3, seed=0,
                          shape=(48, 64), **store_kw):
    """Random branching history: every ``branch_every``-th commit forks from
    a random earlier version instead of the tip."""
    rng = np.random.RandomState(seed)
    store = VersionStore(tmp_path, **store_kw)
    payloads = {}
    p = make_payload(rng, shape=shape)
    vids = [store.commit(p, message="root")]
    payloads[vids[0]] = p
    for i in range(n - 1):
        if i % branch_every == branch_every - 1:
            parent = int(rng.choice(vids))
        else:
            parent = vids[-1]
        p = perturb(payloads[parent], rng, frac=0.04)
        vid = store.commit(p, parents=[parent], message=f"c{i}")
        payloads[vid] = p
        vids.append(vid)
    return store, vids, payloads


def build_history(tmp_path, tree, *, n=6, budget=256 << 20):
    """A linear history of ``n`` versions, each after the first stored as a
    delta; returns the store, the vids and the tip's tree.  ``dense``
    perturbs a fixed-shape payload; ``grown`` appends rows to a table's
    columns beside an int64 row count, so every column leaf grows."""
    store = VersionStore(tmp_path, cache_budget_bytes=budget)
    if tree == "dense":
        vids, last = build_linear_history(store, n=n, shape=(64, 64))
        return store, vids, flatten_payload(last)
    rng = np.random.default_rng(5)

    def table(field):
        rows = len(field)
        return {"field": field, "key": np.arange(rows, dtype=np.int64) * 3,
                "num_rows": np.array(rows, np.int64)}

    t = table(rng.integers(0, 256, (600, 100), dtype=np.uint8))
    vids = [store.commit(t)]
    for i in range(n - 1):
        new = rng.integers(0, 256, (7 + i, 100), dtype=np.uint8)
        field = np.concatenate([t["field"], new])
        field[rng.integers(0, len(t["field"]), 3)] = i
        t = table(field)
        vids.append(store.commit(t, parents=[vids[-1]]))
    assert all(store.versions[v].stored_base is not None for v in vids[1:])
    return store, vids, t


def assert_trees_equal(a, b):
    assert set(a) == set(b)
    for k in a:
        assert a[k].dtype == b[k].dtype, k
        np.testing.assert_array_equal(a[k], b[k])


class TestCheckoutManyProperty:
    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_batch_identical_to_sequential(self, tmp_path, seed):
        # random branching store, random request subsets (with repeats):
        # checkout_many must be bit-identical to sequential checkouts
        store, vids, payloads = build_branching_store(
            tmp_path / "a", n=10, seed=seed
        )
        rng = np.random.RandomState(100 + seed)
        for _ in range(3):
            k = rng.randint(1, len(vids) + 1)
            batch = [int(v) for v in rng.choice(vids, size=k, replace=True)]
            fresh = VersionStore(tmp_path / "a")  # cold store: no cache state
            sequential = [fresh.checkout(v) for v in batch]
            batched = store.checkout_many(batch)
            for got, want, vid in zip(batched, sequential, batch):
                assert_trees_equal(got, want)
                assert_trees_equal(got, fresh.checkout(vid))

    def test_batch_matches_committed_payloads(self, tmp_path):
        store, vids, payloads = build_branching_store(tmp_path, n=8, seed=7)
        out = store.checkout_many(vids)
        for vid, tree in zip(vids, out):
            assert_trees_equal(tree, flatten_payload(payloads[vid]))

    def test_batch_decodes_shared_prefix_once(self, tmp_path):
        store = VersionStore(tmp_path, cache_budget_bytes=0)
        vids, _ = build_linear_history(store, n=6, shape=(64, 64))
        m = store.materializer
        d0, f0 = m.delta_applies, m.full_decodes
        store.checkout_many(vids)  # whole chain: 1 full + n-1 deltas
        assert m.full_decodes - f0 == 1
        assert m.delta_applies - d0 == len(vids) - 1
        # sequential cold checkouts on a zero-budget cache pay the chain walk
        # per request: strictly more decodes than the single batched plan
        d1, f1 = m.delta_applies, m.full_decodes
        for v in vids:
            store.checkout(v)
        assert (m.delta_applies - d1) + (m.full_decodes - f1) > len(vids)

    def test_checkout_many_empty_and_unknown(self, tmp_path):
        store = VersionStore(tmp_path)
        vids, _ = build_linear_history(store, n=2)
        assert store.checkout_many([]) == []
        with pytest.raises(KeyError):
            store.checkout_many([999])
        # a failed batch must not inflate the workload signal
        with pytest.raises(KeyError):
            store.checkout_many([vids[0], 999])
        assert store.versions[vids[0]].access_count == 0


class TestMaterializationCache:
    def test_warm_checkout_hits_cache(self, tmp_path):
        store = VersionStore(tmp_path)
        vids, _ = build_linear_history(store, n=5)
        store.checkout(vids[-1])
        m = store.materializer
        d0, f0 = m.delta_applies, m.full_decodes
        warm = store.checkout(vids[-1])
        assert (m.delta_applies, m.full_decodes) == (d0, f0)  # no decode
        assert m.cache.hits >= 1
        # intermediates on the chain are warm too
        store.checkout(vids[2])
        assert (m.delta_applies, m.full_decodes) == (d0, f0)

    def test_commit_keeps_warm_entries_chain_mode(self, tmp_path):
        # append-aware default: a commit appends one storage triple but
        # rewrites no existing chain, so warm entries survive and the next
        # checkout of an old version is a pure cache hit
        store = VersionStore(tmp_path)
        vids, payload = build_linear_history(store, n=4)
        chain_fp = store.chain_fingerprint(vids[-1])
        store.checkout(vids[-1])
        assert len(store.materializer.cache.vids()) > 0
        rng = np.random.RandomState(9)
        store.commit(perturb(payload, rng), parents=[vids[-1]])
        assert store.chain_fingerprint(vids[-1]) == chain_fp
        m = store.materializer
        d0, f0 = m.delta_applies, m.full_decodes
        store.checkout(vids[0])
        assert (m.delta_applies, m.full_decodes) == (d0, f0)  # no decode
        assert m.cache.invalidations == 0

    def test_repack_invalidates_cache_and_serves_fresh(self, tmp_path):
        store = VersionStore(tmp_path)
        vids, _ = build_linear_history(store, n=5)
        warm = {v: store.checkout(v) for v in vids}
        fps = {v: store.chain_fingerprint(v) for v in vids}
        store.repack("spt")  # materializes everything: new storage graph
        # every delta-stored version's chain was rewritten; the full root's
        # object is the same bytes, so its chain is unchanged
        rotated = [v for v in vids if store.chain_fingerprint(v) != fps[v]]
        assert rotated == vids[1:]
        assert store.materializer.stats()["purges"] == 1
        for v in vids:
            assert_trees_equal(store.checkout(v), warm[v])

    def test_byte_budget_evicts_lru(self, tmp_path):
        store = VersionStore(tmp_path)
        vids, _ = build_linear_history(store, n=4, shape=(64, 64))
        one_tree = tree_nbytes(store.checkout(vids[0]))
        # budget for ~2 trees: a full-chain checkout must evict
        store2 = VersionStore(tmp_path, cache_budget_bytes=int(one_tree * 2.5))
        store2.checkout(vids[-1])
        cache = store2.materializer.cache
        assert cache.evictions > 0
        assert cache.current_bytes <= cache.budget_bytes

    def test_zero_budget_cache_still_correct(self, tmp_path):
        store = VersionStore(tmp_path, cache_budget_bytes=0)
        vids, last_payload = build_linear_history(store, n=4)
        assert_trees_equal(
            store.checkout(vids[-1]), flatten_payload(last_payload)
        )
        assert list(store.materializer.cache.vids()) == []

    def test_cached_arrays_are_read_only(self, tmp_path):
        store = VersionStore(tmp_path)
        vids, _ = build_linear_history(store, n=2)
        tree = store.checkout(vids[-1])
        with pytest.raises(ValueError):
            tree["w"][0, 0] = 1.0  # mutating would corrupt the shared cache

    def test_zero_budget_arrays_also_read_only(self, tmp_path):
        # regression: the chain pipeline passes untouched leaves through by
        # reference across a batch's results, so even uncached trees must be
        # frozen — a writable alias
        # would let one result's mutation corrupt another's
        store = VersionStore(tmp_path, cache_budget_bytes=0)
        vids, _ = build_linear_history(store, n=3)
        t1, t2 = store.checkout_many([vids[-2], vids[-1]])
        with pytest.raises(ValueError):
            t1["b"][0] = 1.0

    def test_prefetch_warms_hot_versions(self, tmp_path):
        store = VersionStore(tmp_path)
        vids, _ = build_linear_history(store, n=6)
        for _ in range(5):
            store.checkout(vids[-1])
        store.checkout(vids[0])
        stats = store.repack("lmg", budget=store.storage_bytes() * 1.5,
                             use_access_frequencies=True)
        assert "gc_freed_bytes" in stats
        # hottest version is warm under the *new* storage graph
        m = store.materializer
        d0, f0 = m.delta_applies, m.full_decodes
        store.checkout(vids[-1])
        assert (m.delta_applies, m.full_decodes) == (d0, f0)


class TestAccessCountPersistence:
    def test_counts_flush_and_reload(self, tmp_path):
        # regression: checkout bumped access_count in memory only, so
        # repack(use_access_frequencies=True) saw all-zero counts on reload
        store = VersionStore(tmp_path, access_flush_every=4)
        vids, _ = build_linear_history(store, n=3)
        for _ in range(4):  # exactly the flush threshold
            store.checkout(vids[-1])
        del store
        reopened = VersionStore(tmp_path)
        assert reopened.versions[vids[-1]].access_count == 4

    def test_close_flushes_partial_counts(self, tmp_path):
        store = VersionStore(tmp_path, access_flush_every=1000)
        vids, _ = build_linear_history(store, n=3)
        store.checkout(vids[1])
        store.checkout(vids[1])
        store.close()
        reopened = VersionStore(tmp_path)
        assert reopened.versions[vids[1]].access_count == 2

    def test_repack_persists_counts(self, tmp_path):
        store = VersionStore(tmp_path, access_flush_every=1000)
        vids, _ = build_linear_history(store, n=4)
        for _ in range(3):
            store.checkout(vids[-1])
        store.repack("lmg", budget=store.storage_bytes() * 1.5,
                     use_access_frequencies=True)
        reopened = VersionStore(tmp_path)
        assert reopened.versions[vids[-1]].access_count == 3


class TestCycleGuards:
    def _corrupt(self, store, a, b):
        store.versions[a].stored_base = b
        store.versions[b].stored_base = a

    def test_recreation_cost_raises_on_cycle(self, tmp_path):
        # regression: recreation_cost walked stored_base with no bound and
        # looped forever on corrupted metadata
        store = VersionStore(tmp_path)
        vids, _ = build_linear_history(store, n=3)
        self._corrupt(store, vids[1], vids[2])
        with pytest.raises(RuntimeError, match="cycle"):
            store.recreation_cost(vids[2])

    def test_checkout_raises_on_cycle(self, tmp_path):
        store = VersionStore(tmp_path)
        vids, _ = build_linear_history(store, n=3)
        self._corrupt(store, vids[1], vids[2])
        with pytest.raises(RuntimeError, match="cycle"):
            store.checkout(vids[2])

    def test_checkout_many_raises_on_cycle(self, tmp_path):
        store = VersionStore(tmp_path)
        vids, _ = build_linear_history(store, n=3)
        self._corrupt(store, vids[1], vids[2])
        with pytest.raises(RuntimeError, match="cycle"):
            store.checkout_many([vids[0], vids[2]])


class TestRepackGC:
    def test_gc_freed_bytes_surfaced_and_no_dangling(self, tmp_path):
        store = VersionStore(tmp_path)
        vids, _ = build_linear_history(store, n=5)
        stats = store.repack("spt")  # rewrites all deltas as fulls
        assert stats["gc_freed_bytes"] > 0  # old delta objects reclaimed
        live = {m.object_key for m in store.log()}
        assert set(store.objects.keys()) == live  # nothing dangling
        # idempotent second repack frees nothing
        stats2 = store.repack("spt")
        assert stats2["gc_freed_bytes"] == 0
        assert set(store.objects.keys()) == {
            m.object_key for m in store.log()
        }


class TestPlanner:
    def test_plan_topological_and_deduplicated(self, tmp_path):
        store, vids, _ = build_branching_store(tmp_path, n=9, seed=3)
        planner = CheckoutPlanner(store)
        plan = planner.plan(vids)
        seen = set()
        for step in plan.steps:
            if step.base is not None:
                assert step.base in seen  # bases strictly before dependents
            assert step.vid not in seen  # each vid decoded at most once
            seen.add(step.vid)
        assert set(plan.requested) <= seen

    def test_plan_stops_at_cached_vids(self, tmp_path):
        store = VersionStore(tmp_path)
        vids, _ = build_linear_history(store, n=5)
        planner = CheckoutPlanner(store)
        full_plan = planner.plan([vids[-1]])
        short_plan = planner.plan([vids[-1]], cached=[vids[2]])
        assert short_plan.decode_count < full_plan.decode_count
        assert vids[2] in short_plan.from_cache
        assert all(s.vid not in (vids[0], vids[1], vids[2])
                   for s in short_plan.steps)

    def test_cache_standalone_lru_order(self):
        cache = MaterializationCache(budget_bytes=100)
        t = lambda: {"x": np.zeros(10, np.float32)}  # 40 bytes
        cache.put(1, t(), "fp1")
        cache.put(2, t(), "fp2")
        cache.get(1, "fp1")  # refresh 1: now 2 is LRU
        cache.put(3, t(), "fp3")  # over budget: evicts 2
        assert 1 in cache and 3 in cache and 2 not in cache


class TestFusedPipeline:
    """The fused device-resident chain path must be invisible semantically:
    trees bit-identical to a stepwise NumPy decode, decode counters that
    count each planned decode once, and a base evicted mid-flight rebuilt
    through the same executor."""

    @pytest.mark.parametrize("budget", [0, 256 << 20])
    def test_fused_equals_stepwise(self, tmp_path, budget):
        store, vids, _ = build_branching_store(
            tmp_path / "a", n=10, seed=11, cache_budget_bytes=budget
        )
        cold = VersionStore(tmp_path / "a", cache_budget_bytes=budget)
        for vid, got in zip(vids, cold.checkout_many(vids)):
            assert_trees_equal(got, checkout_ref(cold, vid))
        # every version decoded exactly once, counted as the walk would
        n_full = sum(cold.versions[v].stored_base is None for v in vids)
        s = cold.materializer.stats()
        assert (s["full_decodes"], s["delta_applies"]) == (
            n_full, len(vids) - n_full)
        assert (s["hits"], s["misses"]) == (0, len(vids))

    def test_whole_chain_fuses_at_zero_budget(self, tmp_path):
        # budget 0 and a single requested tip: the whole delta chain is one
        # segment — one fused launch wave, one delta apply per hop
        store = VersionStore(tmp_path, cache_budget_bytes=0)
        vids, _ = build_linear_history(store, n=6, shape=(64, 64))
        m = store.materializer
        d0, seg0 = m.delta_applies, m.fused_segments
        store.checkout(vids[-1])
        assert m.delta_applies - d0 == len(vids) - 1
        assert m.fused_segments - seg0 == 1

    @pytest.mark.parametrize("tree", ["dense", "grown"])
    @pytest.mark.parametrize("budget", [0, 256 << 20])
    def test_evicted_base_fallback(self, tmp_path, budget, tree):
        # a vid the planner saw as cached can be evicted before _execute
        # runs (concurrent checkouts sharing one cache); the executor
        # rebuilds it through _materialize_chain, itself a fused plan
        store, vids, tip = build_history(tmp_path, tree, budget=budget)
        m = store.materializer
        store.checkout(vids[2])  # warms the chain prefix through vids[2]
        plan = m.planner.plan([vids[-1]], cached=[vids[2]])
        assert vids[2] in plan.from_cache
        m.cache.purge()  # evict everything between plan and execute
        s0 = m.stats()
        trees = m._execute(plan)
        assert_trees_equal(trees[vids[-1]], tip)
        assert_trees_equal(trees[vids[-1]], checkout_ref(store, vids[-1]))
        # the rebuild decodes vids[0..2] once, the plan the rest
        s1 = m.stats()
        assert s1["full_decodes"] - s0["full_decodes"] == 1
        assert s1["delta_applies"] - s0["delta_applies"] == len(vids) - 1

    @pytest.mark.parametrize("budget", [0, 256 << 20])
    def test_64bit_leaves_bit_exact(self, tmp_path, budget):
        # float64/int64 leaves (NumPy's defaults for dataset columns) keep
        # every byte through commit -> delta -> checkout; with x64 off, an
        # upload before the byte view would narrow them to 32 bits.  The 0-d
        # step counter must keep its shape through the host byte view too
        rng = np.random.RandomState(3)
        col = rng.randn(5000)
        ids = rng.randint(2**40, 2**50, size=3000, dtype=np.int64)
        store = VersionStore(tmp_path, cache_budget_bytes=budget)
        trees, vids = [], []
        for step in range(4):
            col, ids = col.copy(), ids.copy()
            if step:
                # changes a float32 or int32 copy could not represent
                col[rng.randint(col.size, size=3)] *= 1 + 1e-12
                ids[rng.randint(ids.size, size=3)] += 1
            step_ctr = np.array(2**40 + step, np.int64)
            trees.append({"col": col, "ids": ids, "step": step_ctr})
            vids.append(store.commit(trees[-1], parents=vids[-1:]))
        assert all(store.versions[v].stored_base is not None for v in vids[1:])
        cold = VersionStore(tmp_path, cache_budget_bytes=budget)
        # the tip cold (whole chain), then every version in one batch
        got = [cold.checkout(vids[-1])] + cold.checkout_many(vids)
        for tree, want in zip(got, trees[-1:] + trees):
            for k in want:
                assert tree[k].dtype == want[k].dtype, k
                assert np.array_equal(tree[k], want[k]), k

    def test_fused_trees_frozen_and_cached(self, tmp_path):
        store = VersionStore(tmp_path)
        vids, _ = build_linear_history(store, n=4, shape=(64, 64))
        tree = store.checkout(vids[-1])
        with pytest.raises(ValueError):
            tree["w"][0, 0] = 1.0
        # warm-cache semantics: intermediates are cached too
        m = store.materializer
        d0, f0 = m.delta_applies, m.full_decodes
        store.checkout(vids[2])
        assert (m.delta_applies, m.full_decodes) == (d0, f0)
