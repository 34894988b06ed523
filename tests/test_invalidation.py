"""Append-aware cache invalidation semantics, pinned end to end.

The load-bearing store property of the service tier: cache entries carry a
fingerprint of *their own* decode chain (the ``(vid, stored_base,
object_key)`` triples down to the full root).  Contract under test:

* a **commit** appends versions but rewrites no existing chain, so it must
  not evict any warm entry it can't reach — on any branch, under any
  parent topology, with zero invalidation counters moving;
* a **repack** rewrites chains wholesale and must still purge everything;
* correctness is pinned against the stepwise NumPy reference decoder:
  whatever the cache keeps or drops, served trees stay bit-identical to a
  from-scratch decode of the same storage graph;
* **chain fingerprints** change exactly when the entry's own chain
  changes (metadata edits to *other* chains leave them fixed) and the
  stale entry is dropped lazily at lookup;
* the byte-budget LRU keeps working unchanged underneath the tags.
"""

import numpy as np
import pytest

from repro.core import OptimizeSpec
from repro.store import VersionStore

from reference_decode import checkout_ref


def payload(seed: int, shape=(64, 48)):
    # large enough to span several delta blocks: a 2-row perturbation then
    # encodes smaller than full, so commits actually store delta chains
    rng = np.random.RandomState(seed)
    return {"w": rng.randn(*shape).astype(np.float32)}


def perturbed(base, seed: int):
    rng = np.random.RandomState(seed)
    out = {"w": base["w"].copy()}
    out["w"][:2] += rng.randn(2, base["w"].shape[1]).astype(np.float32)
    return out


def build_branching(tmp_path, **kwargs):
    """Two branches off a shared root: A = 1->2->3, B = 1->4->5."""
    store = VersionStore(tmp_path, **kwargs)
    p1 = payload(0)
    v1 = store.commit(p1, message="root")
    pa = perturbed(p1, 1)
    va = store.commit(pa, parents=[v1], message="a1")
    pa2 = perturbed(pa, 2)
    va2 = store.commit(pa2, parents=[va], message="a2")
    pb = perturbed(p1, 3)
    vb = store.commit(pb, parents=[v1], message="b1")
    pb2 = perturbed(pb, 4)
    vb2 = store.commit(pb2, parents=[vb], message="b2")
    trees = {v1: p1, va: pa, va2: pa2, vb: pb, vb2: pb2}
    return store, trees, (v1, va, va2, vb, vb2)


class TestCommitKeepsWarmEntries:
    def test_commit_on_other_branch_keeps_entries_warm(self, tmp_path):
        store, trees, (v1, va, va2, vb, vb2) = build_branching(tmp_path)
        store.checkout_many([va2, vb2])  # warm both branch tips

        # commit onto branch B: branch A's warm tip is unreachable from it
        # (the commit itself materializes its parent, hence s0 comes after)
        store.commit(perturbed(trees[vb2], 9), parents=[vb2], message="b3")
        s0 = store.materializer.stats()

        t = store.checkout(va2)
        s1 = store.materializer.stats()
        assert np.array_equal(t["w"], trees[va2]["w"])
        assert s1["hits"] == s0["hits"] + 1  # served from cache
        assert s1["invalidations"] == s0["invalidations"] == 0
        assert s1["purges"] == 0
        assert s1["full_decodes"] == s0["full_decodes"]

    def test_fifty_commits_zero_invalidation(self, tmp_path):
        store, trees, vids = build_branching(tmp_path)
        hot = list(vids)
        store.checkout_many(hot)
        tip = vids[-1]
        last = trees[tip]
        for i in range(50):
            last = perturbed(last, 100 + i)
            tip = store.commit(last, parents=[tip], message=f"append {i}")
        s0 = store.materializer.stats()
        for v in hot:
            assert np.array_equal(store.checkout(v)["w"], trees[v]["w"])
        s1 = store.materializer.stats()
        assert s1["hits"] - s0["hits"] == len(hot)
        assert s1["invalidations"] == 0 and s1["purges"] == 0


class TestRepackPurges:
    def test_repack_purges_wholesale_and_trees_survive(self, tmp_path):
        store, trees, vids = build_branching(tmp_path)
        store.checkout_many(list(vids))
        assert store.materializer.stats()["entries"] == len(vids)

        store.repack(OptimizeSpec.problem(2))

        s = store.materializer.stats()
        assert s["purges"] >= 1
        assert s["entries"] == 0
        for v, want in trees.items():
            assert np.array_equal(store.checkout(v)["w"], want["w"])

    def test_chain_fingerprints_rotate_exactly_with_chain_changes(
        self, tmp_path
    ):
        def triples(store, v):
            out = []
            while v is not None:
                m = store.versions[v]
                out.append((v, m.stored_base, m.object_key))
                v = m.stored_base
            return tuple(out)

        store, trees, vids = build_branching(tmp_path)
        before_fp = {v: store.chain_fingerprint(v) for v in vids}
        before_ch = {v: triples(store, v) for v in vids}
        # min-recreation (SPT): flattens chains, rewriting delta storage
        store.repack(OptimizeSpec.problem(2))
        changed = [v for v in vids if triples(store, v) != before_ch[v]]
        assert changed  # the branching build always stores some deltas
        for v in vids:
            rotated = store.chain_fingerprint(v) != before_fp[v]
            assert rotated == (v in changed), v


class TestOracleParity:
    def test_chain_mode_matches_stepwise_zero_budget_oracle(self, tmp_path):
        """Whatever the tagged cache keeps across commits, served bytes must
        equal a stepwise from-scratch decode of the same storage graph."""
        root = tmp_path / "store"
        store = VersionStore(root, cache_budget_bytes=64 << 20)
        rng = np.random.RandomState(7)
        tree = payload(7)
        tip = store.commit(tree, message="root")
        vids = [tip]
        for i in range(20):
            tree = perturbed(tree, 200 + i)
            if i % 5 == 4:  # occasional branch off an older version
                parent = vids[rng.randint(0, len(vids))]
                tip = store.commit(tree, parents=[int(parent)],
                                   message=f"branch {i}")
            else:
                tip = store.commit(tree, parents=[tip], message=f"c{i}")
            vids.append(tip)
            # interleave reads so the cache is warm while the graph grows
            store.checkout(vids[rng.randint(0, len(vids))])

        for v in vids:
            got = store.checkout(v)
            want = checkout_ref(store, v)
            assert set(got) == set(want)
            for k in want:
                assert np.array_equal(got[k], want[k]), (v, k)

    def test_stale_entry_dropped_lazily_on_lookup(self, tmp_path):
        store, trees, vids = build_branching(tmp_path)
        v = vids[2]
        store.checkout(v)  # warm
        m = store.materializer
        assert m.probe(v)
        # simulate an out-of-band chain rewrite: the entry's tag no longer
        # matches its chain fingerprint and must be dropped at lookup
        meta = store.versions[v]
        object.__setattr__(meta, "object_key", meta.object_key)
        with m.cache._lock:
            tree, nbytes, _ = m.cache._entries[v]
            m.cache._entries[v] = (tree, nbytes, "stale-tag")
        assert not m.probe(v)
        s0 = m.stats()
        t = store.checkout(v)
        s1 = m.stats()
        assert np.array_equal(t["w"], trees[v]["w"])
        assert s1["invalidations"] == s0["invalidations"] + 1
        assert m.probe(v)  # rebuilt and re-tagged

    def test_prefetch_rewarms_stale_tagged_entry(self, tmp_path):
        """Regression: prefetch skipped any vid already *present* in the
        cache, so a stale-tagged entry (which get() would reject) blocked
        re-warming that vid forever."""
        store, trees, vids = build_branching(tmp_path)
        v = vids[2]
        store.checkout(v)  # warm
        m = store.materializer
        with m.cache._lock:
            tree, nbytes, _ = m.cache._entries[v]
            m.cache._entries[v] = (tree, nbytes, "stale-tag")
        assert not m.probe(v)
        assert m.prefetch([v]) == 1  # was 0: bare containment skipped it
        assert m.probe(v)
        assert np.array_equal(store.checkout(v)["w"], trees[v]["w"])


class TestEvictionUnchanged:
    def test_lru_byte_budget_still_enforced(self, tmp_path):
        one_entry = 64 * 48 * 4  # payload bytes per version
        store, trees, vids = build_branching(
            tmp_path, cache_budget_bytes=int(one_entry * 2.5)
        )
        for v in vids:
            store.checkout(v)
        s = store.materializer.stats()
        assert s["evictions"] >= len(vids) - 2
        assert s["current_bytes"] <= int(one_entry * 2.5)
        # evicted versions still decode correctly
        for v, want in trees.items():
            assert np.array_equal(store.checkout(v)["w"], want["w"])

    def test_checkout_many_survives_eviction_between_plan_and_execute(
        self, tmp_path
    ):
        """A planned-cached vid whose entry vanished must rebuild, not
        assert: pin the fallback path by evicting mid-sequence."""
        store, trees, vids = build_branching(tmp_path)
        store.checkout_many(list(vids))
        # drop one entry behind the planner's back
        victim = vids[2]
        with store.materializer.cache._lock:
            ent = store.materializer.cache._entries.pop(victim)
            store.materializer.cache.current_bytes -= ent[1]
        out = store.checkout_many(list(vids))
        for t, v in zip(out, vids):
            assert np.array_equal(t["w"], trees[v]["w"])


class TestChainFingerprint:
    def test_fingerprint_depends_only_on_own_chain(self, tmp_path):
        store, trees, (v1, va, va2, vb, vb2) = build_branching(tmp_path)
        fa = store.chain_fingerprint(va2)
        fb = store.chain_fingerprint(vb2)
        assert fa != fb
        # recomputation is deterministic
        assert store.chain_fingerprint(va2) == fa

    def test_fingerprint_detects_cycle(self, tmp_path):
        store, trees, vids = build_branching(tmp_path)
        v = vids[2]
        store.versions[v].stored_base = v  # corrupt: self-cycle
        with pytest.raises(RuntimeError, match="cycle"):
            store.chain_fingerprint(v)

    def test_fingerprint_survives_reopen(self, tmp_path):
        store, trees, vids = build_branching(tmp_path)
        fps = {v: store.chain_fingerprint(v) for v in vids}
        store.close()
        reopened = VersionStore(tmp_path)
        for v, fp in fps.items():
            assert reopened.chain_fingerprint(v) == fp
