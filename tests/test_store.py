"""Integration tests: object store, delta codec, version store, repack,
versioned checkpointing with elastic restore."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint import PreemptionGuard, VersionedCheckpointManager
from repro.store import (
    ObjectStore,
    VersionStore,
    apply_delta_chain,
    decode_full,
    encode_delta,
    encode_full,
    flatten_payload,
)

from reference_decode import apply_delta_ref


def make_payload(rng, scale=1.0, shape=(64, 96)):
    return {
        "w": rng.randn(*shape).astype(np.float32) * scale,
        "b": rng.randn(shape[1]).astype(np.float32),
        "emb": {"table": rng.randn(128, 32).astype(np.float32)},
    }


def apply_hop(base, payload):
    """One delta hop through the store's decoder, which must agree with the
    NumPy reference decoder."""
    got = apply_delta_chain(base, [payload])
    want = apply_delta_ref(base, payload)
    assert set(got) == set(want)
    for k in want:
        assert got[k].dtype == want[k].dtype, k
        np.testing.assert_array_equal(got[k], want[k])
    return got


def perturb(payload, rng, frac=0.05):
    """Touch a small contiguous slice of each tensor (a localized edit)."""
    out = jax.tree.map(lambda x: x.copy(), payload)
    n = max(1, int(out["w"].shape[0] * frac))
    out["w"][:n] += rng.randn(n, out["w"].shape[1]).astype(np.float32)
    return out


class TestObjectStore:
    def test_roundtrip_and_dedup(self, tmp_path):
        st = ObjectStore(tmp_path)
        k1, s1 = st.put(b"hello world" * 1000)
        k2, s2 = st.put(b"hello world" * 1000)
        assert k1 == k2
        assert st.get(k1) == b"hello world" * 1000
        assert s1 < 11000  # zstd compressed
        assert len(list(st.keys())) == 1
        st.delete(k1)
        assert not st.exists(k1)


class TestDeltaCodec:
    def test_full_roundtrip(self):
        rng = np.random.RandomState(0)
        flat = flatten_payload(make_payload(rng))
        rec = decode_full(encode_full(flat))
        assert set(rec) == set(flat)
        for k in flat:
            np.testing.assert_array_equal(rec[k], flat[k])

    def test_delta_roundtrip_localized_edit(self):
        rng = np.random.RandomState(1)
        base = flatten_payload(make_payload(rng))
        new = flatten_payload(perturb(make_payload(rng), np.random.RandomState(1)))
        # rebuild identical base for a valid delta
        base = flatten_payload(make_payload(np.random.RandomState(1)))
        new_p = perturb(make_payload(np.random.RandomState(1)), np.random.RandomState(2))
        new = flatten_payload(new_p)
        payload, stats = encode_delta(base, new)
        assert stats["changed_blocks"] < stats["total_blocks"]
        rec = apply_hop(base, payload)
        for k in new:
            np.testing.assert_array_equal(rec[k], new[k])

    def test_delta_much_smaller_than_full(self):
        rng = np.random.RandomState(2)
        base_p = make_payload(rng, shape=(512, 512))
        new_p = perturb(base_p, rng, frac=0.02)
        base, new = flatten_payload(base_p), flatten_payload(new_p)
        d, _ = encode_delta(base, new)
        f = encode_full(new)
        assert len(d) < 0.2 * len(f)

    def test_delta_handles_new_and_deleted_leaves(self):
        rng = np.random.RandomState(3)
        base = flatten_payload(make_payload(rng))
        new = dict(base)
        del new["b"]
        new["extra"] = rng.randn(7, 7).astype(np.float32)
        payload, _ = encode_delta(base, new)
        rec = apply_hop(base, payload)
        assert "b" not in rec and "extra" in rec
        np.testing.assert_array_equal(rec["extra"], new["extra"])

    def test_delta_handles_reshaped_leaf(self):
        rng = np.random.RandomState(4)
        base = flatten_payload(make_payload(rng))
        new = dict(base)
        new["w"] = rng.randn(10, 10).astype(np.float32)
        rec = apply_hop(base, encode_delta(base, new)[0])
        np.testing.assert_array_equal(rec["w"], new["w"])


def build_linear_history(store, n=6, shape=(256, 256), seed=0):
    rng = np.random.RandomState(seed)
    payload = make_payload(rng, shape=shape)
    vids = [store.commit(payload, message="v1")]
    for i in range(n - 1):
        payload = perturb(payload, rng, frac=0.03)
        vids.append(store.commit(payload, parents=[vids[-1]], message=f"v{i+2}"))
    return vids, payload


class TestVersionStore:
    def test_commit_checkout_chain(self, tmp_path):
        store = VersionStore(tmp_path)
        vids, last_payload = build_linear_history(store)
        rec = store.checkout(vids[-1])
        want = flatten_payload(last_payload)
        for k in want:
            np.testing.assert_array_equal(rec[k], want[k])
        # deltas must be much smaller than fulls
        metas = store.log()
        assert metas[0].stored_base is None
        assert all(m.stored_base is not None for m in metas[1:])
        assert sum(m.stored_bytes for m in metas[1:]) < metas[0].stored_bytes

    def test_branch_and_merge(self, tmp_path):
        store = VersionStore(tmp_path)
        rng = np.random.RandomState(1)
        p0 = make_payload(rng)
        v1 = store.commit(p0)
        pa = perturb(p0, rng)
        va = store.commit(pa, parents=[v1])
        pb = perturb(p0, rng)
        vb = store.commit(pb, parents=[v1])
        merged = jax.tree.map(lambda a, b: (a + b) / 2, pa, pb)
        vm = store.commit(merged, parents=[va, vb])
        rec = store.checkout(vm)
        want = flatten_payload(merged)
        for k in want:
            np.testing.assert_array_equal(rec[k], want[k])
        assert store.versions[vm].parents == [va, vb]

    def test_persistence_across_reopen(self, tmp_path):
        store = VersionStore(tmp_path)
        vids, last_payload = build_linear_history(store, n=3)
        del store
        store2 = VersionStore(tmp_path)
        rec = store2.checkout(vids[-1])
        want = flatten_payload(last_payload)
        for k in want:
            np.testing.assert_array_equal(rec[k], want[k])

    @pytest.mark.parametrize("solver,kw", [
        ("mca", {}),
        ("spt", {}),
        ("last", {"alpha": 2.0}),
        ("gith", {"window": 5, "max_depth": 5}),
    ])
    def test_repack_preserves_contents(self, tmp_path, solver, kw):
        store = VersionStore(tmp_path)
        vids, _ = build_linear_history(store, n=5)
        originals = {v: store.checkout(v) for v in vids}
        store.repack(solver, **kw)
        for v in vids:
            rec = store.checkout(v)
            for k in originals[v]:
                np.testing.assert_array_equal(rec[k], originals[v][k])

    def test_repack_spt_materializes_everything(self, tmp_path):
        store = VersionStore(tmp_path)
        vids, _ = build_linear_history(store, n=4)
        stats = store.repack("spt")
        assert all(m.stored_base is None for m in store.log())
        assert stats["after"]["max_recreation_s"] <= stats["before"]["max_recreation_s"] + 1e-9

    def test_repack_mp_enforces_theta(self, tmp_path):
        store = VersionStore(tmp_path)
        vids, _ = build_linear_history(store, n=8)
        # find the SPT bound then ask for 1.5x it
        g, _ = store.build_cost_graph()
        from repro.core import shortest_path_tree
        theta = shortest_path_tree(g).max_recreation() * 1.5
        store.repack("mp", theta=theta)
        worst = max(store.recreation_cost(v) for v in store.versions)
        assert worst <= theta + 1e-9

    def test_repack_lmg_under_budget(self, tmp_path):
        store = VersionStore(tmp_path)
        vids, _ = build_linear_history(store, n=8)
        g, _ = store.build_cost_graph()
        from repro.core import minimum_storage_tree
        budget = minimum_storage_tree(g).storage_cost() * 1.5
        before_sum = sum(store.recreation_cost(v) for v in store.versions)
        store.repack("lmg", budget=budget)
        after_sum = sum(store.recreation_cost(v) for v in store.versions)
        assert after_sum <= before_sum + 1e-9

    def test_gc_reclaims_orphans(self, tmp_path):
        store = VersionStore(tmp_path)
        build_linear_history(store, n=4)
        store.repack("spt")  # rewrites everything as fulls
        # gc ran inside repack: only live objects remain
        live = {m.object_key for m in store.log()}
        assert set(store.objects.keys()) == live

    def test_repack_empty_store(self, tmp_path):
        # regression: used to crash on max() over the empty version set
        store = VersionStore(tmp_path)
        stats = store.repack("spt")
        zero = {"storage_bytes": 0, "sum_recreation_s": 0.0,
                "max_recreation_s": 0.0}
        assert stats == {"before": zero, "after": zero, "gc_freed_bytes": 0}
        assert store.versions == {}

    def test_content_fp_stable_across_checkout_reencode(self, tmp_path):
        # regression: encode_full serialized leaves in dict insertion order,
        # so a checkout (base-order + appended full leaves) re-encoded to
        # different bytes than the commit path and the fp-keyed Δ/Φ edge
        # cache was spuriously invalidated
        import hashlib

        store = VersionStore(tmp_path)
        rng = np.random.RandomState(0)
        p1 = make_payload(rng)
        v1 = store.commit(p1, message="v1")
        # v2 must be stored as a *delta* (checkout then rebuilds the tree in
        # delta-decode order): small perturbation + one added leaf whose key
        # sorts before the base keys
        p2 = perturb(p1, rng, frac=0.03)
        p2["aa_added"] = rng.randn(16).astype(np.float32)
        v2 = store.commit(p2, parents=[v1], message="v2")
        assert store.versions[v2].stored_base == v1
        for v in (v1, v2):
            flat = store.checkout(v)
            refp = hashlib.sha256(encode_full(flat)).hexdigest()
            assert refp == store.versions[v].content_fp


class TestVersionedCheckpointing:
    def _state(self, rng):
        return {
            "params": {"w": jnp.asarray(rng.randn(128, 64), jnp.float32)},
            "opt": {"mu": jnp.asarray(rng.randn(128, 64), jnp.float32),
                    "nu": jnp.asarray(rng.randn(128, 64), jnp.float32)},
            "step": jnp.asarray(0, jnp.int32),
        }

    def test_save_restore_roundtrip(self, tmp_path):
        rng = np.random.RandomState(0)
        mgr = VersionedCheckpointManager(tmp_path)
        state = self._state(rng)
        for step in range(3):
            state = jax.tree.map(
                lambda x: x + 1 if x.dtype == jnp.int32 else x * 1.01, state
            )
            mgr.save(step, state)
        mgr.wait()
        restored = mgr.restore(template=state)
        for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert mgr.latest_step() == 2
        mgr.close()

    def test_restore_with_resharding(self, tmp_path):
        rng = np.random.RandomState(1)
        mgr = VersionedCheckpointManager(tmp_path)
        state = self._state(rng)
        mgr.save(0, state, blocking=True)
        sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
        restored = mgr.restore(template=state, shardings=sharding)
        assert all(
            leaf.sharding == sharding for leaf in jax.tree.leaves(restored)
        )
        mgr.close()

    def test_emergency_save_is_synchronous(self, tmp_path):
        rng = np.random.RandomState(2)
        mgr = VersionedCheckpointManager(tmp_path)
        guard = PreemptionGuard()
        state = self._state(rng)
        guard.trigger()
        assert guard.preempted
        vid = mgr.emergency_save(7, state)
        assert mgr.store.versions[vid].message.startswith("EMERGENCY")
        restored = mgr.restore(template=state, step=7)
        np.testing.assert_array_equal(
            np.asarray(restored["params"]["w"]), np.asarray(state["params"]["w"])
        )
        mgr.close()

    def test_repack_enforces_restore_sla(self, tmp_path):
        rng = np.random.RandomState(3)
        mgr = VersionedCheckpointManager(tmp_path, max_restore_cost_s=10.0)
        state = self._state(rng)
        for step in range(6):
            state = jax.tree.map(lambda x: x * 1.001, state)
            mgr.save(step, state)
        mgr.wait()
        stats = mgr.repack()
        assert stats["after"]["max_recreation_s"] <= 10.0
        restored = mgr.restore(template=state)
        np.testing.assert_array_equal(
            np.asarray(restored["opt"]["mu"]), np.asarray(state["opt"]["mu"])
        )
        mgr.close()
