"""Fused delta-chain pipeline tests: kernel vs stepwise oracle, wire decode
round-trips, and a randomized chain property suite (mixed sparse/full/
tombstone steps, all dtypes incl. bfloat16) asserting the fused path is
bit-identical to the NumPy reference decoder applied step by step."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.chain_apply import chain_delta_apply, chain_delta_apply_batched
from repro.kernels.ref import chain_delta_apply_ref
from repro.store import delta as D

from reference_decode import apply_delta_ref, decode_chain_ref


def _rand_blocks(rng, n):
    return jnp.asarray(rng.randint(-(2**31), 2**31 - 1, (n, 8, 128), np.int64).astype(np.int32))


class TestChainKernel:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    @pytest.mark.parametrize("k,cap", [(1, 8), (4, 8), (7, 16)])
    def test_matches_stepwise_oracle(self, seed, k, cap):
        # random chains with duplicate rows across steps (later must win)
        # and -1 padding interleaved
        rng = np.random.RandomState(seed)
        nb = 24
        base = _rand_blocks(rng, nb)
        idx = rng.randint(0, nb, (k, cap)).astype(np.int32)
        idx[rng.rand(k, cap) < 0.4] = -1
        blocks = _rand_blocks(rng, k * cap).reshape(k, cap, 8, 128)
        want = chain_delta_apply_ref(base, blocks, jnp.asarray(idx))
        got = chain_delta_apply(base, blocks, jnp.asarray(idx))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_empty_and_all_padding(self):
        rng = np.random.RandomState(3)
        base = _rand_blocks(rng, 6)
        empty = chain_delta_apply(
            base, jnp.zeros((0, 8, 128), jnp.int32), jnp.zeros((0,), jnp.int32)
        )
        np.testing.assert_array_equal(np.asarray(empty), np.asarray(base))
        pad = chain_delta_apply(
            base, _rand_blocks(rng, 8), jnp.full((8,), -1, jnp.int32)
        )
        np.testing.assert_array_equal(np.asarray(pad), np.asarray(base))

    @pytest.mark.parametrize("seed", [0, 4])
    def test_batched_matches_per_leaf(self, seed):
        rng = np.random.RandomState(seed)
        l, nb, s = 3, 16, 8
        bases = _rand_blocks(rng, l * nb).reshape(l, nb, 8, 128)
        idx = rng.randint(0, nb, (l, s)).astype(np.int32)
        idx[rng.rand(l, s) < 0.3] = -1
        blocks = _rand_blocks(rng, l * s).reshape(l, s, 8, 128)
        got = chain_delta_apply_batched(bases, blocks, jnp.asarray(idx))
        for i in range(l):
            want = chain_delta_apply_ref(
                bases[i], blocks[i], jnp.asarray(idx[i])
            )
            np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(want))


# ------------------------------------------------------- randomized chains
_DTYPES = [np.float32, np.int8, "bfloat16"]


def _rand_leaf(rng, shape, dtype):
    if dtype == "bfloat16":
        return jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    if np.issubdtype(np.dtype(dtype), np.floating):
        return rng.randn(*shape).astype(dtype)
    return rng.randint(-100, 100, shape).astype(dtype)


def _as_np(a):
    return np.asarray(a, np.float32) if a.dtype == jnp.bfloat16 else np.asarray(a)


def _random_chain(rng, steps=5):
    """A base tree plus ``steps`` encoded deltas with mixed leaf events:
    sparse edits, whole-leaf rewrites (reshape), tombstones, new leaves."""
    shapes = [(40, 64), (128,), (16, 16, 4)]
    base = {
        f"leaf{i}": _rand_leaf(rng, shapes[i % len(shapes)], _DTYPES[i % len(_DTYPES)])
        for i in range(4)
    }
    payloads, cur = [], base
    for _ in range(steps):
        new = {k: np.asarray(v).copy() for k, v in cur.items()}
        for k in list(new):
            r = rng.rand()
            if r < 0.35:  # sparse edit: bump one element
                flat = new[k].reshape(-1)
                flat[rng.randint(0, flat.size)] += np.asarray(1, flat.dtype)
            elif r < 0.45:  # full rewrite: reshape/dtype change
                new[k] = _rand_leaf(
                    rng, (rng.randint(4, 32), 8), _DTYPES[rng.randint(0, 3)]
                )
            elif r < 0.52 and len(new) > 1:  # tombstone
                del new[k]
        if rng.rand() < 0.3:  # new leaf mid-chain
            new[f"new{rng.randint(0, 1000)}"] = _rand_leaf(rng, (8, 8), np.float32)
        payloads.append(D.encode_delta(cur, new)[0])
        cur = new
    return base, payloads


def _assert_trees_bitequal(a, b):
    assert set(a) == set(b), set(a) ^ set(b)
    for k in a:
        assert a[k].dtype == b[k].dtype and a[k].shape == b[k].shape, k
        np.testing.assert_array_equal(_as_np(a[k]), _as_np(b[k]), err_msg=k)


class TestChainProperty:
    @pytest.mark.parametrize("seed", range(6))
    def test_fused_bitidentical_to_stepwise(self, seed):
        rng = np.random.RandomState(seed)
        base, payloads = _random_chain(rng, steps=rng.randint(1, 8))
        want = decode_chain_ref(base, payloads)
        got = D.apply_delta_chain(base, payloads)
        _assert_trees_bitequal(want, got)

    def test_batched_requests_independent(self):
        rng = np.random.RandomState(99)
        chains = [_random_chain(rng, steps=4) for _ in range(3)]
        results = D.apply_delta_chains([(b, p, None) for b, p in chains])
        for (b, p), (tree, blocked) in zip(chains, results):
            _assert_trees_bitequal(decode_chain_ref(b, p), tree)
            # blocked companion covers exactly the kernel-path leaves
            for k, (blk, meta) in blocked.items():
                np.testing.assert_array_equal(
                    _as_np(np.asarray(ops.from_blocks(blk, meta))),
                    _as_np(tree[k]),
                )

    def test_base_blocked_memo_reused(self):
        rng = np.random.RandomState(5)
        base, payloads = _random_chain(rng, steps=3)
        blocked = {
            k: ops.to_blocks(jnp.asarray(v)) for k, v in base.items()
        }
        got = D.apply_delta_chain(base, payloads, base_blocked=blocked)
        _assert_trees_bitequal(decode_chain_ref(base, payloads), got)


class TestDeltaWire:
    def test_roundtrip_matches_bytes_path(self):
        rng = np.random.RandomState(1)
        base, payloads = _random_chain(rng, steps=4)
        cur = base
        for p in payloads:
            wire = D.decode_delta_wire(p)
            want = apply_delta_ref(cur, p)
            _assert_trees_bitequal(want, D.apply_delta_chain(cur, [p]))
            _assert_trees_bitequal(want, D.apply_delta_chain(cur, [wire]))
            cur = want

    def test_wire_fields(self):
        base = {"a": np.ones((8, 8), np.float32), "b": np.ones(4, np.float32)}
        new = {"a": base["a"].copy(), "c": np.zeros(3, np.float32)}
        new["a"][0, 0] = 2.0
        payload, _ = D.encode_delta(base, new)
        wire = D.decode_delta_wire(payload)
        assert wire.tombstones == frozenset({"b"})
        assert set(wire.full) == {"c"}
        assert wire.sparse["a"].n >= 1
        assert wire.sparse["a"].idx.shape == (wire.sparse["a"].n,)
        assert wire.sparse["a"].blocks.shape == (wire.sparse["a"].n, 8, 128)

    def test_corrupt_chain_raises(self):
        # a sparse delta for a leaf the running tree doesn't hold is
        # corruption, not silently-skippable data
        base = {"a": np.ones((8, 8), np.float32)}
        new = dict(base, a=base["a"] + 1)
        payload, _ = D.encode_delta(base, new)
        with pytest.raises(ValueError, match="corrupt chain"):
            D.apply_delta_chain({"other": np.ones(4, np.float32)}, [payload])


class TestSlotBucketing:
    def test_bucket_pow2_min8(self):
        assert [D._slot_bucket(n) for n in (1, 7, 8, 9, 64, 65)] == [
            8, 8, 8, 16, 64, 128,
        ]

    def test_same_bucket_shares_group(self):
        # two leaves with equal (num_blocks, slot_bucket) must land in one
        # launch: the whole point of capacity bucketing
        rng = np.random.RandomState(2)
        base = {
            "x": rng.randn(40, 64).astype(np.float32),
            "y": rng.randn(40, 64).astype(np.float32),
        }
        new = {k: v.copy() for k, v in base.items()}
        new["x"][0, 0] += 1
        new["y"][3, 0] += 1
        payload, _ = D.encode_delta(base, new)
        stats = {}
        D.apply_delta_chains([(base, [payload], None)], stats=stats)
        assert stats["launches"] == 1
