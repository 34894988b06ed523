"""Per-kernel allclose tests vs the pure-jnp oracles (interpret mode).

Sweeps shapes and dtypes per the deliverable spec; hypothesis drives the
property tests (round-trips, idempotence, oracle equivalence).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.block_diff import block_hash, changed_block_mask, hash_coefficients
from repro.kernels.sparse_apply import sparse_delta_apply
from repro.kernels.xor_delta import xor_delta


def rand_blocks(rng, nb):
    return jnp.asarray(
        rng.randint(-(2**31), 2**31, size=(nb, 8, 128), dtype=np.int64).astype(np.int32)
    )


NB_SWEEP = [1, 2, 7, 64, 255, 256, 300]


class TestXorDelta:
    @pytest.mark.parametrize("nb", NB_SWEEP)
    def test_matches_oracle(self, nb):
        rng = np.random.RandomState(nb)
        a, b = rand_blocks(rng, nb), rand_blocks(rng, nb)
        got = xor_delta(a, b)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref.xor_delta_ref(a, b)))

    @pytest.mark.parametrize("rows", [1, 8, 64, 256])
    def test_block_shape_sweep(self, rows):
        rng = np.random.RandomState(rows)
        a, b = rand_blocks(rng, 128), rand_blocks(rng, 128)
        got = xor_delta(a, b, rows_per_program=rows)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref.xor_delta_ref(a, b)))

    def test_self_inverse(self):
        rng = np.random.RandomState(0)
        a, b = rand_blocks(rng, 33), rand_blocks(rng, 33)
        d = xor_delta(a, b)
        np.testing.assert_array_equal(np.asarray(xor_delta(a, d)), np.asarray(b))


class TestChangedBlockMask:
    @pytest.mark.parametrize("nb", NB_SWEEP)
    def test_matches_oracle(self, nb):
        rng = np.random.RandomState(nb)
        a = rand_blocks(rng, nb)
        b = a
        if nb > 2:
            b = a.at[jnp.asarray([0, nb // 2, nb - 1])].add(1)
        got = changed_block_mask(a, b)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(ref.changed_block_mask_ref(a, b))
        )

    def test_identical_inputs_all_zero(self):
        rng = np.random.RandomState(1)
        a = rand_blocks(rng, 50)
        assert int(jnp.sum(changed_block_mask(a, a))) == 0

    def test_single_bit_flip_detected(self):
        rng = np.random.RandomState(2)
        a = rand_blocks(rng, 20)
        b = a.at[13, 5, 77].set(a[13, 5, 77] ^ 1)
        m = np.asarray(changed_block_mask(a, b))[:, 0]
        assert m[13] == 1 and m.sum() == 1


class TestBlockHash:
    @pytest.mark.parametrize("nb", NB_SWEEP)
    def test_matches_oracle(self, nb):
        rng = np.random.RandomState(nb)
        x = rand_blocks(rng, nb)
        coef = jnp.asarray(hash_coefficients())
        np.testing.assert_array_equal(
            np.asarray(block_hash(x, coef)), np.asarray(ref.block_hash_ref(x, coef))
        )

    def test_equal_blocks_equal_hashes(self):
        rng = np.random.RandomState(3)
        x = rand_blocks(rng, 4)
        x = x.at[2].set(x[0])
        h = np.asarray(ops.block_hashes(x))
        assert h[2] == h[0]


class TestSparseApply:
    @pytest.mark.parametrize("nb,k", [(8, 3), (64, 1), (100, 37), (256, 256)])
    def test_matches_oracle(self, nb, k):
        rng = np.random.RandomState(nb * 1000 + k)
        base = rand_blocks(rng, nb)
        blocks = rand_blocks(rng, k)
        idx = jnp.asarray(rng.choice(nb, size=k, replace=False).astype(np.int32))
        got = sparse_delta_apply(base, blocks, idx)
        want = ref.sparse_delta_apply_ref(base, blocks, idx)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_padding_rows_ignored(self):
        rng = np.random.RandomState(9)
        base = rand_blocks(rng, 16)
        blocks = rand_blocks(rng, 4)
        idx = jnp.asarray([2, -1, 5, -1], dtype=jnp.int32)
        got = np.asarray(sparse_delta_apply(base, blocks, idx))
        want = np.array(base)  # writable copy
        want[2] = np.asarray(blocks[0])
        want[5] = np.asarray(blocks[2])
        np.testing.assert_array_equal(got, want)


DTYPES = ["float32", "bfloat16", "int32", "int8", "float16", "uint8"]


class TestBlockLayout:
    @pytest.mark.parametrize("dtype", DTYPES)
    @pytest.mark.parametrize("shape", [(3, 5), (1024,), (2, 8, 130), (4096, 3)])
    def test_roundtrip(self, dtype, shape):
        rng = np.random.RandomState(hash((dtype, shape)) % 2**31)
        if np.issubdtype(np.dtype(dtype if dtype != "bfloat16" else "float32"), np.floating):
            x = jnp.asarray(rng.randn(*shape), dtype=dtype)
        else:
            x = jnp.asarray(rng.randint(0, 100, size=shape), dtype=dtype)
        blocks, meta = ops.to_blocks(x)
        assert blocks.shape[1:] == (8, 128)
        y = ops.from_blocks(blocks, meta)
        assert y.dtype == x.dtype and y.shape == x.shape
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


class TestEndToEndDelta:
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_sparse_roundtrip_tensor(self, dtype):
        rng = np.random.RandomState(7)
        base_t = jnp.asarray(rng.randn(1000, 257), dtype=dtype)
        new_t = base_t.at[100:120].add(1.0)  # localized edit
        bb, meta = ops.to_blocks(base_t)
        nb_, _ = ops.to_blocks(new_t)
        mask, n = ops.count_changed(bb, nb_)
        idx, blocks = ops.compact(mask, nb_, n)
        assert 0 < n < bb.shape[0]
        rec = ops.sparse_apply(bb, blocks, idx)
        out = ops.from_blocks(rec, meta)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(new_t))

    def test_xor_roundtrip_tensor(self):
        rng = np.random.RandomState(8)
        a_t = jnp.asarray(rng.randn(513, 129), dtype=jnp.float32)
        b_t = a_t * 1.5
        ab, meta = ops.to_blocks(a_t)
        bb, _ = ops.to_blocks(b_t)
        delta = ops.xor_encode(ab, bb)
        rec = ops.from_blocks(ops.xor_apply(ab, delta), meta)
        np.testing.assert_array_equal(np.asarray(rec), np.asarray(b_t))


# ----------------------------------------------------------------- hypothesis
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st


@st.composite
def block_pairs(draw):
    nb = draw(st.integers(min_value=1, max_value=48))
    seed = draw(st.integers(min_value=0, max_value=2**31 - 1))
    rng = np.random.RandomState(seed)
    a = rand_blocks(rng, nb)
    n_edits = draw(st.integers(min_value=0, max_value=nb))
    rows = rng.choice(nb, size=n_edits, replace=False) if n_edits else []
    b = a
    for r in rows:
        b = b.at[int(r), rng.randint(8), rng.randint(128)].add(
            int(rng.randint(1, 1000))
        )
    return a, b, sorted(int(r) for r in rows)


class TestKernelProperties:
    @settings(max_examples=25, deadline=None)
    @given(block_pairs())
    def test_mask_identifies_exact_rows(self, pair):
        a, b, rows = pair
        m = np.asarray(changed_block_mask(a, b))[:, 0]
        # edits could be no-ops only if add(0), which we exclude
        assert sorted(np.nonzero(m)[0].tolist()) == rows

    @settings(max_examples=25, deadline=None)
    @given(block_pairs())
    def test_sparse_encode_apply_roundtrip(self, pair):
        a, b, rows = pair
        mask, n = ops.count_changed(a, b)
        idx, blocks = ops.compact(mask, b, n)
        assert n == len(rows)
        rec = ops.sparse_apply(a, blocks, idx)
        np.testing.assert_array_equal(np.asarray(rec), np.asarray(b))

    @settings(max_examples=25, deadline=None)
    @given(block_pairs())
    def test_xor_is_involution(self, pair):
        a, b, _ = pair
        d = ops.xor_encode(a, b)
        np.testing.assert_array_equal(
            np.asarray(ops.xor_apply(a, d)), np.asarray(b)
        )
        # delta of identical inputs is all-zero
        z = ops.xor_encode(a, a)
        assert int(jnp.sum(jnp.abs(z))) == 0
