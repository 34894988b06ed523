"""Regression tests for sparse_encode capacity overflow.

An explicit undersized ``capacity`` used to silently drop the overflowing
changed blocks in ``_compact``'s drop-mode scatter, producing a corrupt
delta that ``sparse_apply`` could not detect.  Kept separate from
``test_kernels.py``, whose module-level hypothesis gate skips the whole
file on containers without hypothesis.
"""

import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.block_diff import changed_block_mask


def _pair(nb=32, changed=12, seed=3):
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    a = jnp.asarray(
        rng.randint(-(2**31), 2**31, size=(nb, 8, 128), dtype=np.int64)
        .astype(np.int32)
    )
    b = a
    for r in range(changed):
        b = b.at[r, 0, 0].add(1)
    return a, b, changed


class TestSparseEncodeCapacity:
    def test_undersized_capacity_raises(self):
        a, b, changed = _pair()
        with pytest.raises(ValueError, match="capacity overflow"):
            ops.sparse_encode(a, b, capacity=changed // 2)

    def test_true_changed_count_propagates_from_compact(self):
        # the traced compaction itself must report the *true* count so
        # fully-traced callers can detect the overflow
        a, b, changed = _pair()
        mask = changed_block_mask(a, b)
        _, _, n = ops._compact(mask, b, capacity=4)
        assert int(n) == changed

    def test_sufficient_capacity_roundtrips(self):
        a, b, changed = _pair()
        idx, blocks, n = ops.sparse_encode(a, b, capacity=16)
        assert n == changed
        rec = ops.sparse_apply(a, blocks, idx)
        np.testing.assert_array_equal(np.asarray(rec), np.asarray(b))

    def test_auto_capacity_unaffected(self):
        # the commit path's capacity, sized from the exact changed count
        a, b, changed = _pair()
        mask, n = ops.count_changed(a, b)
        idx, blocks = ops.compact(mask, b, n)
        assert n == changed
        rec = ops.sparse_apply(a, blocks, idx)
        np.testing.assert_array_equal(np.asarray(rec), np.asarray(b))
