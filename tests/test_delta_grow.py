"""Leaves whose leading axis grows between versions (rows appended to a
table's columns) are stored as block-sparse deltas and rebuilt bit for bit
by every read path: the fused chain, the materializer and fsck, and by the
NumPy reference decoder.  The reference save is plain NumPy: the parent's
rows with the new rows concatenated, then the updated rows scattered in."""

import ml_dtypes
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.block_diff import changed_block_mask
from repro.store import VersionStore
from repro.store.delta import (
    apply_delta_chain,
    decode_delta_wire,
    encode_delta,
)

from reference_decode import apply_delta_ref, decode_chain_ref

BF16 = np.dtype(ml_dtypes.bfloat16)

# (dtype, trailing shape): leaves of a table (a uint8 column of 100 B values,
# an int64 key column) and of a checkpoint (a bf16 matrix)
LEAVES = {"uint8": (np.uint8, (100,)), "int64": (np.int64, ()), "bf16": (BF16, (64,))}
# (rows, rows appended) per leaf: inside the parent's last partial block,
# across it, and across a boundary of the padded block count of a leaf of
# 16 blocks or more (ops.grown_blocks)
GROWTH = {
    "inside_last_block": {"uint8": (50, 10), "int64": (100, 100), "bf16": (10, 5)},
    "across_last_block": {"uint8": (50, 40), "int64": (100, 500), "bf16": (10, 30)},
    "across_bucket": {"uint8": (2700, 300), "int64": (8100, 200), "bf16": (512, 88)},
}


def _values(rng, dtype, shape):
    if dtype == BF16:
        return rng.standard_normal(shape).astype(BF16)
    return rng.integers(0, np.iinfo(dtype).max, shape, dtype=dtype, endpoint=True)


def _grow(rng, a, rows, updates=3):
    """The NumPy reference of a save: ``rows`` new rows concatenated to
    ``a``, then ``updates`` of its old rows rewritten."""
    out = np.concatenate([a, _values(rng, a.dtype, (rows,) + a.shape[1:])])
    at = rng.choice(len(a), min(updates, len(a)), replace=False)
    out[at] = _values(rng, a.dtype, (len(at),) + a.shape[1:])
    return out


def _equal(got, want):
    assert set(got) == set(want)
    for k in want:
        assert got[k].dtype == want[k].dtype and got[k].shape == want[k].shape, k
        assert np.ascontiguousarray(got[k]).tobytes() == want[k].tobytes(), k


def _blocks(a):
    return ops.num_blocks_of(a.nbytes)


@pytest.mark.parametrize("growth", GROWTH)
@pytest.mark.parametrize("leaf", LEAVES)
def test_grown_leaf_reads_back_through_every_path(leaf, growth, tmp_path):
    dtype, trailing = LEAVES[leaf]
    rows, grow = GROWTH[growth][leaf]
    rng = np.random.default_rng(7)
    # beside a large leaf of the same shape, which one element of v2 changes,
    # so that the store keeps every save as a delta
    same = np.arange(200_000, dtype=np.float32)
    v0 = {"leaf": _values(rng, dtype, (rows,) + trailing), "same": same}
    v1 = {"leaf": _grow(rng, v0["leaf"], grow), "same": same}
    v2 = {"leaf": _grow(rng, v1["leaf"], 3), "same": np.where(same == 7, -1, same)}
    n0, n1 = _blocks(v0["leaf"]), _blocks(v1["leaf"])
    if growth == "inside_last_block":
        assert n1 == n0
    elif growth == "across_last_block":
        assert n1 > n0
    else:
        assert n0 >= 16 and ops.grown_blocks(n1) > ops.grown_blocks(n0)

    p1, s1 = encode_delta(v0, v1)
    p2, s2 = encode_delta(v1, v2)
    assert (s1["grown_leaves"], s1["full_leaves"]) == (1, 0)
    assert s1["total_blocks"] == n1 + _blocks(v0["same"])
    # the stored delta holds the changed and appended blocks and the shape:
    # 3 updated rows, each in one block or two, the parent's last block and
    # the blocks past it
    wire = decode_delta_wire(p1)
    assert wire.full == {} and wire.sparse["leaf"].shape == v1["leaf"].shape
    assert wire.sparse["same"].shape is None
    d = wire.sparse["leaf"]
    assert 0 < d.n <= 2 * 3 + 1 + n1 - n0 and np.all(d.idx < n1)

    _equal(apply_delta_ref(v0, p1), v1)
    _equal(decode_chain_ref(v0, [p1, p2]), v2)
    _equal(apply_delta_chain(v0, [p1]), v1)
    _equal(apply_delta_chain(v0, [p1, p2]), v2)

    store = VersionStore(tmp_path, cache_budget_bytes=0)
    vids = [store.commit(v0)]
    for v in (v1, v2):
        vids.append(store.commit(v, parents=[vids[-1]]))
    assert all(store.versions[v].stored_base == p for p, v in zip(vids, vids[1:]))
    for vid, want in zip(vids, (v0, v1, v2)):
        _equal(store.checkout(vid), want)
    # one plan: v2's chain starts from v1's blocked form, which an across-
    # bucket growth has to pad again
    for got, want in zip(store.checkout_many(vids), (v0, v1, v2)):
        _equal(got, want)


@pytest.mark.parametrize("leaf", LEAVES)
def test_grown_leaf_with_no_block_changed(leaf):
    """Zero rows appended inside the last partial block change no block:
    the delta holds no block, only the new shape."""
    dtype, trailing = LEAVES[leaf]
    rows = GROWTH["inside_last_block"][leaf][0]
    v0 = {"leaf": _values(np.random.default_rng(3), dtype, (rows,) + trailing)}
    v1 = {"leaf": np.concatenate([v0["leaf"], np.zeros((1,) + trailing, dtype)])}
    assert _blocks(v1["leaf"]) == _blocks(v0["leaf"])
    payload, stats = encode_delta(v0, v1)
    assert (stats["changed_blocks"], stats["grown_leaves"]) == (0, 1)
    _equal(apply_delta_ref(v0, payload), v1)
    _equal(apply_delta_chain(v0, [payload]), v1)
    v2 = {"leaf": _grow(np.random.default_rng(4), v1["leaf"], 2)}
    _equal(apply_delta_chain(v0, [payload, encode_delta(v1, v2)[0]]), v2)


def test_growing_chain_survives_a_reopen_and_fsck(tmp_path):
    """Twelve saves of a growing table, each stored as a delta against its
    parent; the reopened store checks every version out and fsck is clean."""
    rng = np.random.default_rng(11)
    tree = {"field0": _values(rng, np.uint8, (2000, 100)),
            "field1": _values(rng, np.uint8, (2000, 100)),
            "key": _values(rng, np.int64, (2000,)),
            "emb": _values(rng, BF16, (40, 64))}
    store = VersionStore(tmp_path, cache_budget_bytes=1 << 20)
    versions = [tree]
    vids = [store.commit(tree)]
    for i in range(12):
        tree = {k: _grow(rng, a, 7 + 13 * i) for k, a in tree.items()}
        versions.append(tree)
        vids.append(store.commit(tree, parents=[vids[-1]]))
    assert all(store.versions[v].stored_base == p for p, v in zip(vids, vids[1:]))
    store.close()

    reopened = VersionStore(tmp_path, cache_budget_bytes=1 << 20)
    for vid, want in zip(vids, versions):
        _equal(reopened.checkout(vid), want)
    report = reopened.fsck()
    assert report.findings == [], "\n".join(f.render() for f in report.findings)


@pytest.mark.parametrize("new_shape, new_dtype", [
    ((40, 100), np.uint8),     # shrunk
    ((60, 50), np.uint8),      # trailing shape changed
    ((60, 100), np.int8),      # dtype changed
    ((6000,), np.uint8),       # rank changed
])
def test_leaf_that_did_not_grow_is_stored_whole(new_shape, new_dtype):
    rng = np.random.default_rng(5)
    v0 = {"leaf": _values(rng, np.uint8, (50, 100))}
    v1 = {"leaf": _values(rng, np.dtype(new_dtype), new_shape)}
    payload, stats = encode_delta(v0, v1)
    assert (stats["full_leaves"], stats["grown_leaves"]) == (1, 0)
    wire = decode_delta_wire(payload)
    assert set(wire.full) == {"leaf"} and wire.sparse == {}
    _equal(apply_delta_ref(v0, payload), v1)
    _equal(apply_delta_chain(v0, [payload]), v1)


def test_one_row_growths_share_one_padded_block_count():
    """32 one-row growths of a 66-block column stay in one padded block
    count, so the mask kernel is built at most twice."""
    rng = np.random.default_rng(2)
    tree = {"col": _values(rng, np.uint8, (2700, 100))}
    assert _blocks(tree["col"]) == 66
    before = changed_block_mask._cache_size()
    for _ in range(32):
        new = {"col": _grow(rng, tree["col"], 1, updates=1)}
        payload, _ = encode_delta(tree, new)
        _equal(apply_delta_ref(tree, payload), new)
        tree = new
    assert changed_block_mask._cache_size() - before <= 2


def test_wandering_changed_counts_share_one_compact_program():
    """Saves of a 1,001-block column that change 1 to ~28 blocks all pack
    into the capacity floor, 1/32 of its padded blocks: one ``_compact``
    program, where a power of two of the count alone would build three."""
    rng = np.random.default_rng(6)
    tree = {"col": _values(rng, np.uint8, (41_000, 100))}
    assert _blocks(tree["col"]) == 1001
    before = ops._compact._cache_size()
    counts = set()
    for updates in (0, 3, 20, 26, 1, 9):
        new = {"col": _grow(rng, tree["col"], 1, updates=updates)}
        payload, stats = encode_delta(tree, new)
        counts.add(stats["changed_blocks"])
        _equal(apply_delta_ref(tree, payload), new)
        tree = new
    assert min(counts) <= 8 < 16 < max(counts) <= 32
    assert ops._compact._cache_size() - before == 1
