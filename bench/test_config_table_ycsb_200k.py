"""What a save of ``table-ycsb-200k`` changes, as its reference makes it: a
batch of one-field updates over the records present, rows appended to
every column, and the row count moved with them."""

from __future__ import annotations

import numpy as np

from bench.conftest import reference, tiny_config

CONFIG = "table-ycsb-200k"
BLOCK = 4096


def _blocks(a: np.ndarray, n: int) -> np.ndarray:
    """``a``'s bytes zero-padded to ``n`` 4 KiB blocks."""
    raw = a.reshape(-1).view(np.uint8)
    return np.pad(raw, (0, n * BLOCK - raw.size)).reshape(n, BLOCK)


def test_bench_a_save_updates_a_few_blocks_and_appends_rows():
    ref, cfg = reference(CONFIG), tiny_config(CONFIG)
    inserts = round(cfg["insertproportion"] * cfg["ops_per_save"])
    updates = cfg["ops_per_save"] - inserts
    tree = ref.base_tree(cfg, 2**40 + 9)
    for index in (1, 2):
        saved = ref.apply(cfg, tree, ref.edit(cfg, 2**40 + 9, index))
        assert set(saved) == set(tree)
        changed_old = 0
        assert saved["num_rows"] == len(saved["key"]) == len(tree["key"]) + inserts
        for key, a in tree.items():
            if key == "num_rows":
                continue
            b = saved[key]
            assert b.dtype == a.dtype and b.shape == (len(a) + inserts,) + a.shape[1:]
            # blocks of the parent's extent, diffed as the store diffs a grown
            # leaf: the parent zero-padded, so the last partial block changes
            n = -(-a.nbytes // BLOCK)
            diff = (_blocks(a, n) != _blocks(b, -(-b.nbytes // BLOCK))[:n]).any(axis=1)
            changed_old += int(diff[:-1].sum()) + (a.nbytes % BLOCK == 0 and bool(diff[-1]))
        # an update rewrites one 100 B field value, in one block or across two
        assert 0 < changed_old <= 2 * updates
        assert np.array_equal(saved["key"][: len(tree["key"])], tree["key"])
        assert len(np.unique(saved["key"])) == len(saved["key"])
        tree = saved


def test_bench_keys_are_ycsb_hashed_sequence_numbers():
    """The key column holds YCSB's key numbers under hashed inserts: the
    first record of every YCSB load is ``user6284781860667377211``."""
    ref, cfg = reference(CONFIG), tiny_config(CONFIG)
    tree = ref.base_tree(cfg, 1)
    assert tree["key"][0] == 6284781860667377211
    assert tree["key"].dtype == np.int64 and len(tree["key"]) == cfg["recordcount"]
    field = tree["field0"]
    assert field.dtype == np.uint8 and field.shape == (cfg["recordcount"], cfg["fieldlength"])
    assert set(np.unique(field)) <= {(r & 95) + 32 for r in range(256)}
