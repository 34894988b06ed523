"""What a save of ``ckpt-minicpm-2b-fsdp8`` changes, as its reference makes
it: a full fine-tune's save."""

from __future__ import annotations

import numpy as np

from bench.conftest import reference, tiny_config

CONFIG = "ckpt-minicpm-2b-fsdp8"
BLOCK = 4096


def test_bench_every_save_changes_every_block():
    """A save rewrites every 4 KiB block of every leaf, as a full fine-tune
    does, and nothing else about the tree."""
    ref, cfg = reference(CONFIG), tiny_config(CONFIG)
    base = ref.base_tree(cfg, 9)
    saved = ref.apply(cfg, base, ref.edit(cfg, 9, 1))
    assert set(saved) == set(base)
    for key, a in base.items():
        old = np.ascontiguousarray(a).reshape(-1).view(np.uint8)
        new = np.ascontiguousarray(saved[key]).reshape(-1).view(np.uint8)
        assert new.size == old.size and saved[key].dtype == a.dtype
        pad = -old.size % BLOCK
        diff = np.pad(old != new, (0, pad)).reshape(-1, BLOCK)
        assert diff.any(axis=1).all(), key
