"""Every cell of BENCHMARK.json end to end at a tiny size on the CPU, through
the same phase functions as a chip run: set-up, warm-up, window, comparison
and the cell's metric readers; and the reference's saves."""

from __future__ import annotations

import math
import time

import numpy as np
import pytest

from bench import harness, verify
from bench.conftest import CELLS, HEADLINE

PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
BLOCK = 4096


def run_tiny(cell, tmp_path, seed=2**33 + 5, **kw):
    return harness.run_cell(cell, seed, 1.0, False, time.perf_counter(), PEAKS,
                            tmp_path, **kw)


@pytest.mark.parametrize("name", CELLS)
def test_bench_cell_runs_correct_at_tiny_size(name, tiny, tmp_path):
    cell = tiny(name)
    out = run_tiny(cell, tmp_path)
    assert out.failed == 0 and out.attempted > 0
    assert verify.passed(out.numbers), out.numbers
    metrics = harness.metrics_of(cell, out.window, traced=False)
    # every end-to-end metric the cell names is read, and none is 0
    assert set(metrics) == {m["name"] for m in cell.end_to_end}
    assert all(math.isfinite(m["value"]) and m["value"] > 0 for m in metrics.values())
    assert HEADLINE[name] in metrics


@pytest.mark.parametrize("name", CELLS)
def test_bench_same_seed_same_work(name, tiny):
    """Seeds change the values, never the sizes: every seed's versions have
    the same leaves, shapes and bytes."""
    cell = tiny(name)
    ref, cfg = cell.reference, cell.config
    a, b = ref.base_tree(cfg, 1), ref.base_tree(cfg, 2**40 + 3)
    assert {k: (v.shape, v.dtype) for k, v in a.items()} == \
        {k: (v.shape, v.dtype) for k, v in b.items()}
    assert verify.bytes_wrong(a, b) > 0
    assert verify.bytes_wrong(a, ref.base_tree(cfg, 1)) == 0


@pytest.mark.parametrize("name", CELLS)
def test_bench_every_save_changes_every_block(name, tiny):
    """A save rewrites every 4 KiB block of every leaf, as a full fine-tune
    does, and nothing else about the tree."""
    cell = tiny(name)
    ref, cfg = cell.reference, cell.config
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


@pytest.mark.parametrize("seed", [0, 2**31 + 11, 2**63 + 5])
def test_bench_control_differs_from_the_reference(seed, tiny):
    """The control (one precision below) changes the answer on any seed,
    however large."""
    cell = tiny(next(iter(CELLS)))
    ref, cfg = cell.reference, cell.config
    tree = ref.base_tree(cfg, seed)
    assert verify.bytes_wrong(ref.control(cfg, tree, None), tree) > 0
