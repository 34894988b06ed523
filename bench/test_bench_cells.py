"""Every cell of BENCHMARK.json end to end at a tiny size on the CPU, through
the same phase functions as a chip run: set-up, warm-up, window, comparison
and the cell's metric readers; and what every configuration's reference
must do.  What a configuration's saves change is its own test file's,
``bench/test_config_<config>.py``."""

from __future__ import annotations

import math
import time

import pytest

from bench import harness, verify
from bench.conftest import CELLS, CONFIGS, reference, tiny_config

PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


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
    assert set(metrics) - {"setup_s"}, "the cell reports nothing but its set-up"


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


@pytest.mark.parametrize("config", CONFIGS)
@pytest.mark.parametrize("seed", [0, 2**31 + 11, 2**63 + 5])
def test_bench_control_differs_from_the_reference(seed, config):
    """The control (one step below what the configuration states) changes
    the answer on any seed, however large."""
    ref, cfg = reference(config), tiny_config(config)
    tree = ref.base_tree(cfg, seed)
    assert verify.bytes_wrong(ref.control(cfg, tree, None), tree) > 0
