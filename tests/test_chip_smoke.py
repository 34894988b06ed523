"""chip_smoke.py's phases at a tiny size on the CPU, kernels interpreted."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import chip_smoke  # noqa: E402


def test_phases_at_tiny_size(tmp_path):
    n_layers = 2
    cfg = chip_smoke.ARCHS[chip_smoke.ARCH].reduced()
    spec = chip_smoke.leaf_specs(cfg, n_layers, data_rows=3000)
    assert {spec[k][1] for k in spec} == {
        np.dtype("bfloat16"), np.dtype(np.float32), np.dtype(np.float64),
        np.dtype(np.int64),
    }
    lines = []
    chip_smoke.run(tmp_path, spec, n_layers, seed=0,
                   cache_budget=64 << 20, log=lines.append)
    assert any(line.startswith("fsck:") and "clean" in line for line in lines)


def test_kernels_interpreted_on_cpu():
    assert chip_smoke.kernels_compiled(16, 8) == {
        "interpret": True,
        "mask_tpu_custom_call": False,
        "chain_tpu_custom_call": False,
    }


def test_same_tree_refuses_one_flipped_bit():
    a = {"x": np.arange(6, dtype=np.float64)}
    b = {"x": a["x"].copy()}
    b["x"].view(np.uint8)[3] ^= 1
    chip_smoke.same_tree(a, {"x": a["x"].copy()}, "equal")
    with pytest.raises(chip_smoke.SmokeFailure, match="bytes differ"):
        chip_smoke.same_tree(b, a, "flipped")
    with pytest.raises(chip_smoke.SmokeFailure, match="float32"):
        chip_smoke.same_tree({"x": a["x"].astype(np.float32)}, a, "narrowed")


def test_main_refuses_a_cpu(monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    assert chip_smoke.main() != 0
    out, err = capsys.readouterr()
    assert out == "" and "'cpu'" in err
