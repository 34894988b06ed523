"""The harness finds configurations, traffic mixes and metrics by name, so
that adding one is adding files and entries; and the command refuses to run
anywhere but on a TPU."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

from bench import harness

ROOT = harness.ROOT


def _copy_tree(dst: Path) -> Path:
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    return dst / "bench"


def test_bench_added_files_are_found_by_name(tmp_path, tiny):
    """A new configuration, traffic mix, cell and per-layer metric, added as
    new files and entries beside an untouched copy of the benchmark."""
    bench = _copy_tree(tmp_path)
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    cfg = json.loads((bench / "configs" / "ckpt-minicpm-2b-fsdp8.json").read_text())
    cfg.update(name="ckpt-minicpm-2b-fsdp4", fsdp_ranks=4, shard_fraction=0.25)
    (bench / "configs" / "ckpt-minicpm-2b-fsdp4.json").write_text(json.dumps(cfg))
    shutil.copy(bench / "configs" / "ckpt-minicpm-2b-fsdp8.py",
                bench / "configs" / "ckpt-minicpm-2b-fsdp4.py")
    traffic = json.loads((bench / "traffic" / "save.json").read_text())
    traffic.update(why="two writers", clients=2)
    (bench / "traffic" / "save2.json").write_text(json.dumps(traffic))
    (bench / "layer_metrics" / "requests_per_s.py").write_text(
        "def read(w):\n    return len(w.requests) / w.seconds\n")
    bm = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "ckpt-minicpm-2b-fsdp4", "source": "test",
                          "file": "bench/configs/ckpt-minicpm-2b-fsdp4.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "ckpt-save2", "config": "ckpt-minicpm-2b-fsdp4",
                            "traffic": "save2", "chips": 1, "why": "test"})
    bm["per_layer"].append({"name": "requests_per_s", "unit": "1/s", "better": "higher",
                            "source": "host_clock", "layer": "load generator",
                            "moves": "commit_MBps", "workloads": ["ckpt-save2"]})
    for m in bm["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("ckpt-save2")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))

    cell = harness.Cell.load("ckpt-save2", bench_dir=bench)
    assert cell.config["fsdp_ranks"] == 4
    assert cell.traffic["clients"] == 2
    assert [m["name"] for m in cell.per_layer] == ["requests_per_s"]
    assert {m["name"] for m in cell.end_to_end} == {"commit_MBps", "stored_bytes_ratio",
                                                    "setup_s"}
    # the new cell runs at a tiny size and its new metric is read
    small = tiny("ckpt-save")
    cell.config = {**small.config, "name": "ckpt-minicpm-2b-fsdp4", "fsdp_ranks": 4}
    cell.traffic = {**cell.traffic, "warm_seconds": 0.3}
    out = harness.run_cell(cell, 3, 1.0, False, time.perf_counter(),
                           {"hbm_bytes_per_s": 819e9}, tmp_path / "run")
    assert harness.verify.passed(out.numbers), out.numbers
    assert harness.metrics_of(cell, out.window, traced=True,
                              bench_dir=bench)["requests_per_s"]["value"] > 0
    # and nothing that was there before changed
    assert all(p.read_bytes() == b for p, b in before.items())


def _run_cli(cwd: Path, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "ckpt-save", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_bench_command_refuses_the_cpu_and_names_it():
    done = _run_cli(ROOT)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "'cpu'" in done.stderr


def test_bench_command_refuses_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and bench/, it exits
    nonzero and prints no result."""
    _copy_tree(tmp_path)
    done = _run_cli(tmp_path)
    assert done.returncode != 0
    assert done.stdout.strip() == ""


def test_bench_every_name_resolves_to_a_file():
    bm = harness.benchmark()
    for w in bm["workloads"]:
        cell = harness.Cell.load(w["name"])
        for m in cell.end_to_end:
            assert callable(harness.reader("end_to_end", m["name"]))
        for m in cell.per_layer:
            assert callable(harness.reader("layer_metrics", m["name"]))
    for c in bm["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
