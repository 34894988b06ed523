"""The harness finds configurations, traffic mixes and metrics by name, so
that adding one is adding files and entries; and the command refuses to run
anywhere but on a TPU."""

from __future__ import annotations

import collections
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace
from xml.etree import ElementTree

import pytest

from bench import harness, loadgen
from bench.conftest import CELLS, CONFIGS, tiny_config

ROOT = harness.ROOT


def _copy_tree(dst: Path) -> Path:
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    return dst / "bench"


# A configuration unlike the checkpoint, added to a copy of the benchmark:
# uint8 columns and an int64 key column, every save rewriting a few rows and
# appending rows to every column.
NEW_CONFIG, NEW_MIX, NEW_CELL = "table-toy", "append-batches", "table-append"
TOY_CONFIG = {
    "name": NEW_CONFIG, "source": "written by the registry test", "reduced": [],
    "fields": 10, "field_bytes": 100, "rows": 100_000, "updates": 2, "inserts": 4,
    "history": {"saves": 1},
    "store": {"cache_budget_bytes": 268435456, "codec": "zstd"}, "service": {},
}
TOY_TINY = {"rows": 1000, "store": {"cache_budget_bytes": 300000, "codec": "zstd"}}
TOY_MIX = {"why": "one writer committing batches back to back", "loop": "closed",
           "clients": 1, "ops": {"commit": 1.0}, "warm_seconds": 3}
TOY_REFERENCE = '''"""Plain reference of a toy table: ``fields`` uint8 columns of
``field_bytes`` a row and an int64 key column; a save rewrites ``updates``
rows of every field and appends ``inserts`` rows to every column."""

import numpy as np


def base_tree(cfg, seed):
    rng = np.random.default_rng([seed % (1 << 64), 0])
    n, w = cfg["rows"], cfg["field_bytes"]
    tree = {f"field{i}": rng.integers(0, 256, (n, w), dtype=np.uint8)
            for i in range(cfg["fields"])}
    tree["key"] = np.arange(n, dtype=np.int64)
    return tree


def edit(cfg, seed, index):
    rng = np.random.default_rng([seed % (1 << 64), 1, index])
    f, w = cfg["fields"], cfg["field_bytes"]
    return {"rows": rng.choice(cfg["rows"], cfg["updates"], replace=False),
            "update": rng.integers(0, 256, (f, cfg["updates"], w), dtype=np.uint8),
            "insert": rng.integers(0, 256, (f, cfg["inserts"], w), dtype=np.uint8)}


def apply(cfg, tree, change):
    out = {}
    for i in range(cfg["fields"]):
        col = tree[f"field{i}"].copy()
        col[change["rows"]] = change["update"][i]
        out[f"field{i}"] = np.concatenate([col, change["insert"][i]])
    out["key"] = np.arange(len(tree["key"]) + cfg["inserts"], dtype=np.int64)
    return out


def control(cfg, tree, parent):
    """An acknowledged insert that is not read back: every column's last
    row left out."""
    return {k: a[:-1] for k, a in tree.items()}
'''
TOY_EDIT_TEST = '''"""What a save of ``table-toy`` changes: a few blocks of every field, and
rows appended to every column."""

import numpy as np

from bench.conftest import reference, tiny_config

CONFIG = "table-toy"
BLOCK = 4096


def test_bench_a_save_rewrites_a_few_blocks_and_appends_rows():
    ref, cfg = reference(CONFIG), tiny_config(CONFIG)
    base = ref.base_tree(cfg, 9)
    saved = ref.apply(cfg, base, ref.edit(cfg, 9, 1))
    assert set(saved) == set(base)
    for key, a in base.items():
        b = saved[key]
        assert b.dtype == a.dtype and b.shape == (len(a) + cfg["inserts"],) + a.shape[1:]
        old = a.reshape(-1).view(np.uint8)
        new = b[:len(a)].reshape(-1).view(np.uint8)
        blocks = np.pad(old != new, (0, -old.size % BLOCK)).reshape(-1, BLOCK)
        changed = int(blocks.any(axis=1).sum())
        # a 100 B row lies in one block or across two; keys only grow
        assert (0 < changed <= 2 * cfg["updates"]) if key != "key" else changed == 0, key
'''
#: what the copy's own suites run, each over every cell or configuration
SUITES = ["bench/test_bench_cells.py", "bench/test_bench_faults.py",
          f"bench/test_config_{NEW_CONFIG.replace('-', '_')}.py",
          "bench/test_bench_registry.py::test_bench_every_name_resolves_to_a_file"]


def _add_toy(root: Path) -> None:
    """The toy configuration with its sizes, reference and edit test, its
    mix, its cell and a per-layer metric: new files, and entries appended to
    ``BENCHMARK.json``."""
    bench = root / "bench"
    configs = bench / "configs"
    (configs / f"{NEW_CONFIG}.json").write_text(json.dumps(TOY_CONFIG))
    (configs / f"{NEW_CONFIG}.tiny.json").write_text(json.dumps(TOY_TINY))
    (configs / f"{NEW_CONFIG}.py").write_text(TOY_REFERENCE)
    (bench / f"test_config_{NEW_CONFIG.replace('-', '_')}.py").write_text(TOY_EDIT_TEST)
    (bench / "traffic" / f"{NEW_MIX}.json").write_text(json.dumps(TOY_MIX))
    (bench / "layer_metrics" / "requests_per_s.py").write_text(
        "def read(w):\n    return len(w.requests) / w.seconds\n")
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": NEW_CONFIG, "source": "test",
                          "file": f"bench/configs/{NEW_CONFIG}.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": NEW_CELL, "config": NEW_CONFIG,
                            "traffic": NEW_MIX, "chips": 1, "why": "test"})
    bm["per_layer"].append({"name": "requests_per_s", "unit": "1/s", "better": "higher",
                            "source": "host_clock", "layer": "load generator",
                            "moves": "commit_MBps", "workloads": [NEW_CELL]})
    for m in bm["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append(NEW_CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bm, indent=2))


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    """A copy of the benchmark with the toy added, its files as they were
    before, and the outcome of each case of the copy's own suites, run from
    the copy's root in a process of their own."""
    root = tmp_path_factory.mktemp("added")
    bench = _copy_tree(root)
    before = {p: p.read_bytes()
              for p in [root / "BENCHMARK.json", *bench.rglob("*")] if p.is_file()}
    _add_toy(root)
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
    env.update(PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    xml = root / "suites.xml"
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         f"--junitxml={xml}", *SUITES],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)
    cases = {}
    if xml.exists():
        for case in ElementTree.parse(xml).iter("testcase"):
            cases[case.get("name")] = next(
                (c.tag for c in case if c.tag in ("failure", "error", "skipped")), "passed")
    return SimpleNamespace(root=root, bench=bench, before=before, done=done, cases=cases)


def test_bench_added_files_are_found_by_name(added):
    """A new configuration, traffic mix, cell and per-layer metric, added as
    new files and entries beside an untouched copy of the benchmark."""
    cell = harness.Cell.load(NEW_CELL, bench_dir=added.bench)
    assert cell.config == TOY_CONFIG and cell.traffic == TOY_MIX
    assert Path(cell.reference.__file__) == added.bench / "configs" / f"{NEW_CONFIG}.py"
    assert [m["name"] for m in cell.per_layer] == ["requests_per_s"]
    assert {m["name"] for m in cell.end_to_end} == \
        {m["name"] for m in harness.benchmark()["end_to_end"]}
    window = harness.Window(requests=[loadgen.Request("commit", 0.0, 0.5)], seconds=2.0,
                            setup_s=1.0, stored_bytes=0, peaks={})
    assert harness.metrics_of(cell, window, traced=True, bench_dir=added.bench) == \
        {"requests_per_s": {"value": 0.5, "unit": "1/s"}}


def _cases_of(name: str, cases) -> collections.Counter:
    """Passed cases that take ``name`` as a parameter, counted by test."""
    param = re.compile(rf"[\[-]{re.escape(name)}[\]-]")
    return collections.Counter(c.split("[")[0] for c, outcome in cases.items()
                               if outcome == "passed" and param.search(c))


def test_bench_added_cell_passes_every_per_cell_case(added):
    """The copy's own suites hold the added cell and configuration to every
    case they hold the committed ones to, and every case passes."""
    done = added.done
    assert done.returncode == 0, done.stdout[-4000:] + done.stderr[-4000:]
    assert added.cases and set(added.cases.values()) == {"passed"}, added.cases
    new_cell, new_config = _cases_of(NEW_CELL, added.cases), _cases_of(NEW_CONFIG, added.cases)
    assert set(new_cell) == {
        "test_bench_cell_runs_correct_at_tiny_size", "test_bench_same_seed_same_work",
        "test_bench_control_is_not_correct", "test_bench_planted_fault_is_not_correct",
        "test_bench_commit_acknowledged_but_not_stored_is_not_correct"}
    assert set(new_config) == {"test_bench_control_differs_from_the_reference"}
    for old in CELLS:
        assert _cases_of(old, added.cases) == new_cell, old
    for old in CONFIGS:
        assert _cases_of(old, added.cases) == new_config, old
    assert "test_bench_a_save_rewrites_a_few_blocks_and_appends_rows" in added.cases


def _only_appended(old, new) -> bool:
    """``new`` is ``old`` with items appended to its lists, and nothing else."""
    if isinstance(old, dict):
        return (isinstance(new, dict) and old.keys() == new.keys()
                and all(_only_appended(v, new[k]) for k, v in old.items()))
    if isinstance(old, list):
        return (isinstance(new, list) and len(new) >= len(old)
                and all(_only_appended(a, b) for a, b in zip(old, new)))
    return old == new


def test_bench_adding_changes_no_file_that_was_there(added):
    """Adding the cell edits no file of the benchmark: ``BENCHMARK.json``
    only gains entries, and every other file reads as it did."""
    bm = added.root / "BENCHMARK.json"
    assert _only_appended(json.loads(added.before[bm]), json.loads(bm.read_text()))
    assert [p for p, b in added.before.items() if p != bm and p.read_bytes() != b] == []


def _run_cli(cwd: Path, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", next(iter(CELLS)), "--seed", "1",
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
        tiny_config(c["name"])  # raises, naming the file, where it is missing
