"""Tiny cells for the CPU rehearsal: each cell of ``BENCHMARK.json`` with its
configuration cut to a size the interpreted kernels get through in a second
or two.  The cut is the configuration's own file,
``bench/configs/<config>.tiny.json``, whose keys replace the configuration's;
nothing here names a configuration or a cell."""

from __future__ import annotations

import json

import pytest

from bench import harness

TINY_TRAFFIC = {"warm_seconds": 0.3}

_BM = harness.benchmark()
#: each cell of BENCHMARK.json: configuration and traffic mix
CELLS = {w["name"]: (w["config"], w["traffic"]) for w in _BM["workloads"]}
#: each configuration of BENCHMARK.json
CONFIGS = [c["name"] for c in _BM["configs"]]


def tiny_config(config: str) -> dict:
    """Configuration ``config`` with the keys of its ``.tiny.json`` in place."""
    path = harness.HERE / "configs" / f"{config}.tiny.json"
    if not path.exists():
        raise FileNotFoundError(
            f"{path} not found: every configuration brings its CPU-rehearsal sizes")
    full = json.loads((harness.HERE / "configs" / f"{config}.json").read_text())
    return {**full, **json.loads(path.read_text())}


def reference(config: str):
    """The plain reference module of ``config``."""
    return harness._module(harness.HERE / "configs" / f"{config}.py")


def tiny_cell(name: str) -> harness.Cell:
    config, traffic = CELLS[name]
    cell = harness.Cell.build(name, config, traffic)
    cell.config = tiny_config(config)
    cell.traffic = {**cell.traffic, **TINY_TRAFFIC}
    return cell


@pytest.fixture
def tiny():
    return tiny_cell
