"""Tiny cells for the CPU rehearsal: each real cell with its configuration
cut to a size the interpreted kernels get through in a second or two."""

from __future__ import annotations

import copy

import pytest

from bench import harness

TINY_CONFIG = {
    "ckpt-minicpm-2b-fsdp8": {
        "hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 2,
        "num_key_value_heads": 2, "head_dim": 64, "vocab_size": 512,
        "num_hidden_layers": 2, "history": {"saves": 1},
        "store": {"cache_budget_bytes": 300_000, "codec": "zstd"},
    },
}
TINY_TRAFFIC = {"warm_seconds": 0.3}

#: each cell of BENCHMARK.json: configuration and traffic mix
CELLS = {w["name"]: (w["config"], w["traffic"]) for w in harness.benchmark()["workloads"]}
#: the end-to-end metric each cell reports besides ``setup_s``
HEADLINE = {"ckpt-save": "commit_MBps"}


def tiny_cell(name: str) -> harness.Cell:
    cell = harness.Cell.build(name, *CELLS[name])
    cell.config = {**copy.deepcopy(cell.config), **TINY_CONFIG[cell.config["name"]]}
    cell.traffic = {**cell.traffic, **TINY_TRAFFIC}
    return cell


@pytest.fixture
def tiny():
    return tiny_cell
