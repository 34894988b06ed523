"""The comparison that decides ``correct`` catches what it must, in every
cell: the control of the cell's configuration, and faults planted in the
timed path while the rest of a run goes on as usual (at a tiny size, on the
CPU): a save that leaves the state unchanged, half of a save left out, a
byte altered where the tree is taken in, and a save acknowledged but never
written."""

from __future__ import annotations

import time

import numpy as np
import pytest

from bench import harness, verify
from bench.conftest import CELLS

PEAKS = {"hbm_bytes_per_s": 819e9}


def run(cell, tmp_path, **kw):
    out = harness.run_cell(cell, 77, 1.0, False, time.perf_counter(), PEAKS, tmp_path, **kw)
    return out.numbers


@pytest.mark.parametrize("name", CELLS)
def test_bench_control_is_not_correct(name, tiny, tmp_path):
    numbers = run(tiny(name), tmp_path, control=True)
    assert not verify.passed(numbers), numbers
    assert numbers["commits_lost"] > 0


def _unchanged(monkeypatch):
    """A save whose delta carries no change: it reads back as its parent."""
    from repro.store import version_store

    real = version_store.encode_delta
    monkeypatch.setattr(version_store, "encode_delta", lambda base, new: real(base, base))


def _half_left_out(monkeypatch):
    """A save that stores only the first half of its leaves."""
    from repro.store import version_store

    real = version_store.flatten_payload

    def half(payload):
        flat = real(payload)
        keys = sorted(flat)[: max(1, len(flat) // 2)]
        return {k: flat[k] for k in keys}

    monkeypatch.setattr(version_store, "flatten_payload", half)


def _flipped_byte(monkeypatch):
    """One byte of one leaf altered where the store takes the tree in."""
    from repro.store import version_store

    real = version_store.flatten_payload

    def flipped(payload):
        flat = real(payload)
        key = sorted(flat)[0]
        arr = np.array(flat[key])
        arr.reshape(-1).view(np.uint8)[0] ^= 1
        return {**flat, key: arr}

    monkeypatch.setattr(version_store, "flatten_payload", flipped)


FAULTS = {"state_unchanged": _unchanged, "half_left_out": _half_left_out,
          "flipped_byte": _flipped_byte}


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", FAULTS)
def test_bench_planted_fault_is_not_correct(fault, name, tiny, tmp_path, monkeypatch):
    FAULTS[fault](monkeypatch)
    numbers = run(tiny(name), tmp_path)
    assert not verify.passed(numbers), numbers
    assert numbers["commits_lost"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_bench_commit_acknowledged_but_not_stored_is_not_correct(name, tiny, tmp_path,
                                                                 monkeypatch):
    """Saves acknowledged while their objects sit in a write buffer that is
    never flushed: the store that wrote them reads them, a reopened one
    cannot."""
    import hashlib

    from repro.store.objectstore import ObjectStore

    real_get = ObjectStore.get

    def buffered_put(self, payload):
        key = hashlib.sha256(payload).hexdigest()
        self.__dict__.setdefault("_unflushed", {})[key] = payload
        return key, len(payload)

    def get(self, key):
        mem = self.__dict__.get("_unflushed", {})
        return mem[key] if key in mem else real_get(self, key)

    monkeypatch.setattr(ObjectStore, "put", buffered_put)
    monkeypatch.setattr(ObjectStore, "get", get)
    numbers = run(tiny(name), tmp_path)
    assert numbers["commits_lost"] > 0
