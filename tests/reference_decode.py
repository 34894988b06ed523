"""Stepwise delta decoding in plain NumPy, kept as the test oracle for the
store's fused chain pipeline (``repro.store.delta.apply_delta_chains``).

One hop at a time, and nothing the pipeline itself runs: no ``ops``, no
Pallas kernel, no device.  A leaf's bytes are viewed as 4 KiB rows (one
storage block each, the last zero-padded); a grown leaf is the parent's
bytes zero-padded to its new shape; each changed block's content is
written at its row index; tombstoned leaves go, and whole (``full``) leaves
are decoded as stored.  Payloads are parsed with ``decode_delta_wire``
(the wire format is what both sides share), and full objects with msgpack.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Iterable

import ml_dtypes
import msgpack
import numpy as np

from repro.store.delta import decode_delta_wire

BLOCK = 4096  # bytes per storage block: (8, 128) int32

FlatTree = Dict[str, np.ndarray]


def _dtype(name: str) -> np.dtype:
    return np.dtype(ml_dtypes.bfloat16) if name == "bfloat16" else np.dtype(name)


def leaf_from_wire(d: Dict) -> np.ndarray:
    """A whole leaf as the wire stores it: dtype, shape and raw bytes."""
    a = np.frombuffer(d["data"], _dtype(d["dtype"]))
    return a.reshape(d["shape"]).copy()


def apply_sparse(base: np.ndarray, idx: np.ndarray, blocks: np.ndarray,
                 shape=None) -> np.ndarray:
    """``base`` with the 4 KiB blocks ``blocks`` written at rows ``idx``,
    grown first (zero rows appended) to ``shape`` where one is given."""
    shape = base.shape if shape is None else tuple(shape)
    nbytes = math.prod(shape) * base.dtype.itemsize
    rows = -(-nbytes // BLOCK)
    buf = np.zeros(rows * BLOCK, np.uint8)
    old = np.ascontiguousarray(base).reshape(-1).view(np.uint8)
    buf[: old.size] = old
    idx = np.asarray(idx, np.int64)
    if idx.size:
        if idx.min() < 0 or idx.max() >= rows:
            raise ValueError(f"block index out of range for {rows} blocks")
        content = np.ascontiguousarray(blocks, np.int32).view(np.uint8)
        buf.reshape(rows, BLOCK)[idx] = content.reshape(idx.size, BLOCK)
    return buf[:nbytes].view(base.dtype).reshape(shape)


def apply_delta_ref(base: FlatTree, payload) -> FlatTree:
    """One delta hop: ``base`` turned into the version ``payload`` encodes."""
    wire = decode_delta_wire(payload) if isinstance(payload, bytes) else payload
    out: FlatTree = {}
    for key, arr in base.items():
        if key not in wire.tombstones:
            out[key] = np.asarray(arr)
    for key, d in wire.sparse.items():
        if key not in out:
            raise ValueError(f"sparse delta for absent leaf {key!r}")
        out[key] = apply_sparse(out[key], d.idx, d.blocks, d.shape)
    for key, d in wire.full.items():
        out[key] = leaf_from_wire(d)
    return out


def decode_chain_ref(base: FlatTree, payloads: Iterable) -> FlatTree:
    """``payloads`` applied to ``base`` in chain order, one hop each."""
    return functools.reduce(apply_delta_ref, payloads, base)


def decode_full_ref(payload: bytes) -> FlatTree:
    obj = msgpack.unpackb(payload, raw=False)
    assert obj["kind"] == "full", obj["kind"]
    return {k: leaf_from_wire(d) for k, d in obj["leaves"].items()}


def checkout_ref(store, vid: int) -> FlatTree:
    """``vid`` recreated from the store's object files along its storage
    chain, bypassing the materializer and its cache."""
    chain = []
    v = vid
    while v is not None:
        chain.append(store.versions[v])
        v = chain[-1].stored_base
    root, *deltas = reversed(chain)
    tree = decode_full_ref(store.objects.get(root.object_key))
    return decode_chain_ref(
        tree, [store.objects.get(m.object_key) for m in deltas]
    )
