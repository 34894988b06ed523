"""The delta payload writer against ``msgpack.packb``.

``encode_delta`` lays its payload out in one buffer sized in advance and
copies each fetched array into place once.  Its bytes must be what
``msgpack.packb(..., use_bin_type=True)`` writes for the dict form the
codec built before it had a writer (every bin a ``bytes``), in the same key
order, so object keys, bytes at rest and old stores stay as they are.  The
dict form here is built in NumPy alone: a leaf's bytes as zero-padded 4 KiB
rows, the rows that differ from the parent's, a grown leaf against its
parent zero-padded to its new size.
"""

import hashlib

import ml_dtypes
import msgpack
import numpy as np
import pytest

from repro.kernels import ops
from repro.store.delta import (
    _pack_frame,
    apply_delta_chain,
    apply_delta_chains,
    decode_delta_wire,
    encode_delta,
    encode_full,
)
from repro.store.objectstore import Codec, ObjectStore
from repro.store.version_store import VersionStore

BF16 = np.dtype(ml_dtypes.bfloat16)
BLOCK = 4096


def _rows(a: np.ndarray, rows: int) -> np.ndarray:
    """``a``'s bytes as ``rows`` zero-padded 4 KiB rows."""
    raw = np.ascontiguousarray(a).reshape(-1).view(np.uint8)
    buf = np.zeros(rows * BLOCK, np.uint8)
    buf[: raw.size] = raw
    return buf.reshape(rows, BLOCK)


def _grew(b: np.ndarray, a: np.ndarray) -> bool:
    return (b.ndim >= 1 and b.ndim == a.ndim and b.dtype == a.dtype
            and b.shape[1:] == a.shape[1:] and b.shape[0] < a.shape[0])


def dict_form(base, new) -> dict:
    """The delta as the dict ``msgpack.packb`` was given: bins as bytes."""
    sparse, full = {}, {}
    for key, a in new.items():
        b = base.get(key)
        grown = b is not None and _grew(b, a)
        if b is None or (not grown and (b.shape != a.shape or b.dtype != a.dtype)):
            full[key] = {"dtype": "bfloat16" if a.dtype == BF16 else a.dtype.str,
                         "shape": list(a.shape), "data": a.tobytes()}
            continue
        rows = -(-a.nbytes // BLOCK)
        if grown:
            rows = ops.grown_blocks(rows)
        old, cur = _rows(b, rows), _rows(a, rows)
        idx = np.flatnonzero((old != cur).any(axis=1)).astype(np.int32)
        entry = {"idx": idx.tobytes(), "blocks": cur[idx].tobytes(),
                 "n": int(idx.size)}
        if grown:
            entry["shape"] = list(a.shape)
        sparse[key] = entry
    return {"kind": "delta", "sparse": sparse, "full": full,
            "tombstones": [k for k in base if k not in new]}


def packb_form(base, new) -> bytes:
    return msgpack.packb(dict_form(base, new), use_bin_type=True)


def _f32(n: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal(n).astype(np.float32)


def _bumped(a: np.ndarray, blocks) -> np.ndarray:
    """``a`` with one byte changed in each listed 4 KiB block."""
    out = a.copy()
    raw = out.reshape(-1).view(np.uint8)
    for i in blocks:
        raw[i * BLOCK] ^= 0x5A
    return out


def _case_dense():
    a = _f32(3 * 1024)
    return {"w": a}, {"w": a + 1.0}


def _case_sparse():
    a = _f32(8 * 1024)
    return {"w": a}, {"w": _bumped(a, [1, 6])}


def _case_unchanged():
    a = _f32(2 * 1024)
    return {"w": a, "v": a[:1024]}, {"w": a.copy(), "v": _bumped(a[:1024], [0])}


def _case_grown():
    col = np.arange(1000, dtype=np.int64)
    new = np.concatenate([col, np.arange(300, dtype=np.int64) + 7])
    new[3] = -1
    return {"key": col, "f": np.zeros((40, 100), np.uint8)}, {
        "key": new, "f": np.ones((90, 100), np.uint8)}


def _case_whole():
    base = {"reshaped": _f32(16).reshape(4, 4),
            "retyped": np.arange(10, dtype=np.int32),
            "same": _f32(1024)}
    new = {"reshaped": _f32(16, 1).reshape(2, 8),
           "retyped": np.arange(10, dtype=np.float32),
           "same": _bumped(base["same"], [0]),
           "added": np.arange(24, dtype=np.int16).reshape(2, 3, 4)[:, ::2]}
    return base, new


def _case_tombstones():
    base = {k: _f32(1024, i) for i, k in enumerate("xyz")}
    return base, {"y": base["y"] + 2.0}


def _case_dtypes():
    base = {"bf16": _f32(2048).astype(BF16),
            "i64": np.arange(600, dtype=np.int64),
            "step": np.array(41, np.int64)}
    new = {"bf16": (_f32(2048) * 2).astype(BF16),
           "i64": np.arange(600, dtype=np.int64) * 3,
           "step": np.array(42, np.int64),
           "new_bf16": np.ones((3, 5), BF16),
           "new_0d": np.array(2.5, np.float32)}
    return base, new


def _case_bin(nbytes: int):
    """A leaf stored whole whose bin is ``nbytes`` long."""
    def make():
        a = _f32(1024)
        return {"w": a}, {"w": _bumped(a, [0]),
                          "raw": (np.arange(nbytes) % 251).astype(np.uint8)}
    return make


def _case_sparse_bins():
    """Sparse bins: ``idx`` of 4 and 64 (bin8) and 256 (bin16) bytes,
    ``blocks`` of 4,096 (bin16), 65,536 and 262,144 (bin32) bytes."""
    a, b = _f32(64 * 1024), _f32(16 * 1024, 1)
    return {"one": a[:1024], "sixteen": b, "all64": a}, {
        "one": _bumped(a[:1024], [0]), "sixteen": b + 1.0, "all64": a - 1.0}


def _case_key(n: int):
    def make():
        a = _f32(1024)
        return {"k" * n: a}, {"k" * n: _bumped(a, [0]), "n" * n: a[:7]}
    return make


def _case_map16():
    base = {f"leaf{i:02d}": _f32(1024, i) for i in range(36)}
    new = {k: (v + 1.0 if i % 2 else v) for i, (k, v) in enumerate(base.items())
           if i < 20}
    new.update({f"new{i:02d}": np.full(3, i, np.int32) for i in range(17)})
    return base, new


CASES = {
    "dense": _case_dense,
    "sparse": _case_sparse,
    "n0": _case_unchanged,
    "grown": _case_grown,
    "whole_new_reshaped_retyped": _case_whole,
    "tombstones": _case_tombstones,
    "bf16_int64_0d": _case_dtypes,
    **{f"bin_{n}": _case_bin(n) for n in (0, 255, 256, 65535, 65536)},
    "sparse_bins": _case_sparse_bins,
    **{f"key_{n}": _case_key(n) for n in (31, 32, 255, 256)},
    "map16": _case_map16,
}


def _equal(got, want):
    assert set(got) == set(want)
    for k in want:
        assert got[k].dtype == want[k].dtype and got[k].shape == want[k].shape, k
        assert np.asarray(got[k]).tobytes() == want[k].tobytes(), k


@pytest.mark.parametrize("case", list(CASES))
def test_writer_bytes_equal_packb(case):
    base, new = CASES[case]()
    payload, stats = encode_delta(base, new)
    ref = packb_form(base, new)
    assert len(payload) == len(ref)
    assert payload == ref
    form = dict_form(base, new)
    assert stats["changed_blocks"] == sum(e["n"] for e in form["sparse"].values())
    assert stats["full_leaves"] == len(form["full"])
    wire = decode_delta_wire(payload)
    assert set(wire.sparse) == set(form["sparse"])
    assert wire.tombstones == frozenset(form["tombstones"])
    _equal(apply_delta_chain(base, [payload]), new)


def test_frame_is_packb_with_arrays_as_bins():
    obj = {"kind": "x", "m": {"a": np.arange(5, dtype=np.int16)[::2],
                              "b": np.array(3, np.int64), "c": b"", "n": 70000,
                              "s": [2, 300, 70000], "t": ["u", "v" * 40]}}
    want = msgpack.packb(
        {"kind": "x", "m": {**obj["m"], "a": obj["m"]["a"].tobytes(),
                            "b": obj["m"]["b"].tobytes()}},
        use_bin_type=True)
    assert _pack_frame(obj) == want


@pytest.mark.parametrize("backend", ["zstd", "zlib"])
def test_writer_payload_through_consumers(tmp_path, backend):
    """A writer payload goes wherever ``bytes`` went: ``len``, sha256, the
    codec, the object store and the chain decode, and lands under the object
    key and size of its ``packb`` form."""
    base, new = _case_map16()
    payload, _ = encode_delta(base, new)
    ref = packb_form(base, new)
    assert len(payload) == len(ref)
    assert hashlib.sha256(payload).hexdigest() == hashlib.sha256(ref).hexdigest()
    codec = Codec(backend=backend)
    assert codec.compress(payload) == codec.compress(ref)
    key, stored = ObjectStore(tmp_path / "a", codec=codec).put(payload)
    assert (key, stored) == ObjectStore(tmp_path / "b", codec=codec).put(ref)
    assert ObjectStore(tmp_path / "a", codec=codec).get(key) == ref
    assert msgpack.unpackb(payload, raw=False) == msgpack.unpackb(ref, raw=False)
    ((tree, _),) = apply_delta_chains([(base, [payload], None)])
    _equal(tree, new)
    # a frame long enough for zstd's multi-threaded path (over two jobs)
    big = np.arange(5 << 20, dtype=np.int32) % 977
    frame = _pack_frame({"kind": "delta", "sparse": {"w": {
        "idx": np.arange(big.size // 1024, dtype=np.int32), "blocks": big,
        "n": big.size // 1024}}, "full": {}, "tombstones": []})
    assert codec.compress(frame) == codec.compress(bytes(frame))


def _pair(stored: str):
    rng = np.random.default_rng(7)
    base = {"emb": rng.standard_normal((64, 256)).astype(np.float32),
            "step": np.array(1, np.int64)}
    if stored == "whole":  # every block changes: the delta is the larger
        new = {"emb": base["emb"] + 1.0, "step": np.array(2, np.int64)}
    else:
        new = {"emb": _bumped(base["emb"], [3]), "step": np.array(2, np.int64)}
    return base, new


# (object_key, stored_bytes) of the version these pairs commit, as the store
# recorded them before the delta payload had a writer (zstd level 3)
RECORDED = {
    "whole": ("7b874bd45fad70fb973ea09e28bb15c2589609738018fa661337fc85956e1445",
              59494),
    "delta": ("fb8001991bbd24a392ddbc70ee80de8b4bdf8b3a36f22098c13c8573ea74926a",
              3932),
}


@pytest.mark.parametrize("stored", ["whole", "delta"])
def test_commit_records_packb_object(tmp_path, stored):
    base, new = _pair(stored)
    store = VersionStore(tmp_path / "s")
    v1 = store.commit(base)
    v2 = store.commit(new, parents=[v1])
    meta = store.versions[v2]
    obj = encode_full(new) if stored == "whole" else packb_form(base, new)
    assert meta.stored_base == (None if stored == "whole" else v1)
    assert meta.object_key == hashlib.sha256(obj).hexdigest()
    assert meta.stored_bytes == len(store.objects.codec.compress(obj))
    if store.objects.codec.backend == "zstd":
        assert (meta.object_key, meta.stored_bytes) == RECORDED[stored]
    _equal(store.checkout(v2), new)
