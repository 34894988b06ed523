"""The NumPy reference decoder pinned on hand-built delta wires, where the
expected bytes are spelled out without any encoder; the store's fused chain
decoder must agree with it there too."""

import msgpack
import numpy as np
import pytest

from repro.store.delta import apply_delta_chain

from reference_decode import apply_delta_ref

FILL = np.arange(1024, dtype=np.int32)  # one 4 KiB block of content


def _wire(a):
    return {"dtype": a.dtype.str, "shape": list(a.shape), "data": a.tobytes()}


def _same_shape():
    """A float32 leaf of two blocks whose second block is rewritten, beside
    an untouched leaf, a tombstoned one and one stored whole."""
    a = np.linspace(-1, 1, 2048, dtype=np.float32)
    base = {"a": a, "u": np.ones(3, np.int8), "t": np.zeros(4, np.float32)}
    whole = np.arange(6, dtype=np.int16).reshape(2, 3)
    sparse = {"a": {"idx": np.array([1], np.int32).tobytes(),
                    "blocks": FILL.tobytes(), "n": 1}}
    want_a = a.copy()
    want_a[1024:] = FILL.view(np.float32)
    want = {"a": want_a, "u": base["u"], "f": whole}
    return base, sparse, {"f": _wire(whole)}, ["t"], want


def _grown():
    """A uint8 column of one block grown from 10 to 50 rows of 100 B: the
    appended rows are zero up to the block written at row 1."""
    g = np.arange(1000, dtype=np.int64).astype(np.uint8).reshape(10, 100)
    sparse = {"g": {"idx": np.array([1], np.int32).tobytes(),
                    "blocks": FILL.tobytes(), "n": 1, "shape": [50, 100]}}
    flat = np.concatenate([g.reshape(-1), np.zeros(3096, np.uint8),
                           FILL.view(np.uint8)[:904]])
    return {"g": g}, sparse, {}, [], {"g": flat.reshape(50, 100)}


@pytest.mark.parametrize("case", [_same_shape, _grown],
                         ids=["same_shape", "grown"])
def test_reference_decode_of_a_hand_built_wire(case):
    base, sparse, full, tombstones, want = case()
    payload = msgpack.packb(
        {"kind": "delta", "sparse": sparse, "full": full,
         "tombstones": tombstones},
        use_bin_type=True,
    )
    for got in (apply_delta_ref(base, payload),
                apply_delta_chain(base, [payload])):
        assert set(got) == set(want)
        for k in want:
            assert got[k].dtype == want[k].dtype, k
            assert got[k].shape == want[k].shape, k
            assert np.ascontiguousarray(got[k]).tobytes() == want[k].tobytes(), k
