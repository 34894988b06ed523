"""Plain reference of ``table-ycsb-200k``: every saved version of a
YCSB-shaped table from the seed, in NumPy, independent of the store.

Version 0 is the loaded ``usertable``: ``recordcount`` records in insertion
order, one uint8 ``(rows, fieldlength)`` column per field, an int64 key
column holding FNV-64 of each record's sequence number (YCSB's hashed
insert order), and the row count as an int64 scalar, as a Parquet file's
footer carries it.  Save ``i`` is one batch of ``ops_per_save`` operations:
updates of one field of a record drawn scrambled-zipfian over the records
present, applied in order, then inserts appended as new rows.  A save
depends only on the seed and its index, so any version's tree is its
parent's tree with its edit applied.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

Tree = Dict[str, np.ndarray]

#: YCSB's ScrambledZipfianGenerator: a zipfian over this many items, with
#: its zeta precomputed for them at constant 0.99, then hashed into range
ITEM_COUNT = 10_000_000_000
ZETAN = 26.46902820178302
FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211


def fnv64(values: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64`` of each value: FNV-1 over its 8 bytes,
    low byte first, then the absolute value as a signed 64-bit number."""
    v = np.asarray(values, np.int64).astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, np.uint64)
    for _ in range(8):
        h ^= v & np.uint64(0xFF)
        h *= np.uint64(FNV_PRIME_64)
        v >>= np.uint64(8)
    return np.abs(h.view(np.int64))


def scrambled_zipfian(rng: np.random.Generator, n: int, records: int,
                      theta: float) -> np.ndarray:
    """``n`` record numbers below ``records``, as YCSB's
    ScrambledZipfianGenerator draws them: a zipfian rank over
    ``ITEM_COUNT`` items (Gray et al.'s method, as ZipfianGenerator),
    FNV-64 hashed, modulo the records present."""
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / ITEM_COUNT) ** (1.0 - theta)) / (1.0 - zeta2 / ZETAN)
    u = rng.random(n)
    uz = u * ZETAN
    rank = (ITEM_COUNT * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    rank = np.where(uz < 1.0 + 0.5 ** theta, 1, rank)
    rank = np.where(uz < 1.0, 0, rank)
    return fnv64(rank) % records


def _field_bytes(rng: np.random.Generator, shape) -> np.ndarray:
    """Field values as YCSB's RandomByteIterator makes them: each byte
    ``(r & 95) + ' '`` for a random ``r``."""
    raw = np.frombuffer(rng.bytes(int(np.prod(shape))), np.uint8).reshape(shape)
    return (raw & np.uint8(95)) + np.uint8(32)


def _inserts(cfg: dict) -> int:
    return round(cfg["ops_per_save"] * cfg["insertproportion"])


def rows_before(cfg: dict, index: int) -> int:
    """Records present before save ``index`` (1, 2, ...) is applied."""
    return cfg["recordcount"] + _inserts(cfg) * (index - 1)


def base_tree(cfg: dict, seed: int) -> Tree:
    rng = np.random.default_rng([seed % (1 << 64), 0])
    n, f, w = cfg["recordcount"], cfg["fieldcount"], cfg["fieldlength"]
    tree = {f"field{i}": _field_bytes(rng, (n, w)) for i in range(f)}
    tree["key"] = fnv64(np.arange(n))
    tree["num_rows"] = np.array(n, np.int64)
    return tree


def edit(cfg: dict, seed: int, index: int) -> dict:
    """Save ``index``'s batch: the updates in op order (record, field, new
    value) over the records present, and the inserted rows."""
    rng = np.random.default_rng([seed % (1 << 64), 1, index])
    n, f, w = rows_before(cfg, index), cfg["fieldcount"], cfg["fieldlength"]
    m = _inserts(cfg)
    updates = cfg["ops_per_save"] - m
    return {
        "rows": scrambled_zipfian(rng, updates, n, cfg["zipfian_constant"]),
        "fields": rng.integers(0, f, updates),
        "values": _field_bytes(rng, (updates, w)),
        "insert_values": _field_bytes(rng, (f, m, w)),
        "insert_keys": fnv64(np.arange(n, n + m)),
    }


def apply(cfg: dict, tree: Tree, change: dict) -> Tree:
    """``tree`` with ``change`` applied, as a new tree: the updates in op
    order (a later write of a record's field wins), then the inserts
    appended."""
    n, m = len(tree["key"]), len(change["insert_keys"])
    out: Tree = {}
    for i in range(cfg["fieldcount"]):
        col = np.empty((n + m, cfg["fieldlength"]), np.uint8)
        col[:n] = tree[f"field{i}"]
        mine = np.flatnonzero(change["fields"] == i)
        rows = change["rows"][mine]
        # the last write of each row: first occurrence in reverse order
        _, last = np.unique(rows[::-1], return_index=True)
        keep = mine[len(mine) - 1 - last]
        col[change["rows"][keep]] = change["values"][keep]
        col[n:] = change["insert_values"][i]
        out[f"field{i}"] = col
    out["key"] = np.concatenate([tree["key"], change["insert_keys"]])
    out["num_rows"] = np.array(n + m, np.int64)
    return out


def control(cfg: dict, tree: Tree, parent: Optional[Tree]) -> Tree:
    """The save's inserted rows lost: every column cut to the parent's row
    count, and the row count with them (the last row dropped where there is
    no parent)."""
    rows = len(parent["key"]) if parent is not None else len(tree["key"]) - 1
    out = {k: a[:rows] for k, a in tree.items() if a.ndim}
    out["num_rows"] = np.array(rows, np.int64)
    return out
