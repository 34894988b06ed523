"""Pytree-level delta codec over the Pallas block kernels.

Encodes a *version payload* (any pytree of arrays — model params, optimizer
state, dataset shards) either fully or as a delta against a base payload:

* leaves present in both with identical shape/dtype → **block-sparse delta**
  (changed-block indices + packed 4 KiB blocks, from the Pallas mask/compact
  path) — the common case for checkpoint chains where few blocks move;
* leaves whose leading axis *grew* (same trailing shape and dtype: a table
  column that rows were appended to) → the same block-sparse delta against
  the parent zero-padded to the new size, so the appended blocks are
  changed blocks past the parent's end; the wire entry carries the new
  shape;
* new / shrunk / otherwise reshaped leaves → stored whole;
* deleted leaves → tombstones.

Wire format is msgpack; zstd happens in the object store.  The codec also
returns the *measured* Δ (serialized bytes) and a Φ estimate from
:class:`RecreationCostModel` — these feed the paper's cost matrices, keeping
Δ and Φ genuinely distinct quantities (Scenario 3: Φ ≠ Δ).

Blocked-layout + chain-fusion contract
--------------------------------------
A leaf's bytes are viewed as ``(num_blocks, 8, 128)`` int32 — one 4 KiB
storage block per TPU VMEM tile (:func:`repro.kernels.ops.to_blocks`).  A
sparse wire entry stores the *new content* of each changed block plus its
row index, with ``_compact``'s device-side padding trimmed before
serialization; padding is reintroduced at decode time as ``idx = -1`` slots.

Because sparse entries carry content (not XOR), a K-step chain composes by
**last-writer-wins per block row**, which :func:`apply_delta_chain` exploits:
the chain's packed deltas are flattened *in chain order* into one padded
device stack and applied in a single fused Pallas dispatch
(:mod:`repro.kernels.chain_apply`), bit-identical to applying the K deltas
one after another.  Per-leaf slot counts are bucketed to powers of
two (min 8) and leaves with equal ``(num_blocks, slot_bucket)`` are batched
into one kernel launch, so jit caches are shared across chains of different
lengths and sparsity — the same shape-bucketing discipline as
``core/solvers/jax_backend.py``.  A leaf's chain segment restarts at any
mid-chain full rewrite (shape/dtype change) and ends at a tombstone; only
the segments between those events reach the kernel.  A leaf that grows
along its chain is rebuilt at the last shape of its chain: the origin is
zero-padded to that size (:func:`repro.kernels.ops.grown_blocks`, so a
leaf growing a little at every version keeps one padded block count) and
every step's blocks land inside it.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, FrozenSet, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import msgpack
import numpy as np

from ..kernels import ops
from ..kernels.ref import BLOCK_BYTES
from ..obs.tracer import span as _span

FlatTree = Dict[str, np.ndarray]
# device-resident companion of a FlatTree: blocked form + layout meta per
# leaf (only leaves that went through the block pipeline appear)
BlockedLeaf = Tuple[jnp.ndarray, "ops.BlockMeta"]
BlockedTree = Dict[str, BlockedLeaf]

_SLOT_BUCKET_MIN = 8


def payload_leaves(tree: Any) -> Dict[str, Any]:
    """A pytree's leaves as given, keyed by '/'-joined path."""
    return {
        "/".join(_path_str(p) for p in path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def flatten_payload(tree: Any) -> FlatTree:
    """Flatten a pytree to {path: np.ndarray} with '/'-joined keys.  NumPy
    leaves come back as the caller's own arrays, not copies."""
    return {k: np.asarray(leaf) for k, leaf in payload_leaves(tree).items()}


def _path_str(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return str(p.idx)
    return str(p)


# --------------------------------------------------------------------- wire
def _arr_to_wire(a: np.ndarray) -> Dict:
    return {
        "dtype": a.dtype.str if a.dtype != jnp.bfloat16 else "bfloat16",
        "shape": list(a.shape),
        "data": a.tobytes(),
    }


def _arr_from_wire(d: Dict) -> np.ndarray:
    dtype = jnp.bfloat16 if d["dtype"] == "bfloat16" else np.dtype(d["dtype"])
    return np.frombuffer(d["data"], dtype=dtype).reshape(d["shape"]).copy()


def encode_full(flat: FlatTree) -> bytes:
    # sorted-key serialization: the content fingerprint (sha256 of these
    # bytes) must not depend on dict insertion order, which differs between
    # the commit path (tree_flatten order) and checkout-re-encode
    # (apply_delta_chains rebuild order) — an order-dependent fp would
    # spuriously invalidate the incremental Δ/Φ edge cache
    with _span("delta.encode_full"):
        return msgpack.packb(
            {"kind": "full",
             "leaves": {k: _arr_to_wire(flat[k]) for k in sorted(flat)}},
            use_bin_type=True,
        )


def decode_full(payload: bytes) -> FlatTree:
    with _span("delta.decode_full"):
        obj = msgpack.unpackb(payload, raw=False)
        assert obj["kind"] == "full", obj["kind"]
        return {k: _arr_from_wire(v) for k, v in obj["leaves"].items()}


def _grew(base: np.ndarray, new: np.ndarray) -> bool:
    """``new`` is ``base`` with rows appended: same dtype and trailing
    shape, a longer leading axis."""
    return (base.ndim >= 1 and base.ndim == new.ndim and base.dtype == new.dtype
            and base.shape[1:] == new.shape[1:] and base.shape[0] < new.shape[0])


def encode_delta(base: FlatTree, new: FlatTree) -> Tuple[bytes, Dict]:
    """Delta payload turning `base` into `new`, plus stats for Φ modelling.

    A leaf whose leading axis grew is diffed against the parent zero-padded
    to the new size (both in :func:`repro.kernels.ops.grown_blocks` blocks),
    and its wire entry carries the new ``shape``.

    Traced as ``delta.encode_delta``; for each leaf diffed on the device,
    ``delta.upload`` (host staging and the *enqueue* of both leaves'
    transfers: it ends before the device holds them), ``delta.count`` (the
    mask and its changed count: it ends at the count's sync, so it absorbs
    the device's wait for the upload and the mask) and ``delta.fetch``
    (compaction and the downloads of the changed rows); then
    ``delta.pack``.  The span's attributes count what crossed between host
    and device, at the transfer sites: ``h2d_bytes`` (the blocks uploaded
    from host leaves), ``d2h_bytes`` (each int32 changed count and the
    fetched ``idx[:n]`` and ``blocks[:n]``, a grown leaf's whole compacted
    capacity); and the leaves and blocks:
    ``changed_blocks``, ``total_blocks`` (blocks diffed, padding
    excluded), ``grown_leaves`` (diffed after growing) and ``full_leaves``
    (stored whole).
    """
    with _span("delta.encode_delta") as sp:
        sparse, full = {}, {}
        stats = {"changed_blocks": 0, "total_blocks": 0, "full_leaves": 0,
                 "grown_leaves": 0}
        h2d = d2h = 0
        tombstones = [k for k in base if k not in new]
        for key, arr in new.items():
            b = base.get(key)
            grown = b is not None and _grew(b, arr)
            if b is None or (not grown and (b.shape != arr.shape
                                            or b.dtype != arr.dtype)):
                full[key] = _arr_to_wire(arr)
                stats["full_leaves"] += 1
                continue
            real = ops.num_blocks_of(arr.nbytes)
            padded = (ops.grown_blocks(real),) if grown else ()
            # NumPy in: the byte view happens on the host, so 64-bit leaves
            # keep every byte (ops.to_blocks)
            with _span("delta.upload"):
                bb, _ = ops.to_blocks(b, *padded)
                nb, _ = ops.to_blocks(arr, *padded)
            with _span("delta.count"):
                mask, n = ops.count_changed(bb, nb)
            stats["changed_blocks"] += n
            stats["total_blocks"] += real
            stats["grown_leaves"] += grown
            if sp:
                # a device leaf is blocked on the device: nothing crosses
                h2d += sum(blk.nbytes for blk, x in ((bb, b), (nb, arr))
                           if isinstance(x, np.ndarray))
                d2h += 4  # the int32 count
            entry = {"idx": b"", "blocks": b"", "n": int(n)}
            if grown:
                entry["shape"] = list(arr.shape)
            sparse[key] = entry
            if n == 0:
                continue
            with _span("delta.fetch"):
                idx, blocks = ops.compact(mask, nb, n)
                if grown:
                    # a grown leaf's changed count differs from version to
                    # version, and a device slice builds a program per
                    # length: the whole capacity comes down instead
                    idx, blocks = jax.device_get((idx, blocks))
                    fetched = idx.nbytes + blocks.nbytes
                # trim padding before serialization (padding is a
                # device-side artifact)
                host_idx = np.asarray(idx[:n], np.int32)
                host_blocks = np.asarray(blocks[:n], np.int32)
                entry["idx"] = host_idx.tobytes()
                entry["blocks"] = host_blocks.tobytes()
            if sp:
                d2h += fetched if grown else host_idx.nbytes + host_blocks.nbytes
        with _span("delta.pack"):
            payload = msgpack.packb(
                {"kind": "delta", "sparse": sparse, "full": full,
                 "tombstones": tombstones},
                use_bin_type=True,
            )
        if sp:
            sp.set(h2d_bytes=h2d, d2h_bytes=d2h, **stats)
    return payload, stats


# ------------------------------------------------------------- wire decode
@dataclasses.dataclass(frozen=True)
class SparseLeafDelta:
    """One leaf's packed sparse delta, trimmed (no padding slots)."""

    idx: np.ndarray      # (n,) int32 changed block rows
    blocks: np.ndarray   # (n, 8, 128) int32 packed new block content
    n: int
    # the leaf's new shape where its leading axis grew (None: unchanged);
    # payloads written before leaves could grow carry none
    shape: Optional[Tuple[int, ...]] = None


@dataclasses.dataclass(frozen=True)
class DeltaWire:
    """A decoded delta payload: msgpack unpacked once, arrays zero-copy views
    ready for device upload (the decode path of the fused chain pipeline)."""

    sparse: Dict[str, SparseLeafDelta]
    full: Dict[str, Dict]          # raw wire dicts, decoded lazily
    tombstones: FrozenSet[str]


def decode_delta_wire(payload: bytes) -> DeltaWire:
    """Unpack a delta payload into :class:`DeltaWire` (no block application)."""
    obj = msgpack.unpackb(payload, raw=False)
    assert obj["kind"] == "delta", obj["kind"]
    sparse: Dict[str, SparseLeafDelta] = {}
    for key, d in obj["sparse"].items():
        n = int(d["n"])
        shape = tuple(d["shape"]) if "shape" in d else None
        if n == 0:
            sparse[key] = SparseLeafDelta(
                np.empty((0,), np.int32), np.empty((0, 8, 128), np.int32), 0,
                shape,
            )
            continue
        idx = np.frombuffer(d["idx"], np.int32)
        blocks = np.frombuffer(d["blocks"], np.int32).reshape(-1, 8, 128)
        sparse[key] = SparseLeafDelta(idx, blocks, n, shape)
    return DeltaWire(sparse, obj["full"], frozenset(obj["tombstones"]))


def _grown_meta(arr: np.ndarray, shape: Tuple[int, ...]) -> "ops.BlockMeta":
    """Layout of ``arr`` grown to ``shape``, in its padded block count;
    raises where ``shape`` is no growth of ``arr`` (a corrupt chain)."""
    if arr.ndim == 0 or shape[1:] != arr.shape[1:] or shape[0] < arr.shape[0]:
        raise ValueError(
            f"corrupt delta: a leaf of shape {arr.shape} cannot grow to {shape}"
        )
    nbytes = math.prod(shape) * arr.dtype.itemsize
    return ops.BlockMeta(str(arr.dtype), tuple(shape), nbytes,
                         ops.grown_blocks(ops.num_blocks_of(nbytes)))


def _zero_grown(arr: np.ndarray, shape: Tuple[int, ...]) -> np.ndarray:
    """``arr`` with zero rows appended up to ``shape`` (a grown leaf whose
    delta changed no block: the appended rows are all zero bytes)."""
    out = np.zeros(shape, arr.dtype)
    out[: arr.shape[0]] = arr
    return out


# ----------------------------------------------------- fused chain pipeline
def _slot_bucket(n: int) -> int:
    """Pad per-leaf chain slot counts to powers of two (min 8) so the fused
    kernel's jit cache is shared across chains of different depth/sparsity."""
    cap = _SLOT_BUCKET_MIN
    while cap < n:
        cap *= 2
    return cap


@dataclasses.dataclass
class _LeafProgram:
    """Per-leaf chain plan: an origin value plus the sparse segments applied
    after it (segments restart at mid-chain full rewrites)."""

    req: int                       # request index in the batch
    key: str
    origin_step: Optional[int]     # None → base tree; else wires[i].full
    segments: List[SparseLeafDelta]
    shape: Optional[Tuple[int, ...]]  # the last grown shape, if it grew


# (origin_step, segments, shape) of one leaf of a chain's final tree
_Program = Tuple[Optional[int], List[SparseLeafDelta], Optional[Tuple[int, ...]]]


def _resolve_leaf_programs(
    base: FlatTree, wires: Sequence[DeltaWire]
) -> Dict[str, _Program]:
    """Fold a chain's per-step leaf events into one program per final leaf.

    Walks steps in order: tombstones kill a leaf, full rewrites restart its
    chain segment (later sparse deltas apply on the rewritten value), sparse
    deltas append to the current segment, and a grown leaf's delta sets the
    shape the leaf ends at.  The result maps every leaf of the chain's
    *final* tree to ``(origin_step, segments, shape)``, ``shape`` None where
    the leaf kept its origin's shape.
    """
    state: Dict[str, _Program] = {k: (None, [], None) for k in base}
    for i, w in enumerate(wires):
        for k in w.tombstones:
            state.pop(k, None)
        for k, d in w.sparse.items():
            if d.n == 0 and d.shape is None:
                continue
            st = state.get(k)
            if st is None:
                raise ValueError(
                    f"corrupt chain: step {i} carries a sparse delta for "
                    f"leaf {k!r} absent from the running tree"
                )
            if d.n:
                st[1].append(d)
            if d.shape is not None:
                state[k] = (st[0], st[1], d.shape)
        for k in w.full:
            state[k] = (i, [], None)
    return state


def apply_delta_chains(
    requests: Sequence[
        Tuple[FlatTree, Sequence[Union[bytes, DeltaWire]], Optional[BlockedTree]]
    ],
    *,
    stats: Optional[Dict[str, int]] = None,
) -> List[Tuple[FlatTree, BlockedTree]]:
    """Apply one delta chain per request, fused and batched across requests.

    Each request is ``(base_tree, chain_payloads, base_blocked)`` —
    ``base_blocked`` optionally carries the base's device-resident blocked
    leaves so repeatedly-edited leaves skip re-``to_blocks``.  Leaves are
    grouped by ``(num_blocks, slot_bucket)`` across *all* requests and each
    group runs as one :func:`repro.kernels.ops.chain_apply_batched` launch.
    A leaf that grew along its chain runs in the padded block count of its
    last shape, its origin zero-padded to it.

    Returns ``[(tree, blocked)]`` per request, bit-identical to applying
    each chain's deltas one hop at a time (pinned against the NumPy
    reference decoder in ``tests/reference_decode.py``).  Leaves no step
    touches pass through by reference from ``base_tree``.  ``stats``
    (optional) is bumped with ``launches`` / ``fused_slots`` for
    observability.

    Traced as ``delta.apply_chains``, with ``h2d_bytes`` (origin leaves
    uploaded to block form, and the padded slot stacks), ``d2h_bytes``
    (the fetched result blocks) and ``grown_leaves`` (leaves rebuilt at a
    grown shape) besides the launch counts.
    """
    with _span("delta.apply_chains", requests=len(requests)) as sp:
        # the caller's stats dict accumulates across calls; snapshot so the
        # span attributes only this call's launches
        launch0 = (stats or {}).get("launches", 0)
        slots0 = (stats or {}).get("fused_slots", 0)
        h2d = d2h = grown = 0
        wire_chains: List[List[DeltaWire]] = []
        outs: List[FlatTree] = []
        blocked_outs: List[BlockedTree] = []
        units: List[_LeafProgram] = []
        for ri, (base, payloads, _) in enumerate(requests):
            wires = [
                decode_delta_wire(p) if isinstance(p, bytes) else p
                for p in payloads
            ]
            wire_chains.append(wires)
            out: FlatTree = {}
            outs.append(out)
            blocked_outs.append({})
            for key, (origin_step, segs, shape) in _resolve_leaf_programs(
                base, wires
            ).items():
                grown += shape is not None
                if not segs:
                    # untouched leaf (reference passthrough) or plain full
                    # decode, zero-padded where it grew with no block changed
                    arr = (
                        base[key]
                        if origin_step is None
                        else _arr_from_wire(wires[origin_step].full[key])
                    )
                    out[key] = arr if shape is None else _zero_grown(arr, shape)
                    continue
                units.append(_LeafProgram(ri, key, origin_step, segs, shape))

        # shape-bucketed grouping: one fused launch per (num_blocks,
        # slot_bucket)
        groups: Dict[Tuple[int, int], List[_LeafProgram]] = {}
        origins: Dict[Tuple[int, str], Tuple[Any, "ops.BlockMeta"]] = {}
        for u in units:
            base, _, base_blocked = requests[u.req]
            arr = (base[u.key] if u.origin_step is None else _arr_from_wire(
                wire_chains[u.req][u.origin_step].full[u.key]))
            final = None if u.shape is None else _grown_meta(arr, u.shape)
            rows = final and final.num_blocks
            pre = (base_blocked or {}).get(u.key) if u.origin_step is None else None
            if pre is not None and pre[0].shape[0] == (rows or pre[1].num_blocks):
                origin_blocks, meta = pre
            else:
                origin_blocks, meta = ops.to_blocks(arr, rows)
                if sp and isinstance(arr, np.ndarray):
                    h2d += origin_blocks.nbytes
            origins[(u.req, u.key)] = (origin_blocks, final or meta)
            total = sum(s.n for s in u.segments)
            groups.setdefault(
                (int(origin_blocks.shape[0]), _slot_bucket(total)), []
            ).append(u)

        # dispatch every group first, keeping results on device; the host
        # copies happen once at the end as a single batched transfer
        # (device_get issues the async copies together), not one blocking
        # sync per leaf
        host_fetch: List[Tuple[int, str, Any, "ops.BlockMeta"]] = []
        for (nb, cap), members in groups.items():
            idx_pad = np.full((len(members), cap), -1, np.int32)
            blk_pad = np.zeros((len(members), cap, 8, 128), np.int32)
            for li, u in enumerate(members):
                at = 0
                for seg in u.segments:  # chain order: later slots win
                    idx_pad[li, at : at + seg.n] = seg.idx
                    blk_pad[li, at : at + seg.n] = seg.blocks
                    at += seg.n
            if sp:
                h2d += idx_pad.nbytes + blk_pad.nbytes
            if len(members) == 1:
                u = members[0]
                ob, meta = origins[(u.req, u.key)]
                rec = ops.chain_apply(
                    ob, jnp.asarray(blk_pad[0]), jnp.asarray(idx_pad[0])
                )
                recs = [rec]
            else:
                stack = jnp.stack([origins[(u.req, u.key)][0] for u in members])
                recs = ops.chain_apply_batched(
                    stack, jnp.asarray(blk_pad), jnp.asarray(idx_pad)
                )
            if stats is not None:
                stats["launches"] = stats.get("launches", 0) + 1
                stats["fused_slots"] = (
                    stats.get("fused_slots", 0) + len(members) * cap
                )
            for u, rec in zip(members, recs):
                meta = origins[(u.req, u.key)][1]
                blocked_outs[u.req][u.key] = (rec, meta)
                host_fetch.append((u.req, u.key, rec, meta))
        if host_fetch:
            # int32 blocks come back and are viewed as each leaf's dtype on
            # the host, which 64-bit dtypes survive
            fetched = jax.device_get([rec for _, _, rec, _ in host_fetch])
            for (req, key, _, meta), blocks in zip(host_fetch, fetched):
                outs[req][key] = ops.from_blocks(blocks, meta)
            if sp:
                d2h = sum(b.nbytes for b in fetched)
        if sp:
            if stats is not None:
                sp.set(
                    launches=stats.get("launches", 0) - launch0,
                    fused_slots=stats.get("fused_slots", 0) - slots0,
                )
            else:
                sp.set(launches=len(groups))
            sp.set(leaves=len(units), h2d_bytes=h2d, d2h_bytes=d2h,
                   grown_leaves=grown)
    return list(zip(outs, blocked_outs))


def apply_delta_chain(
    base: FlatTree,
    payloads: Sequence[Union[bytes, DeltaWire]],
    *,
    base_blocked: Optional[BlockedTree] = None,
) -> FlatTree:
    """Fused K-step chain application (single chain): one device dispatch
    per leaf-shape group (see :func:`apply_delta_chains`).  fsck calls it
    with one payload at a time, to decode a chain hop by hop."""
    return apply_delta_chains([(base, payloads, base_blocked)])[0][0]


# ----------------------------------------------------------------- Φ model
@dataclasses.dataclass(frozen=True)
class RecreationCostModel:
    """Maps a stored object to an estimated recreation cost in seconds.

    Distinct from Δ (bytes at rest): reading is charged at storage bandwidth,
    decompression and block application at their own rates — so compact,
    compute-heavy deltas genuinely trade Φ against Δ (paper Scenario 3).
    """

    read_gbps: float = 2.0          # storage/network read bandwidth
    decompress_gbps: float = 1.0    # zstd decode rate
    apply_gbps: float = 8.0         # on-device block scatter rate
    seek_s: float = 0.005           # per-object latency

    def phi(self, stored_bytes: int, raw_bytes: int, applied_bytes: int) -> float:
        return (
            self.seek_s
            + stored_bytes / (self.read_gbps * 1e9)
            + raw_bytes / (self.decompress_gbps * 1e9)
            + applied_bytes / (self.apply_gbps * 1e9)
        )

    def phi_full(self, stored_bytes: int, raw_bytes: int) -> float:
        return self.phi(stored_bytes, raw_bytes, 0)

    def phi_delta(self, stored_bytes: int, raw_bytes: int, changed_blocks: int) -> float:
        return self.phi(stored_bytes, raw_bytes, changed_blocks * BLOCK_BYTES)
