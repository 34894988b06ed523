"""Public jit'd wrappers around the delta-path kernels.

Converts arbitrary tensors (any dtype/shape) to and from the canonical
``(num_blocks, 8, 128)`` int32 block layout, and exposes the encode/apply
operations the store uses:

* :func:`to_blocks` / :func:`from_blocks` — byte-preserving (bitcast + pad)
  layout conversion, on the host for NumPy input and on the device for JAX
  input; :func:`grown_blocks` sizes the padded block count of a leaf that
  grows between versions;
* :func:`xor_encode` / :func:`xor_apply` — the paper's XOR delta variant;
* :func:`count_changed` / :func:`compact` / :func:`sparse_apply` —
  block-sparse delta: changed-block mask (Pallas) and its count,
  compaction to (idx, blocks), scattered apply (Pallas).  Capacity is
  rounded up to a power of two so jit recompiles stay bounded when the
  number of changed blocks varies between commits; :func:`sparse_encode`
  compacts into a capacity the caller fixes.

Every kernel here is interpreted where the default backend is the CPU and
compiled on a TPU (:func:`repro.kernels.resolve_interpret`).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .block_diff import block_hash, changed_block_mask, hash_coefficients
from .chain_apply import chain_delta_apply, chain_delta_apply_batched
from .ref import BLOCK_BYTES, BLOCK_ELEMS
from .sparse_apply import sparse_delta_apply
from .xor_delta import xor_delta


@dataclasses.dataclass(frozen=True)
class BlockMeta:
    """Everything needed to reverse :func:`to_blocks`."""

    dtype: str
    shape: Tuple[int, ...]
    nbytes: int
    num_blocks: int


def num_blocks_of(nbytes: int) -> int:
    """Blocks that hold ``nbytes`` bytes."""
    return -(-nbytes // BLOCK_BYTES)


def grown_blocks(num_blocks: int) -> int:
    """The block count a leaf of ``num_blocks`` blocks is padded to where it
    grew since its parent: rounded up to its four leading bits, so the
    padding is under an eighth of the leaf and a leaf that grows a little at
    every version keeps one padded size (one compiled program per kernel)
    for many versions.  Counts under 16 are their own bucket."""
    shift = max(0, num_blocks.bit_length() - 4)
    return -(-num_blocks >> shift) << shift


def to_blocks(x, num_blocks: Optional[int] = None) -> Tuple[jnp.ndarray, BlockMeta]:
    """View a tensor's bytes as (num_blocks, 8, 128) int32, zero-padded.

    ``num_blocks`` pads to more blocks than the bytes fill (a grown leaf's
    bucket, :func:`grown_blocks`); :func:`from_blocks` drops the padding.
    A NumPy array is viewed on the host and uploaded as int32 blocks, so
    every dtype keeps every byte: with ``jax_enable_x64`` off (the default),
    uploading a float64/int64 array first would narrow it to 32 bits.  A JAX
    array is bitcast on the device."""
    if isinstance(x, np.ndarray):
        return _host_to_blocks(x, num_blocks)
    nbytes = x.size * x.dtype.itemsize
    num_blocks = num_blocks or num_blocks_of(nbytes)
    flat_u8 = jax.lax.bitcast_convert_type(x.reshape(-1), jnp.uint8).reshape(-1)
    pad = num_blocks * BLOCK_BYTES - nbytes
    if pad:
        flat_u8 = jnp.concatenate([flat_u8, jnp.zeros((pad,), jnp.uint8)])
    as_i32 = jax.lax.bitcast_convert_type(
        flat_u8.reshape(-1, 4), jnp.int32
    ).reshape(num_blocks, 8, 128)
    meta = BlockMeta(str(x.dtype), tuple(x.shape), nbytes, num_blocks)
    return as_i32, meta


def _host_to_blocks(x: np.ndarray,
                    num_blocks: Optional[int]) -> Tuple[jnp.ndarray, BlockMeta]:
    num_blocks = num_blocks or num_blocks_of(x.nbytes)
    raw = np.ascontiguousarray(x).reshape(-1).view(np.uint8)
    pad = num_blocks * BLOCK_BYTES - x.nbytes
    if pad:
        raw = np.concatenate([raw, np.zeros((pad,), np.uint8)])
    blocks = jnp.asarray(raw.view(np.int32).reshape(num_blocks, 8, 128))
    return blocks, BlockMeta(str(x.dtype), tuple(x.shape), x.nbytes, num_blocks)


def from_blocks(blocks, meta: BlockMeta):
    """Inverse of :func:`to_blocks`.  NumPy blocks (fetched from the device)
    are viewed on the host, which any dtype survives; JAX blocks are bitcast
    on the device and must hold a dtype the device has."""
    if isinstance(blocks, np.ndarray):
        flat = blocks.reshape(-1).view(np.uint8)[: meta.nbytes]
        return flat.view(jnp.dtype(meta.dtype)).reshape(meta.shape)
    flat_u8 = jax.lax.bitcast_convert_type(
        blocks.reshape(-1), jnp.uint8
    ).reshape(-1)[: meta.nbytes]
    dtype = jnp.dtype(meta.dtype)
    itemsize = dtype.itemsize
    if itemsize > 1:
        flat = jax.lax.bitcast_convert_type(flat_u8.reshape(-1, itemsize), dtype)
    else:
        flat = jax.lax.bitcast_convert_type(flat_u8, dtype)
    return flat.reshape(meta.shape)


# ------------------------------------------------------------------ XOR delta
def xor_encode(base_blocks: jnp.ndarray, new_blocks: jnp.ndarray) -> jnp.ndarray:
    return xor_delta(base_blocks, new_blocks)


def xor_apply(base_blocks: jnp.ndarray, delta_blocks: jnp.ndarray) -> jnp.ndarray:
    return xor_delta(base_blocks, delta_blocks)


# ---------------------------------------------------------------- block hash
# built on first use, never at import (that would initialize a backend and,
# on a TPU host, take the chip); functools.cache keeps the first use safe
# under the serving tier's reader threads — concurrent first callers at worst
# build equal arrays, and no caller sees a half-assigned global
@functools.cache
def _hash_coef() -> jnp.ndarray:
    return jnp.asarray(hash_coefficients())


def block_hashes(blocks: jnp.ndarray) -> jnp.ndarray:
    return block_hash(blocks, _hash_coef())[:, 0]


# --------------------------------------------------------- block-sparse delta
def _round_capacity(k: int) -> int:
    cap = 8
    while cap < k:
        cap *= 2
    return cap


@functools.partial(jax.jit, static_argnames=("capacity",))
def _compact(mask: jnp.ndarray, new_blocks: jnp.ndarray, capacity: int):
    """Pack changed block rows into (idx[capacity], blocks[capacity]).

    Padding slots are *collision-free by construction*: they point at the
    first unchanged row (where new == base, so a redundant write is a no-op),
    or — when every row changed — at row 0 carrying row 0's new content (a
    redundant write of correct data).  The apply kernel therefore never needs
    conditional stores for slots emitted by this function.
    """
    m = mask[:, 0].astype(jnp.int32)
    nb = m.shape[0]
    order = jnp.cumsum(m) - 1  # destination slot per changed row
    slots = jnp.where(m == 1, order, capacity)  # unchanged -> dropped
    # explicit index_dtype: int32 whether or not jax_enable_x64 is active
    pad_row = jax.lax.argmin(m, 0, jnp.int32)  # first unchanged row (0 if none)
    idx = jnp.full((capacity,), -1, jnp.int32)
    idx = idx.at[slots].set(jnp.arange(nb, dtype=jnp.int32), mode="drop")
    idx = jnp.where(idx >= 0, idx, pad_row)
    gathered = new_blocks[idx]
    # NB the returned count is the *true* number of changed rows: when it
    # exceeds ``capacity`` the drop-mode scatter above has discarded the
    # overflow and the packed delta is incomplete — callers must check
    # (sparse_encode raises host-side; fully-traced callers branch on it).
    return idx, gathered, jnp.sum(m, dtype=jnp.int32)


def count_changed(
    base_blocks: jnp.ndarray, new_blocks: jnp.ndarray
) -> Tuple[jnp.ndarray, int]:
    """The changed-block mask and the changed count, on the host.

    The one device→host sync of a diff: the mask sum both sizes the
    capacity and *is* the changed count, so :func:`_compact`'s (identical)
    device-side count is never materialized host-side."""
    mask = changed_block_mask(base_blocks, new_blocks)
    return mask, int(jnp.sum(mask[:, 0]))


def compact_capacity(n: int, num_blocks: int) -> int:
    """The capacity :func:`compact` packs ``n`` changed rows of a
    ``num_blocks``-row mask into: a power of two, and at least 1/32 of the
    rows.  A leaf whose changed count wanders from version to version (a
    table's update batches) then keeps one capacity, and one ``_compact``
    program, while the count stays under ~3% of its blocks; a leaf meets at
    most six capacities."""
    return _round_capacity(max(1, n, num_blocks // 32))


def compact(
    mask: jnp.ndarray, new_blocks: jnp.ndarray, n: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(idx, blocks)`` of the ``n`` changed rows, in
    :func:`compact_capacity` slots (padded past ``n``; see
    :func:`_compact`)."""
    capacity = compact_capacity(n, mask.shape[0])
    idx, blocks, _ = _compact(mask, new_blocks, capacity)
    return idx, blocks


def sparse_encode(
    base_blocks: jnp.ndarray, new_blocks: jnp.ndarray, *, capacity: int
) -> Tuple[jnp.ndarray, jnp.ndarray, int]:
    """Return (idx, packed_blocks, n_changed) for the block-sparse delta in
    a fixed ``capacity``, which keeps jit recompiles bounded.

    The capacity must cover the changed count: an undersized capacity would
    silently drop changed blocks in ``_compact`` (producing a delta that
    ``sparse_apply`` cannot detect as corrupt), so this host-side wrapper
    raises instead.  To size the capacity from the exact count (the commit
    path), use :func:`count_changed` then :func:`compact`.  Fully-traced
    callers should use ``_compact`` directly and branch on the returned
    count.
    """
    mask = changed_block_mask(base_blocks, new_blocks)
    idx, blocks, n_dev = _compact(mask, new_blocks, capacity)
    n = int(n_dev)
    if n > capacity:
        raise ValueError(
            f"sparse_encode capacity overflow: {n} changed blocks exceed "
            f"capacity={capacity}; pass capacity>={_round_capacity(n)} (or "
            f"size it with count_changed and compact)"
        )
    return idx, blocks, n


def sparse_apply(
    base_blocks: jnp.ndarray, packed_blocks: jnp.ndarray, idx: jnp.ndarray
) -> jnp.ndarray:
    return sparse_delta_apply(base_blocks, packed_blocks, idx)


# ------------------------------------------------------------- chain apply
def chain_apply(
    base_blocks: jnp.ndarray, packed_blocks: jnp.ndarray, idx: jnp.ndarray
) -> jnp.ndarray:
    """Fused K-step chain application (see :mod:`.chain_apply`): ``idx`` /
    ``packed_blocks`` stack K packed sparse deltas in chain order, flat or
    ``(K, capacity)``-shaped, padding ``idx < 0``."""
    return chain_delta_apply(base_blocks, packed_blocks, idx)


def chain_apply_batched(
    base_stack: jnp.ndarray, packed_blocks: jnp.ndarray, idx: jnp.ndarray
) -> jnp.ndarray:
    """Fused chain application for L same-sized leaves in one launch."""
    return chain_delta_apply_batched(base_stack, packed_blocks, idx)
