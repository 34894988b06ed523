"""Pallas TPU kernel: fused delta-*chain* application — whole-chain checkout.

Recreating ``V_k`` along a K-step storage chain used to pay K sequential
:func:`~repro.kernels.sparse_apply.sparse_delta_apply` dispatches, each
bracketed by a host↔device round trip.  This kernel applies the *entire*
chain in one dispatch: the K packed sparse deltas are first folded into one
effective write set without touching the base, then scattered in a single
pass.

Folding (``_fold_dest``, plain XLA inside the jit): the chain's sparse
deltas carry *new block content* (not XOR), so chain composition is
last-writer-wins per block row.  Flattening the ``(K, capacity)`` delta
stack in chain order makes "last writer" simply the **maximum flat slot id**
writing a row — a single deterministic scatter-max builds the winner map,
and a gather marks every slot as winner / loser.  Losers and padding slots
(``idx < 0``) are redirected to a trash row appended past the base, so the
scatter kernel needs **no conditional stores**: every grid step
unconditionally copies its 4 KiB block to its destination row, exactly one
winner lands on every changed row, and everything else lands on the trash
row that is sliced off afterwards.  (``_compact``'s collision-free padding
contract makes its padding slots redundant self-writes; the redirect handles
them and plain ``-1`` wire padding uniformly.)

The scatter itself mirrors ``sparse_apply``: a scalar-prefetched destination
vector drives the output BlockSpec, ``input_output_aliases`` keeps the base
in place, and the cost is O(total changed bytes + base copy), independent of
chain depth K.

``chain_delta_apply_batched`` fuses *many leaves* (same block count, same
padded slot count — the shape-bucketing contract of
``store/delta.py``) into one launch by offsetting each leaf's block rows
into a concatenated row space with a single shared trash row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret


def _fold_dest(idx_flat: jnp.ndarray, num_rows: int) -> jnp.ndarray:
    """Destination row per flat slot: its block row if the slot is the chain's
    last writer of that row, else the trash row ``num_rows``.

    ``idx_flat`` is the chain's packed block-row indices flattened in chain
    order; negative entries are padding.  Later slots win (scatter-max over
    flat slot ids — deterministic, unlike duplicate-index scatter-set).
    """
    s = idx_flat.shape[0]
    slot_ids = jnp.arange(s, dtype=jnp.int32)
    valid = idx_flat >= 0
    rows = jnp.where(valid, idx_flat, num_rows)
    winner = (
        jnp.full((num_rows,), -1, jnp.int32)
        .at[rows]
        .max(slot_ids, mode="drop")  # drop: padding rows point out of bounds
    )
    win_of_slot = winner[jnp.where(valid, idx_flat, 0)]
    is_winner = valid & (win_of_slot == slot_ids)
    return jnp.where(is_winner, idx_flat, num_rows).astype(jnp.int32)


def _chain_kernel(dest_ref, base_ref, blocks_ref, o_ref):
    # dest redirection already resolved winners host/XLA-side: every grid
    # step writes its block unconditionally (losers land on the trash row)
    del dest_ref, base_ref
    o_ref[...] = blocks_ref[...]


def _chain_call(
    base: jnp.ndarray, blocks: jnp.ndarray, idx: jnp.ndarray,
    interpret: bool | None,
) -> jnp.ndarray:
    """One fused scatter over ``(num_rows + 1)`` rows (last row = trash)."""
    nb = base.shape[0]
    s = idx.shape[0]
    dest = _fold_dest(idx, nb)
    base_p = jnp.concatenate([base, jnp.zeros((1, 8, 128), jnp.int32)], axis=0)

    def dest_row(i, dest_ref):
        return (dest_ref[i], 0, 0)

    out = pl.pallas_call(
        _chain_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s,),
            in_specs=[
                pl.BlockSpec((1, 8, 128), dest_row),  # base tile (aliased out)
                pl.BlockSpec((1, 8, 128), lambda i, dest_ref: (i, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 8, 128), dest_row),
        ),
        out_shape=jax.ShapeDtypeStruct(base_p.shape, base_p.dtype),
        input_output_aliases={1: 0},  # alias `base_p` (arg after prefetch)
        interpret=resolve_interpret(interpret),
    )(dest, base_p, blocks)
    return out[:nb]


@functools.partial(jax.jit, static_argnames=("interpret",))
def chain_delta_apply(
    base: jnp.ndarray,
    blocks: jnp.ndarray,
    idx: jnp.ndarray,
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Apply a whole K-step sparse-delta chain to one blocked leaf.

    base   : (num_blocks, 8, 128) int32
    blocks : (K, capacity, 8, 128) or flat (S, 8, 128) int32 packed content
    idx    : (K, capacity) or flat (S,) int32 block rows, -1 = padding;
             flat order IS chain order (step 0 first) — later slots win.

    Bit-identical to folding ``sparse_delta_apply`` over the K steps, in one
    jitted dispatch.
    """
    assert base.dtype == jnp.int32 and base.shape[1:] == (8, 128)
    idx = idx.reshape(-1)
    blocks = blocks.reshape(-1, 8, 128)
    assert blocks.dtype == jnp.int32 and idx.shape[0] == blocks.shape[0]
    if idx.shape[0] == 0:
        return base
    return _chain_call(base, blocks, idx, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def chain_delta_apply_batched(
    bases: jnp.ndarray,
    blocks: jnp.ndarray,
    idx: jnp.ndarray,
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Apply L independent delta chains to L same-sized leaves in ONE launch.

    bases  : (L, num_blocks, 8, 128) int32
    blocks : (L, S, 8, 128) int32 — each leaf's chain flattened+padded to S
    idx    : (L, S) int32 block rows within the leaf, -1 = padding

    Leaves' rows are offset into one concatenated row space (rows are
    disjoint across leaves, so the per-row chain order is preserved) and
    share a single trash row.
    """
    l, nb = bases.shape[0], bases.shape[1]
    s = idx.shape[1]
    assert blocks.shape[:2] == (l, s)
    if s == 0 or l == 0:
        return bases
    offs = (jnp.arange(l, dtype=jnp.int32) * nb)[:, None]
    idx_flat = jnp.where(idx >= 0, idx + offs, -1).reshape(-1)
    out = _chain_call(
        bases.reshape(l * nb, 8, 128), blocks.reshape(l * s, 8, 128),
        idx_flat, interpret,
    )
    return out.reshape(l, nb, 8, 128)
