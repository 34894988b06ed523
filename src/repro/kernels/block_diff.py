"""Pallas TPU kernels: changed-block detection + block content hashing.

These feed the store's block-sparse delta encoder (DESIGN.md §5): the
changed-block mask selects which 4 KiB blocks of a new checkpoint shard
actually differ from the delta base, and the block hash provides dedup hints
for content addressing.  Both are single-pass VMEM reductions over the
(num_blocks, 8, 128) int32 block layout.  The kernel reduces only the
sublane axis and writes a lane-dense ``(num_blocks, 128)`` partial; XLA
finishes the lane reduction to the ``(num_blocks, 1)`` result.  (Reducing
both minor axes in the kernel and writing ``(rows, 1)`` aborts the v5e
Mosaic compiler at layout assignment.)  The partial costs one extra HBM
write of 512 B per 4 KiB block read.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import resolve_interpret

DEFAULT_ROWS_PER_PROGRAM = 256


def _mask_kernel(a_ref, b_ref, o_ref):
    diff = (a_ref[...] != b_ref[...]).astype(jnp.int32)
    o_ref[...] = jnp.max(diff, axis=1)


def _lane_partial_call(kernel, x, *operands, rows_per_program, interpret):
    """Run ``kernel`` over row tiles of the ``(nb, 8, 128)`` block arrays in
    ``(x,) + operands`` (each either block-shaped or one ``(8, 128)`` tile
    broadcast to every program), returning its ``(nb, 128)`` partial."""
    nb = x.shape[0]
    rows = min(rows_per_program, nb)
    blk_spec = pl.BlockSpec((rows,) + x.shape[1:], lambda i: (i, 0, 0))
    tile_spec = pl.BlockSpec(x.shape[1:], lambda i: (0, 0))
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(nb, rows),),
        in_specs=[blk_spec]
        + [blk_spec if o.ndim == 3 else tile_spec for o in operands],
        out_specs=pl.BlockSpec((rows, x.shape[2]), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, x.shape[2]), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(x, *operands)


@functools.partial(jax.jit, static_argnames=("rows_per_program", "interpret"))
def changed_block_mask(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    rows_per_program: int = DEFAULT_ROWS_PER_PROGRAM,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """(num_blocks, 1) int32 mask of blocks where ``a`` and ``b`` differ."""
    assert a.shape == b.shape and a.dtype == b.dtype == jnp.int32
    part = _lane_partial_call(
        _mask_kernel, a, b,
        rows_per_program=rows_per_program, interpret=interpret,
    )
    return jnp.max(part, axis=1, keepdims=True)


def _hash_kernel(x_ref, coef_ref, o_ref):
    prod = x_ref[...] * coef_ref[...]
    o_ref[...] = jnp.sum(prod, axis=1, dtype=jnp.int32)


def hash_coefficients(seed: int = 0x9E3779B9) -> np.ndarray:
    """Deterministic odd per-position multipliers for the block hash."""
    rng = np.random.RandomState(seed & 0x7FFFFFFF)
    coef = rng.randint(0, 2**31, size=(8, 128), dtype=np.int64)
    coef = (coef * 2 + 1).astype(np.int64)  # odd => position-bijective
    return coef.astype(np.uint32).view(np.int32).reshape(8, 128)


@functools.partial(jax.jit, static_argnames=("rows_per_program", "interpret"))
def block_hash(
    x: jnp.ndarray,
    coef: jnp.ndarray,
    *,
    rows_per_program: int = DEFAULT_ROWS_PER_PROGRAM,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """(num_blocks, 1) int32 position-weighted hash per 4 KiB block."""
    assert x.dtype == jnp.int32 and coef.shape == x.shape[1:]
    part = _lane_partial_call(
        _hash_kernel, x, coef,
        rows_per_program=rows_per_program, interpret=interpret,
    )
    # int32 addition wraps modulo 2**32 in any order: bit-identical to the
    # single whole-tile sum of block_hash_ref
    return jnp.sum(part, axis=1, dtype=jnp.int32, keepdims=True)
