"""Pallas TPU kernel: block-sparse delta *apply* — the recreation hot path.

Recreating version ``V_j`` from ``V_i`` along the storage tree applies a
packed set of changed 4 KiB blocks onto the base shard.  The kernel uses a
scalar-prefetched index vector so the output BlockSpec can place each delta
block at its dynamic destination row — the TPU analogue of a scattered
memcpy, one VMEM tile per grid step.  ``input_output_aliases`` keeps the
base in place: unchanged blocks are never touched, so the cost is
O(changed bytes), matching the Φ model of DESIGN.md §5.

Padding rows (idx < 0) are redirected to row 0 and masked by writing the
base tile back (`jnp.where` on the prefetched index).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret


def _apply_kernel(idx_ref, base_ref, blocks_ref, o_ref):
    i = pl.program_id(0)
    valid = idx_ref[i] >= 0
    o_ref[...] = jnp.where(valid, blocks_ref[...], base_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def sparse_delta_apply(
    base: jnp.ndarray,
    blocks: jnp.ndarray,
    idx: jnp.ndarray,
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Scatter ``blocks[k]`` into ``base[idx[k]]``; idx<0 rows are padding.

    base   : (num_blocks, 8, 128) int32
    blocks : (k, 8, 128) int32
    idx    : (k,) int32
    """
    assert base.dtype == blocks.dtype == jnp.int32
    assert base.shape[1:] == blocks.shape[1:] == (8, 128)
    assert idx.shape == (blocks.shape[0],)
    k = blocks.shape[0]

    def dest_row(i, idx_ref):
        return (jnp.maximum(idx_ref[i], 0), 0, 0)

    return pl.pallas_call(
        _apply_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(k,),
            in_specs=[
                pl.BlockSpec((1, 8, 128), dest_row),  # base tile at the dest
                pl.BlockSpec((1, 8, 128), lambda i, idx_ref: (i, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 8, 128), dest_row),
        ),
        out_shape=jax.ShapeDtypeStruct(base.shape, base.dtype),
        input_output_aliases={1: 0},  # alias `base` (arg after prefetch) to out
        interpret=resolve_interpret(interpret),
    )(idx, base, blocks)
