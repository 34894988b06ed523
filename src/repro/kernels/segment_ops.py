"""Pallas kernels: segment-min / segment-argmin over padded CSR rows.

The jitted solver backend (:mod:`repro.core.solvers.jax_backend`) reshapes
the version graph's CSR rows into a dense ``(rows, width)`` matrix padded
with ``+inf`` — in-edges per vertex for the SSSP relaxation, flat candidate
vectors for the LMG scoring round.  The reductions over that layout are the
solver inner loops, and they compile to single-pass VMEM row reductions:

* :func:`segment_min_rows`    — per-row minimum (the Bellman-Ford relaxation);
* :func:`segment_argmin_rows` — per-row first-minimum index (parent/candidate
  selection; first occurrence matches NumPy tie-breaking);
* :func:`min_argmin_1d`       — global ``(min, argmin)`` of a flat vector via
  the row kernels (vertex selection in Prim/MP, ρ-argmax in LMG).

Each wrapper takes ``use_pallas``: ``True`` routes through the Pallas kernels
(interpreted where the backend is the CPU, compiled on a TPU — see
:func:`repro.kernels.resolve_interpret`), ``False`` lowers the same
reduction through plain XLA ops — the fast path on CPU, where the Pallas
interpreter adds per-call overhead.  Both paths are bit-identical: the
reductions are order-insensitive min/first-argmin over the same floats.

Index math is int32 end to end: argmins use an explicit ``index_dtype`` and
the flat-index reconstruction in :func:`min_argmin_1d` guards its int32
capacity host-side instead of relying on ``jax_enable_x64`` widening (which
silently does not happen in the default production mode).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from . import resolve_interpret

DEFAULT_ROWS_PER_PROGRAM = 256
LANE = 128  # pad the minor dim to the TPU lane width

# largest flat vector min_argmin_1d can index with int32 math (padded length
# r * LANE + col must not wrap); guarded host-side because shapes are static
MAX_INT32_ELEMS = (1 << 31) - 1 - LANE


def _min_kernel(x_ref, o_ref):
    o_ref[...] = jnp.min(x_ref[...], axis=1)[:, None]


def _argmin_kernel(x_ref, o_ref):
    # explicit index_dtype: jnp.argmin would emit int64 under jax_enable_x64
    # (and silently int32 without it) — int32 is the contract either way
    o_ref[...] = lax.argmin(x_ref[...], 1, jnp.int32)[:, None]


def _row_call(kernel, x: jnp.ndarray, out_dtype, *, rows_per_program: int,
              interpret: bool | None) -> jnp.ndarray:
    nr, nc = x.shape
    rows = min(rows_per_program, nr)
    grid = (pl.cdiv(nr, rows),)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows, nc), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nr, 1), out_dtype),
        interpret=resolve_interpret(interpret),
    )(x)[:, 0]


def segment_min_rows(
    x: jnp.ndarray,
    *,
    use_pallas: bool = True,
    rows_per_program: int = DEFAULT_ROWS_PER_PROGRAM,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Per-row minimum of a padded ``(rows, width)`` segment matrix."""
    if not use_pallas:
        return jnp.min(x, axis=1)
    return _row_call(_min_kernel, x, x.dtype,
                     rows_per_program=rows_per_program, interpret=interpret)


def segment_argmin_rows(
    x: jnp.ndarray,
    *,
    use_pallas: bool = True,
    rows_per_program: int = DEFAULT_ROWS_PER_PROGRAM,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Per-row index of the first minimum (NumPy ``argmin`` tie-breaking)."""
    if not use_pallas:
        return lax.argmin(x, 1, jnp.int32)
    return _row_call(_argmin_kernel, x, jnp.int32,
                     rows_per_program=rows_per_program, interpret=interpret)


def pad_to_rows(x: jnp.ndarray, fill) -> jnp.ndarray:
    """Reshape a flat vector to ``(rows, LANE)``, padding the tail with
    ``fill`` — the layout the row kernels reduce over."""
    n = x.shape[0]
    pad = (-n) % LANE
    if pad:
        x = jnp.concatenate([x, jnp.full((pad,), fill, x.dtype)])
    return x.reshape(-1, LANE)


def min_argmin_1d(
    x: jnp.ndarray, *, use_pallas: bool = True
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Global ``(min, first argmin)`` of a flat float vector.

    Two-stage: per-row kernel reduction, then a (tiny) reduction over row
    minima.  First-occurrence semantics survive both stages — the first row
    attaining the global min is picked, then the first column within it.

    Flat indices are int32 (``r * LANE + col`` never widens): vectors longer
    than :data:`MAX_INT32_ELEMS` are refused host-side rather than silently
    wrapping — without ``jax_enable_x64`` an ``astype(int64)`` would have
    been a silent int32 downcast anyway.
    """
    if x.shape[0] > MAX_INT32_ELEMS:
        raise ValueError(
            f"min_argmin_1d int32 index capacity exceeded: {x.shape[0]} "
            f"elements > {MAX_INT32_ELEMS}"
        )
    if not use_pallas:
        i = lax.argmin(x, 0, jnp.int32)
        return x[i], i
    x2 = pad_to_rows(x, jnp.inf)
    row_min = segment_min_rows(x2, use_pallas=True)
    r = lax.argmin(row_min, 0, jnp.int32)
    # argmin only the winning row — a full per-row argmin pass would double
    # the kernel work for a single consumed lane
    col = segment_argmin_rows(x2[r][None, :], use_pallas=True)[0]
    i = r * LANE + col
    return row_min[r], jnp.minimum(i, x.shape[0] - 1)
