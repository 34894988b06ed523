"""Pallas kernel package for the delta/storage hot paths.

Interpret-mode policy
---------------------
Every Pallas kernel in this package (``xor_delta``, ``block_diff``,
``sparse_apply``, ``chain_apply``, ``segment_ops``, ``flash_attention``)
takes ``interpret=None`` by default and resolves it through
:func:`resolve_interpret` when it is called (traced), never when it is
imported: the kernel body runs under the Pallas interpreter only where the
default backend is the CPU, and is compiled through Mosaic on a TPU.  An
explicit ``interpret=True/False`` overrides the platform (tests exercise
both, and a compile for a described TPU topology passes ``False``, since the
process's default backend is still the CPU there).
"""

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """``interpret`` if given, else whether the default backend is the CPU.

    Initializes JAX's backends on first use, so call it from a kernel
    wrapper (at trace time), not at import."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() == "cpu"
