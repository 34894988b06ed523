"""Pallas TPU flash-attention (forward) — the §Perf structural fix.

The dry-run's dominant memory term for every attention arch is the unfused
online-softmax chain: XLA materializes each (B, Sq, KV, g, chunk) f32
score/probability tensor in HBM (~15 round trips per layer; e.g. 928 × 0.5 GB
at deepseek train_4k).  This kernel keeps the whole chain in VMEM: HBM
traffic collapses to Q + K + V + O (+ the tiny m/l carries), i.e.

    bytes ≈ 2·B·S·(H + 2·KV)·hd·bf16   per layer
    vs    ≳ 12·B·S²/chunk-scaled f32 score traffic for the unfused chain.

Grid: (batch·kv_head, q_blocks) with an inner fori_loop over KV blocks —
one (block_q × block_k) f32 score tile lives in registers/VMEM at a time.
Blocks default to 512×512 (q-tile 512×128 bf16 = 128 KiB; score tile
512×512 f32 = 1 MiB — comfortably inside v5e's 128 MiB VMEM with double
buffering).  MXU dims (block_q, hd, block_k) are all multiples of 128.

Causal masking is positional (global offsets), so the same kernel serves
prefill (Sq == Sk) and chunked-prefill.  GQA folds the group into the
q-block rows.  Forward-only: serving paths use it directly; the train
backward would pair it with dq/dk/dv kernels (future work, noted in
EXPERIMENTS §Perf).  Validated against layers.chunked_attention in
interpret mode (tests/test_flash_attention.py).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import resolve_interpret

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -0.7 * float(np.finfo(np.float32).max)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, causal: bool,
                  scale: float, seq_k: int):
    # q_ref: (block_q, g, hd) for one (b, kv_head, q_block); k/v: (seq_k, hd)
    qi = pl.program_id(1)
    block_q = q_ref.shape[0]
    g, hd = q_ref.shape[1], q_ref.shape[2]
    q = (q_ref[...].astype(jnp.float32) * scale).reshape(block_q * g, hd)
    q = q.astype(q_ref.dtype)

    n_kb = seq_k // block_k
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, g), 0
    ).reshape(block_q * g)

    def body(kb, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(kb * block_k, block_k), :]          # (block_k, hd)
        v = v_ref[pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                    # (bq*g, block_k)
        if causal:
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            s = jnp.where(k_pos <= q_pos[:, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc = acc * corr[:, None] + pv
        return m_new, l_new, acc

    m0 = jnp.full((block_q * g,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q * g,), jnp.float32)
    a0 = jnp.zeros((block_q * g, hd), jnp.float32)
    if causal:
        # only blocks up to the diagonal contribute
        last = jnp.minimum(n_kb, (qi + 1) * block_q // block_k + 1)
    else:
        last = n_kb
    m, l, acc = jax.lax.fori_loop(0, last, body, (m0, l0, a0))
    out = acc / jnp.maximum(l[:, None], 1e-30)
    o_ref[...] = out.reshape(block_q, g, hd).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret", "softmax_scale"),
)
def flash_attention(
    q: jnp.ndarray,   # (B, Sq, H, hd)
    k: jnp.ndarray,   # (B, Sk, KV, hd)
    v: jnp.ndarray,   # (B, Sk, KV, hd)
    *,
    causal: bool = True,
    softmax_scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool | None = None,
) -> jnp.ndarray:
    B, Sq, H, hd = q.shape
    _, Sk, KV, hd_v = v.shape
    assert H % KV == 0 and hd == k.shape[-1] and hd_v == hd, \
        "flash kernel requires uniform head dims (MLA uses the XLA path)"
    g = H // KV
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(hd)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0

    # layout: (B, KV, Sq, g, hd) so one grid step owns one (b, kv) pair
    qt = q.reshape(B, Sq, KV, g, hd).transpose(0, 2, 1, 3, 4)
    kt = k.transpose(0, 2, 1, 3)  # (B, KV, Sk, hd)
    vt = v.transpose(0, 2, 1, 3)

    grid = (B * KV, Sq // block_q)
    kernel = functools.partial(
        _flash_kernel, block_k=block_k, causal=causal, scale=scale, seq_k=Sk
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, g, hd),
                         lambda bh, qi: (bh // KV, bh % KV, qi, 0, 0)),
            pl.BlockSpec((None, None, Sk, hd),
                         lambda bh, qi: (bh // KV, bh % KV, 0, 0)),
            pl.BlockSpec((None, None, Sk, hd),
                         lambda bh, qi: (bh // KV, bh % KV, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, g, hd),
                               lambda bh, qi: (bh // KV, bh % KV, qi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, Sq, g, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3, 4).reshape(B, Sq, H, hd)


def flash_hbm_bytes(B: int, Sq: int, Sk: int, H: int, KV: int, hd: int,
                    dtype_bytes: int = 2) -> int:
    """Analytic HBM traffic of the kernel (the §Perf substitution term):
    Q and O once; K/V once per q-block wave (VMEM-resident within a wave)."""
    q_o = 2 * B * Sq * H * hd * dtype_bytes
    kv = 2 * B * Sk * KV * hd * dtype_bytes * max(1, Sq // DEFAULT_BLOCK_Q)
    return q_o + kv
