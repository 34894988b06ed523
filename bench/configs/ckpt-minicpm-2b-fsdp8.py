"""Plain reference of ``ckpt-minicpm-2b-fsdp8``: every saved version's tree
from the seed, in NumPy, independent of the store.

Version 0 is rank 0's shard of a MiniCPM-2B checkpoint (bf16 weights drawn
from the seed, an int64 step counter); save ``i`` moves every bf16 weight of
its parent one unit in the last place, so that every 4 KiB block changes,
and sets the step to ``1000 * i``.  A save depends only on its index, so
any version's tree is its parent's tree with its edit applied.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import ml_dtypes
import numpy as np

BF16 = np.dtype(ml_dtypes.bfloat16)
Tree = Dict[str, np.ndarray]


def _rows(n: int, cfg: dict) -> int:
    """Rank 0's share of ``n`` rows under the stated row slicing."""
    return -(-n // cfg["fsdp_ranks"])


def layout(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], np.dtype]]:
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    spec = {"params/embed": ((_rows(cfg["vocab_size"], cfg), d), BF16),
            "params/final_norm": ((d,), BF16)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"params/layers/{i}"
        spec.update({
            f"{p}/attn_norm": ((d,), BF16),
            f"{p}/attn/wq": ((_rows(d, cfg), q), BF16),
            f"{p}/attn/wk": ((_rows(d, cfg), kv), BF16),
            f"{p}/attn/wv": ((_rows(d, cfg), kv), BF16),
            f"{p}/attn/wo": ((_rows(q, cfg), d), BF16),
            f"{p}/mlp_norm": ((d,), BF16),
            f"{p}/mlp/w_gate": ((_rows(d, cfg), ff), BF16),
            f"{p}/mlp/w_up": ((_rows(d, cfg), ff), BF16),
            f"{p}/mlp/w_down": ((_rows(ff, cfg), d), BF16),
        })
    spec["step"] = ((), np.dtype(np.int64))
    return spec


def _weights(rng: np.random.Generator, n: int) -> np.ndarray:
    """n bf16 weights: random sign and mantissa, the exponent uniform over
    the 8 binades below 1."""
    raw = np.frombuffer(rng.bytes(2 * n), np.uint16)
    exponent = ((raw >> np.uint16(7)) & np.uint16(7)) + np.uint16(119)
    return ((raw & np.uint16(0x807F)) | (exponent << np.uint16(7))).view(BF16)


def base_tree(cfg: dict, seed: int) -> Tree:
    tree: Tree = {}
    for j, (key, (shape, dt)) in enumerate(layout(cfg).items()):
        if dt == np.int64:
            tree[key] = np.zeros(shape, dt)
        else:
            rng = np.random.default_rng([seed % (1 << 64), 0, j])
            tree[key] = _weights(rng, int(np.prod(shape))).reshape(shape)
    return tree


def edit(cfg: dict, seed: int, index: int) -> dict:
    """Save ``index``'s change: every bf16 weight one unit in the last place
    away from zero, the step counter to ``1000 * index``."""
    return {"ulps": 1, "step": 1000 * index}


def apply(cfg: dict, tree: Tree, change: dict) -> Tree:
    """``tree`` with ``change`` applied, as a new tree."""
    out = {k: (a.view(np.uint16) + np.uint16(change["ulps"])).view(BF16)
           for k, a in tree.items() if a.dtype == BF16}
    out["step"] = np.array(change["step"], np.int64)
    return out


def control(cfg: dict, tree: Tree, parent: Optional[Tree]) -> Tree:
    """The reference one precision below the stated bf16: every bf16 leaf
    through float8_e4m3fn and back."""
    f8 = np.dtype(ml_dtypes.float8_e4m3fn)
    return {k: (a.astype(f8).astype(BF16) if a.dtype == BF16 else a)
            for k, a in tree.items()}
