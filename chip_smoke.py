#!/usr/bin/env python3
"""Chip smoke: the versioned store's commit/checkout path on one TPU chip.

Builds a training job's checkpoint history at MiniCPM-2B's published widths
(``repro.configs.ARCHS["minicpm-2b"]``, arXiv 2404.06395: d_model 2304,
36x64 heads, SwiGLU d_ff 5760, tied 122753-row embedding, bf16) with
``n_layers`` cut from 40 to 4, plus a float32 optimizer moment, a float64
dataset column and an int64 step counter.  Through the normal entry points
(``Repository``, ``DatasetService``) it commits a base version, four
fine-tunes on ``main`` (each rewriting ~2% of the 4 KiB blocks of a few
leaves) and one on a branch; checks the tip out cold at chain depth 4, then
warm, then a batch across both branches; repacks under a Problem 6 spec,
runs fsck, and checks everything out again.  Every checkout must be
bit-identical to what was committed, and the Pallas kernels must have been
compiled for the chip, not interpreted.

Run from the checkout root on a machine with a TPU:

    python3 chip_smoke.py [--seed N]

It exits nonzero, and prints no result, unless JAX's default device is a
TPU.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import ARCHS, ModelConfig  # noqa: E402
from repro.core import OptimizeSpec  # noqa: E402
from repro.kernels import resolve_interpret  # noqa: E402
from repro.kernels.block_diff import changed_block_mask  # noqa: E402
from repro.kernels.chain_apply import chain_delta_apply  # noqa: E402
from repro.kernels.ref import BLOCK_BYTES  # noqa: E402
from repro.store import Repository  # noqa: E402

ARCH = "minicpm-2b"
SMOKE_LAYERS = 4            # published: 40
DATA_ROWS = 1 << 20         # float64 dataset column
FINE_TUNES = 4              # commits on main after the base: tip depth 4
REWRITE_FRAC = 0.02         # share of a tuned leaf's 4 KiB blocks rewritten
CACHE_BUDGET = 8 << 30      # holds every version of the history (~1.1 GB each)

Tree = Dict[str, np.ndarray]
Spec = Dict[str, Tuple[Tuple[int, ...], np.dtype]]
Log = Callable[[str], None]


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ------------------------------------------------------------------- data
def leaf_specs(cfg: ModelConfig, n_layers: int, data_rows: int) -> Spec:
    """Leaf shapes and dtypes of one checkpoint: a dense SwiGLU transformer
    with tied embeddings (bf16), one float32 Adam moment, a float64 dataset
    column and an int64 step counter."""
    bf16 = np.dtype(jnp.bfloat16)
    d, hd = cfg.d_model, cfg.head_dim or cfg.d_model // cfg.n_heads
    q, kv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    spec: Spec = {"params/embed": ((cfg.vocab, d), bf16),
                  "params/final_norm": ((d,), bf16)}
    for i in range(n_layers):
        p = f"params/layers/{i}"
        spec.update({
            f"{p}/attn_norm": ((d,), bf16),
            f"{p}/attn/wq": ((d, q), bf16),
            f"{p}/attn/wk": ((d, kv), bf16),
            f"{p}/attn/wv": ((d, kv), bf16),
            f"{p}/attn/wo": ((q, d), bf16),
            f"{p}/mlp_norm": ((d,), bf16),
            f"{p}/mlp/w_gate": ((d, cfg.d_ff), bf16),
            f"{p}/mlp/w_up": ((d, cfg.d_ff), bf16),
            f"{p}/mlp/w_down": ((cfg.d_ff, d), bf16),
        })
    spec["opt/mu/layers/0/mlp/w_up"] = ((d, cfg.d_ff), np.dtype(np.float32))
    spec["data/sample_weights"] = ((data_rows,), np.dtype(np.float64))
    spec["step"] = ((), np.dtype(np.int64))
    return spec


def _random(rng: np.random.Generator, shape, dtype: np.dtype) -> np.ndarray:
    if dtype == np.float64:
        return rng.random(shape)
    return rng.standard_normal(shape, dtype=np.float32).astype(dtype)


def make_tree(spec: Spec, rng: np.random.Generator) -> Tree:
    """The base checkpoint: random float leaves, counters at zero."""
    return {
        k: np.zeros(shape, dt) if dt == np.int64 else _random(rng, shape, dt)
        for k, (shape, dt) in spec.items()
    }


def rewrite_blocks(
    a: np.ndarray, rng: np.random.Generator, frac: float
) -> np.ndarray:
    """A copy of ``a`` with ~``frac`` of its 4 KiB blocks rewritten."""
    flat = a.reshape(-1).copy()
    per_block = BLOCK_BYTES // a.itemsize
    n_blocks = -(-flat.size // per_block)
    k = max(1, round(frac * n_blocks))
    starts = rng.choice(n_blocks, size=k, replace=False) * per_block
    idx = (starts[:, None] + np.arange(per_block)).reshape(-1)
    idx = idx[idx < flat.size]
    flat[idx] = _random(rng, idx.shape, a.dtype)
    return flat.reshape(a.shape)


def fine_tune(tree: Tree, rng: np.random.Generator, layer: int,
              n_layers: int) -> Tree:
    """The next checkpoint: ~2% of the blocks of a few leaves rewritten, the
    step counter advanced; untouched leaves are shared with ``tree``."""
    out = dict(tree)
    layer %= n_layers
    for key in ("params/embed", f"params/layers/{layer}/attn/wq",
                f"params/layers/{layer}/mlp/w_down",
                "opt/mu/layers/0/mlp/w_up", "data/sample_weights"):
        out[key] = rewrite_blocks(tree[key], rng, REWRITE_FRAC)
    out["step"] = np.array(tree["step"] + 1000, np.int64)
    return out


def same_tree(got: Tree, want: Tree, label: str) -> None:
    """Bit-identical leaves, dtype and shape included."""
    require(set(got) == set(want),
            f"{label}: leaf sets differ by {sorted(set(got) ^ set(want))}")
    for k, w in want.items():
        g = got[k]
        require(g.dtype == w.dtype and g.shape == w.shape,
                f"{label}/{k}: got {g.dtype}{g.shape}, want {w.dtype}{w.shape}")
        require(np.array_equal(np.ascontiguousarray(g).view(np.uint8),
                                np.ascontiguousarray(w).view(np.uint8)),
                f"{label}/{k}: bytes differ")


def chain_depth(repo: Repository, vid: int) -> int:
    depth, v = 0, repo.store.versions[vid].stored_base
    while v is not None:
        depth, v = depth + 1, repo.store.versions[v].stored_base
    return depth


def errors(svc) -> Dict[str, int]:
    counters = svc.metrics.snapshot()["counters"]
    return {k: n for k, n in counters.items() if k.startswith("errors.") and n}


# ----------------------------------------------------------------- phases
def make_history(spec: Spec, n_layers: int, seed: int) -> List[Tree]:
    """The trees to commit: a base, FINE_TUNES fine-tunes each on the one
    before, then one fine-tune of ``history[2]`` for the ``eval`` branch."""
    rng = np.random.default_rng(seed)
    history = [make_tree(spec, rng)]
    for i in range(FINE_TUNES):
        history.append(fine_tune(history[-1], rng, i, n_layers))
    history.append(fine_tune(history[2], rng, n_layers - 1, n_layers))
    return history


async def commit_history(repo: Repository, history: List[Tree],
                         log: Log) -> Dict[int, Tree]:
    """Commit ``history`` through the service: all but the last on ``main``,
    the last on branch ``eval`` made at the third version."""
    committed: Dict[int, Tree] = {}
    secs = []
    async with repo.serve(readers=2) as svc:
        for i, tree in enumerate(history):
            branch = None
            if i == len(history) - 1:
                branch = repo.branch("eval", at=sorted(committed)[2])
            t0 = time.perf_counter()
            vid = await svc.commit(tree, message=f"v{i}", branch=branch)
            secs.append(time.perf_counter() - t0)
            committed[vid] = tree
        require(not errors(svc), f"commit errors {errors(svc)}")
    for vid in sorted(committed)[1:]:
        require(repo.store.versions[vid].stored_base is not None,
                f"v{vid} was stored whole, not as a delta")
    log("commit latency per version (base first): "
        + ", ".join(f"{t:.3f}" for t in secs) + " s")
    return committed


async def serve_checkouts(repo: Repository, committed: Dict[int, Tree],
                          log: Log) -> None:
    """Tip cold at full chain depth, then warm, then a batch across both
    branches — on a freshly opened repository, so the cache starts empty.
    Only the service calls are timed; the byte comparisons come after."""
    tip, eval_tip = repo.resolve("main"), repo.resolve("eval")
    depth = chain_depth(repo, tip)
    require(depth >= FINE_TUNES, f"tip chain depth {depth} < {FINE_TUNES}")
    refs: List = ["main", "eval", *sorted(committed)]
    async with repo.serve(readers=2) as svc:
        t0 = time.perf_counter()
        cold = await svc.checkout("main")
        t1 = time.perf_counter()
        warm = await svc.checkout("main")
        t2 = time.perf_counter()
        require(svc.metrics.counter("checkout.warm_hits") == 1,
                "second checkout of the tip was not served warm")
        trees = await svc.checkout_many(refs)
        t3 = time.perf_counter()
        require(not errors(svc), f"checkout errors {errors(svc)}")
    same_tree(cold, committed[tip], "cold tip")
    same_tree(warm, committed[tip], "warm tip")
    for ref, tree in zip(refs, trees):
        vid = {"main": tip, "eval": eval_tip}.get(ref, ref)
        same_tree(tree, committed[vid], f"checkout_many {ref}")
    log(f"cold tip checkout (chain depth {depth}): {t1 - t0:.3f} s")
    log(f"warm tip checkout: {t2 - t1:.3f} s")
    log(f"checkout_many of {len(refs)} refs: {t3 - t2:.3f} s")


async def repack_fsck(repo: Repository, committed: Dict[int, Tree],
                      log: Log) -> None:
    """Repack under Problem 6 with a bound that forces shorter chains, fsck
    clean, then every version checks out bit-identical again."""
    costs = [repo.store.recreation_cost(v) for v in committed]
    theta = min(costs) + (max(costs) - min(costs)) / 2
    vids = sorted(committed)
    async with repo.serve(readers=2) as svc:
        t0 = time.perf_counter()
        out = await svc.repack(OptimizeSpec.problem(6, theta=theta))
        t1 = time.perf_counter()
        report = await svc.fsck()
        t2 = time.perf_counter()
        trees = await svc.checkout_many(vids)
        t3 = time.perf_counter()
        require(not errors(svc), f"service errors {errors(svc)}")
    require(not report.findings,
            f"fsck findings after repack: {report.findings}")
    for vid, tree in zip(vids, trees):
        same_tree(tree, committed[vid], f"after repack v{vid}")
    depths = {v: chain_depth(repo, v) for v in vids}
    log(f"repack (Problem 6, theta {theta:.6f} s): {t1 - t0:.3f} s, "
        f"storage {out['before']['storage_bytes']} -> "
        f"{out['after']['storage_bytes']} B, chain depths {depths}")
    log(f"fsck: {t2 - t1:.3f} s, clean")
    log(f"checkout_many of {len(vids)} after repack: {t3 - t2:.3f} s")


def run(root: Path, spec: Spec, n_layers: int, seed: int,
        cache_budget: int, log: Log) -> None:
    """Every phase, each on a freshly opened repository at ``root``."""
    t0 = time.perf_counter()
    history = make_history(spec, n_layers, seed)
    log(f"data for {len(history)} versions: {time.perf_counter() - t0:.3f} s")
    with Repository(root, cache_budget_bytes=cache_budget) as repo:
        committed = asyncio.run(commit_history(repo, history, log))
    with Repository(root, cache_budget_bytes=cache_budget) as repo:
        asyncio.run(serve_checkouts(repo, committed, log))
    with Repository(root, cache_budget_bytes=cache_budget) as repo:
        asyncio.run(repack_fsck(repo, committed, log))


def kernels_compiled(num_blocks: int, slots: int) -> Dict[str, bool]:
    """Whether the kernels resolve to compiled mode on this backend, and
    whether the lowered mask and chain-apply programs at these sizes hold a
    Mosaic kernel (``tpu_custom_call``) rather than interpreted XLA."""
    blocks = jax.ShapeDtypeStruct((num_blocks, 8, 128), jnp.int32)
    mask = changed_block_mask.lower(blocks, blocks).as_text()
    chain = chain_delta_apply.lower(
        blocks, jax.ShapeDtypeStruct((slots, 8, 128), jnp.int32),
        jax.ShapeDtypeStruct((slots,), jnp.int32),
    ).as_text()
    return {
        "interpret": resolve_interpret(),
        "mask_tpu_custom_call": "tpu_custom_call" in mask,
        "chain_tpu_custom_call": "tpu_custom_call" in chain,
    }


# ------------------------------------------------------------------- main
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0, help="data seed")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's default device is "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    kind = dev.device_kind

    def log(msg: str) -> None:
        print(f"[{kind}] {msg}", flush=True)

    log(f"compile cache: {enable_compile_cache()}")
    cfg = ARCHS[ARCH]
    spec = leaf_specs(cfg, SMOKE_LAYERS, DATA_ROWS)
    total = sum(int(np.prod(s)) * dt.itemsize for s, dt in spec.values())
    log(f"{ARCH}: n_layers cut {cfg.n_layers} -> {SMOKE_LAYERS}; "
        f"{len(spec)} leaves, {total} B per version")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as root:
        run(Path(root), spec, SMOKE_LAYERS, args.seed, CACHE_BUDGET, log)

    embed_shape, embed_dt = spec["params/embed"]
    nb = -(-int(np.prod(embed_shape)) * embed_dt.itemsize // BLOCK_BYTES)
    proof = kernels_compiled(nb, 4096)
    log(f"kernels at {nb} blocks: {proof}")
    require(not proof["interpret"], "kernels resolve to interpret mode")
    require(proof["mask_tpu_custom_call"] and proof["chain_tpu_custom_call"],
            "lowered kernels hold no tpu_custom_call")
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": kind, "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
