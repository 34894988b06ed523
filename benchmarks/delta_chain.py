"""Deep-chain checkout benchmark: fused chain pipeline vs stepwise applies.

The device-resident delta pipeline (:mod:`repro.store.delta` +
:mod:`repro.kernels.chain_apply`) exists to make deep delta chains cheap:
a K-step chain used to pay K ``to_blocks``/``sparse_apply``/``from_blocks``
round trips; fused, the whole chain is one padded device stack and one
Pallas dispatch per leaf-shape group.  This benchmark sweeps chain depth
over a linear history and measures cold ms/checkout through two otherwise
identical stores — ``fuse_chains=True`` vs ``False`` — verifying bit
identity at every depth (the fused path must be an optimization, never a
semantic change).

Acceptance: fused ≥ 3× faster at chain depth ≥ 16.

Results append to ``BENCH_serving_checkout.json`` (the serving benchmark's
history file — same serving tier, one timeline) tagged
``"benchmark": "delta_chain"``, and the suite registers as ``delta_chain``
in ``benchmarks.run`` with small depths for CI smoke.

Run standalone:
    PYTHONPATH=src python -m benchmarks.delta_chain [--depths 1,4,16,64]
        [--reps 5] [--shape 96x128]
"""

from __future__ import annotations

import argparse
import json
import tempfile
import time
from typing import Dict, Iterable, List, Sequence

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.store import VersionStore

from .common import Row
from .serving_checkout import BENCH_PATH, _NO_FLUSH, record

DEFAULT_DEPTHS = (1, 2, 4, 8, 16, 32, 64)
DEFAULT_REPS = 5
DEFAULT_SHAPE = (96, 128)


def build_linear_store(
    root: str, depth: int, *, shape=DEFAULT_SHAPE, seed: int = 0
) -> List[int]:
    """Linear history: one root + ``depth`` sparse-delta commits on one chain.

    Each commit perturbs a couple of rows (a block or two of the blocked
    layout), so every link stores as a sparse delta and a depth-d checkout
    genuinely walks d delta applies.
    """
    rng = np.random.RandomState(seed)
    store = VersionStore(
        root,
        cache_budget_bytes=0,
        delta_hops=depth + 1,
        access_flush_every=_NO_FLUSH,
    )
    payload = {
        "w": rng.randn(*shape).astype(np.float32),
        "b": rng.randn(shape[1]).astype(np.float32),
    }
    vids = [store.commit(payload, message="root")]
    for i in range(depth):
        payload = {k: v.copy() for k, v in payload.items()}
        row = rng.randint(0, shape[0] - 2)
        payload["w"][row : row + 2] += rng.randn(2, shape[1]).astype(np.float32)
        vids.append(store.commit(payload, parents=[vids[-1]], message=f"c{i}"))
    chain_links = sum(
        1 for v in vids if store.versions[v].stored_base is not None
    )
    assert chain_links == depth, f"expected a pure chain, got {chain_links}/{depth}"
    return vids


def run_benchmark(
    depths: Sequence[int] = DEFAULT_DEPTHS,
    *,
    reps: int = DEFAULT_REPS,
    shape=DEFAULT_SHAPE,
    seed: int = 0,
) -> Dict:
    max_depth = max(depths)
    sweep = []
    with tempfile.TemporaryDirectory(prefix="repro_chain_") as d:
        vids = build_linear_store(d, max_depth, shape=shape, seed=seed)
        fused = VersionStore(
            d, cache_budget_bytes=0, access_flush_every=_NO_FLUSH,
            fuse_chains=True,
        )
        stepwise = VersionStore(
            d, cache_budget_bytes=0, access_flush_every=_NO_FLUSH,
            fuse_chains=False,
        )
        for depth in depths:
            vid = vids[depth]
            t_f = _timed(fused, vid, reps)
            t_s = _timed(stepwise, vid, reps)
            f_tree = fused.checkout(vid)
            s_tree = stepwise.checkout(vid)
            identical = set(f_tree) == set(s_tree) and all(
                np.array_equal(f_tree[k], s_tree[k]) for k in f_tree
            )
            sweep.append(
                {
                    "depth": depth,
                    "fused_ms": round(t_f * 1e3, 4),
                    "stepwise_ms": round(t_s * 1e3, 4),
                    "speedup": round(t_s / max(t_f, 1e-9), 2),
                    "identical": bool(identical),
                }
            )
    deep = [p for p in sweep if p["depth"] >= 16]
    return {
        "benchmark": "delta_chain",
        "shape": list(shape),
        "reps": reps,
        "sweep": sweep,
        "all_identical": all(p["identical"] for p in sweep),
        "min_deep_speedup": min((p["speedup"] for p in deep), default=None),
    }


def _timed(store: VersionStore, vid: int, reps: int) -> float:
    store.checkout(vid)  # warmup: jit compiles off the clock (cache budget 0)
    t0 = time.monotonic()
    for _ in range(reps):
        store.checkout(vid)
    return (time.monotonic() - t0) / reps


def delta_chain(
    depths: Sequence[int] = (1, 4, 8), reps: int = 2
) -> Iterable[Row]:
    """``benchmarks.run`` suite adapter (small depths for CI smoke).

    The smoke asserts fused ≡ stepwise at every depth; the ≥3× deep-chain
    speedup is checked by the standalone CLI at depth ≥ 16.
    """
    result = run_benchmark(depths, reps=reps)
    record(result)
    assert result["all_identical"], "fused checkout diverged from stepwise"
    for p in result["sweep"]:
        yield Row(
            name=f"delta_chain/depth{p['depth']}",
            us_per_call=p["fused_ms"] * 1e3,
            derived=f"stepwise_ms={p['stepwise_ms']};speedup={p['speedup']}x",
        )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--depths", default=",".join(map(str, DEFAULT_DEPTHS)))
    ap.add_argument("--reps", type=int, default=DEFAULT_REPS)
    ap.add_argument("--shape", default="96x128")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    depths = tuple(int(x) for x in args.depths.split(","))
    shape = tuple(int(x) for x in args.shape.split("x"))
    result = run_benchmark(depths, reps=args.reps, shape=shape, seed=args.seed)
    record(result)
    print(json.dumps(result, indent=2))
    if not result["all_identical"]:
        raise SystemExit("FUSED/STEPWISE MISMATCH")
    deep = result["min_deep_speedup"]
    if deep is not None:
        ok = deep >= 3.0
        print(f"# min speedup at depth>=16: {deep}x ({'OK' if ok else 'BELOW 3x'})")


if __name__ == "__main__":
    enable_compile_cache()
    main()
