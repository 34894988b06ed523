"""Serving-path checkout benchmark: cold vs warm, batched vs sequential.

Models the DataHub serving workload the materialization subsystem exists
for: many requests hitting hot versions of a branching store under a
zipfian access distribution.  Three measurements:

* **cold** — every checkout decodes its full storage chain
  (``cache_budget_bytes=0`` disables the FlatTree cache);
* **warm** — the same workload through the byte-budgeted
  ``MaterializationCache`` after one warmup pass, with the measured hit
  rate (acceptance: warm ≥ 10× faster than cold at n=1k);
* **batch** — ``checkout_many`` on chain-sharing batches vs the same
  requests issued sequentially, both uncached, isolating the planner's
  shared-prefix deduplication (acceptance: batched strictly faster).

The store is built with bounded-depth delta chains (a commit whose chain
would exceed ``max_chain`` is stored full), mirroring what any repack with a
recreation bound produces — without it, cold checkouts of a 1k-linear chain
would measure pathology, not the serving path.

Results append to ``BENCH_serving_checkout.json`` in the repo root (one
entry per run, accumulating history across PRs) and the suite registers as
``serving_checkout`` in ``benchmarks.run`` with a small n for CI smoke.

Run standalone:
    PYTHONPATH=src python -m benchmarks.serving_checkout [--n 1000]
        [--requests 600] [--zipf 1.1] [--batch-size 8] [--batches 12]
"""

from __future__ import annotations

import argparse
import json
import tempfile
import time
from pathlib import Path
from typing import Dict, Iterable, List, Optional

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.store import VersionStore

from .common import Row

BENCH_PATH = Path(__file__).resolve().parent.parent / "BENCH_serving_checkout.json"
DEFAULT_N = 1_000
DEFAULT_REQUESTS = 600
DEFAULT_ZIPF_S = 1.1

# serving replicas flush access counts rarely; keep metadata writes out of
# the per-checkout latency being measured
_NO_FLUSH = 1 << 30


def build_store(
    root: str,
    n: int,
    *,
    seed: int = 0,
    shape=(48, 64),
    branch_every: int = 4,
    max_chain: int = 12,
    cache_budget_bytes: int = 256 << 20,
) -> VersionStore:
    """Branching history of ``n`` versions with bounded-depth delta chains."""
    rng = np.random.RandomState(seed)
    store = VersionStore(
        root,
        cache_budget_bytes=cache_budget_bytes,
        access_flush_every=_NO_FLUSH,
    )
    payload = {"w": rng.randn(*shape).astype(np.float32)}
    vids = [store.commit(payload, message="root")]
    payloads = {vids[0]: payload}
    depth = {vids[0]: 0}
    for i in range(n - 1):
        if i % branch_every == branch_every - 1:
            parent = int(vids[rng.randint(0, len(vids))])
        else:
            parent = vids[-1]
        p = {"w": payloads[parent]["w"].copy()}
        row = rng.randint(0, shape[0] - 2)
        p["w"][row : row + 2] += rng.randn(2, shape[1]).astype(np.float32)
        if depth[parent] >= max_chain:
            vid = store.commit(p, message=f"c{i} (chain cap)")
            depth[vid] = 0
        else:
            vid = store.commit(p, parents=[parent], message=f"c{i}")
            # commit may still have stored it full if the delta was larger
            depth[vid] = (
                depth[parent] + 1
                if store.versions[vid].stored_base is not None
                else 0
            )
        payloads[vid] = p
        vids.append(vid)
    return store


def zipf_requests(
    vids: List[int], requests: int, *, s: float, seed: int
) -> List[int]:
    """Zipfian workload: rank r of a seeded permutation gets p ∝ 1/r^s."""
    rng = np.random.RandomState(seed)
    ranked = rng.permutation(vids)
    p = 1.0 / np.arange(1, len(ranked) + 1) ** s
    p /= p.sum()
    return [int(v) for v in rng.choice(ranked, size=requests, p=p)]


def _timed_checkouts(store: VersionStore, workload: List[int]) -> float:
    t0 = time.monotonic()
    for vid in workload:
        store.checkout(vid)
    return time.monotonic() - t0


def chain_sharing_batches(
    store: VersionStore, *, batch_size: int, batches: int, seed: int
) -> List[List[int]]:
    """Batches drawn from single storage chains (maximal prefix sharing)."""
    rng = np.random.RandomState(seed)
    by_depth = sorted(
        store.versions, key=lambda v: -_chain_len(store, v)
    )
    out = []
    for i in range(batches):
        tip = by_depth[i % max(1, len(by_depth) // 4)]
        chain = []
        v: Optional[int] = tip
        while v is not None and len(chain) < batch_size:
            chain.append(v)
            v = store.versions[v].stored_base
        while len(chain) < batch_size:  # top up from the tip's neighborhood
            chain.append(int(rng.choice(list(store.versions))))
        out.append(chain)
    return out


def _chain_len(store: VersionStore, vid: int) -> int:
    n, v = 0, vid
    while v is not None:
        v = store.versions[v].stored_base
        n += 1
    return n


def run_benchmark(
    n: int = DEFAULT_N,
    *,
    requests: int = DEFAULT_REQUESTS,
    zipf_s: float = DEFAULT_ZIPF_S,
    batch_size: int = 8,
    batches: int = 12,
    cold_requests: Optional[int] = None,
    seed: int = 0,
) -> Dict:
    cold_requests = cold_requests or min(requests, 200)
    with tempfile.TemporaryDirectory(prefix="repro_serving_") as d:
        store = build_store(d, n, seed=seed)
        vids = sorted(store.versions)
        workload = zipf_requests(vids, requests, s=zipf_s, seed=seed + 1)

        # cold: no FlatTree cache, every request re-decodes its chain
        cold_store = VersionStore(
            d, cache_budget_bytes=0, access_flush_every=_NO_FLUSH
        )
        cold_s = _timed_checkouts(cold_store, workload[:cold_requests])
        cold_ms = cold_s / cold_requests * 1e3

        # warm: one warmup pass, then the measured pass on a hot cache
        warm_store = VersionStore(d, access_flush_every=_NO_FLUSH)
        _timed_checkouts(warm_store, workload)  # warmup
        s0 = warm_store.materializer.stats()
        warm_s = _timed_checkouts(warm_store, workload)
        s1 = warm_store.materializer.stats()
        warm_ms = warm_s / requests * 1e3
        hits = s1["hits"] - s0["hits"]
        misses = s1["misses"] - s0["misses"]
        hit_rate = hits / max(1, hits + misses)

        # batched vs sequential on chain-sharing batches, both uncached —
        # what remains is exactly the planner's shared-prefix dedup
        batch_list = chain_sharing_batches(
            store, batch_size=batch_size, batches=batches, seed=seed + 2
        )
        batch_store = VersionStore(
            d, cache_budget_bytes=0, access_flush_every=_NO_FLUSH
        )
        t0 = time.monotonic()
        for b in batch_list:
            batch_store.checkout_many(b)
        batched_s = time.monotonic() - t0
        t0 = time.monotonic()
        for b in batch_list:
            for v in b:
                batch_store.checkout(v)
        sequential_s = time.monotonic() - t0

        result = {
            "n": n,
            "requests": requests,
            "cold_requests": cold_requests,
            "zipf_s": zipf_s,
            "storage_bytes": store.storage_bytes(),
            "cold_ms_per_checkout": round(cold_ms, 4),
            "warm_ms_per_checkout": round(warm_ms, 4),
            "warm_speedup": round(cold_ms / max(warm_ms, 1e-9), 2),
            "hit_rate": round(hit_rate, 4),
            "cache_bytes": warm_store.materializer.cache.current_bytes,
            "batch": {
                "batch_size": batch_size,
                "batches": batches,
                "batched_s": round(batched_s, 4),
                "sequential_s": round(sequential_s, 4),
                "speedup": round(sequential_s / max(batched_s, 1e-9), 2),
            },
        }
    return result


def record(result: Dict, path: Path = BENCH_PATH) -> None:
    history = []
    if path.exists():
        history = json.loads(path.read_text())
    history.append(
        {"timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"), "result": result}
    )
    path.write_text(json.dumps(history, indent=2) + "\n")


def serving_checkout(n: int = 150, requests: int = 240) -> Iterable[Row]:
    """``benchmarks.run`` suite adapter: small n so the orchestrator and the
    CI smoke stay fast; the standalone CLI runs the full 1k-version check."""
    result = run_benchmark(n, requests=requests, batches=6)
    record(result)
    yield Row(
        name=f"serving/cold_checkout/n{n}",
        us_per_call=result["cold_ms_per_checkout"] * 1e3,
        derived=f"storage_mb={result['storage_bytes']/1e6:.1f}",
    )
    yield Row(
        name=f"serving/warm_checkout/n{n}",
        us_per_call=result["warm_ms_per_checkout"] * 1e3,
        derived=(
            f"speedup={result['warm_speedup']};hit_rate={result['hit_rate']}"
        ),
    )
    yield Row(
        name=f"serving/batch{result['batch']['batch_size']}/n{n}",
        us_per_call=result["batch"]["batched_s"]
        / max(result["batch"]["batches"], 1)
        * 1e6,
        derived=f"vs_sequential={result['batch']['speedup']}x",
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=DEFAULT_N)
    ap.add_argument("--requests", type=int, default=DEFAULT_REQUESTS)
    ap.add_argument("--zipf", type=float, default=DEFAULT_ZIPF_S)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--batches", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    result = run_benchmark(
        args.n,
        requests=args.requests,
        zipf_s=args.zipf,
        batch_size=args.batch_size,
        batches=args.batches,
        seed=args.seed,
    )
    record(result)
    print(json.dumps(result, indent=2))
    ok_warm = result["warm_speedup"] >= 10.0
    ok_batch = result["batch"]["speedup"] > 1.0
    print(
        f"# warm {result['warm_speedup']}x vs cold "
        f"({'OK' if ok_warm else 'BELOW 10x'}), "
        f"batched {result['batch']['speedup']}x vs sequential "
        f"({'OK' if ok_batch else 'NOT FASTER'})"
    )


if __name__ == "__main__":
    enable_compile_cache()
    main()
