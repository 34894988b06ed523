"""Sustained serving benchmark: open-loop zipfian traffic through the
``DatasetService`` tier, mixed checkout/commit.

Where ``serving_checkout`` measures the store's raw materialization paths,
this drives the *service* the way a client fleet would: requests arrive on
a Poisson process at a target rate whether or not earlier ones finished
(open loop — latency includes queue wait, so a saturated service shows up
as a p99 cliff rather than silently throttling the workload), version
popularity is zipfian, and a fraction of the traffic is commits appending
fresh versions while checkouts keep hitting the old hot set.

That interleaving is exactly the case the cache's per-entry decode-chain
fingerprints exist for: a commit appends to the storage graph and
invalidates nothing it can't reach, so the hot set stays warm across writes.

Acceptance: no warm cache entry is invalidated while commits run
(``invalidations == 0``), and the stage spans reconcile with the service's
metrics.  Results (QPS, p50/p99, hit rate, coalescing/batching counters)
append to ``BENCH_serving_qps.json``; the suite registers as
``serving_qps`` in ``benchmarks.run`` with a small n + short duration for
CI smoke.

The measured run executes under an enabled :mod:`repro.obs` tracer: a
per-stage breakdown (queue wait / decode / device delta-apply span totals)
and a span↔metrics reconciliation ratio land in the results, and the run
exports a Perfetto-loadable Chrome trace (``BENCH_serving_qps_trace.json``)
so a regression in the summary numbers can be opened as a timeline.

Run standalone:
    PYTHONPATH=src python -m benchmarks.serving_qps [--n 400]
        [--requests 800] [--qps 400] [--write-fraction 0.08] [--zipf 1.1]
        [--trace-out PATH]
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import tempfile
import time
from pathlib import Path
from typing import Dict, Iterable, List, Optional

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.obs import Tracer, chrome_trace, set_tracer, validate_chrome_trace
from repro.store.repository import Repository

from .common import Row
from .serving_checkout import _NO_FLUSH, build_store, zipf_requests

BENCH_PATH = Path(__file__).resolve().parent.parent / "BENCH_serving_qps.json"
TRACE_PATH = (
    Path(__file__).resolve().parent.parent / "BENCH_serving_qps_trace.json"
)
DEFAULT_N = 400
DEFAULT_REQUESTS = 800
DEFAULT_QPS = 400.0
DEFAULT_WRITE_FRACTION = 0.08
DEFAULT_ZIPF_S = 1.1


@dataclasses.dataclass
class _Event:
    """One scheduled arrival: offset from traffic start, op, payload."""

    at: float
    op: str  # "checkout" | "commit"
    vid: Optional[int] = None
    tree: Optional[dict] = None


def make_workload(
    vids: List[int],
    requests: int,
    *,
    qps: float,
    write_fraction: float,
    zipf_s: float,
    seed: int,
    shape=(48, 64),
) -> List[_Event]:
    """Poisson arrivals at ``qps``; zipfian reads, commits salted in."""
    rng = np.random.RandomState(seed)
    gaps = rng.exponential(1.0 / qps, size=requests)
    arrivals = np.cumsum(gaps)
    reads = zipf_requests(vids, requests, s=zipf_s, seed=seed + 1)
    events = []
    for i in range(requests):
        if rng.rand() < write_fraction:
            tree = {"w": rng.randn(*shape).astype(np.float32)}
            events.append(_Event(at=float(arrivals[i]), op="commit", tree=tree))
        else:
            events.append(
                _Event(at=float(arrivals[i]), op="checkout", vid=reads[i])
            )
    return events


async def run_traffic(
    repo: Repository,
    events: List[_Event],
    *,
    readers: int = 4,
    batch_window_s: float = 0.002,
    max_batch: int = 32,
    tracer: Optional[Tracer] = None,
) -> Dict:
    """Fire the recorded workload open-loop; return QPS + latency rollups."""
    async with repo.serve(
        readers=readers, batch_window_s=batch_window_s, max_batch=max_batch
    ) as svc:
        loop = asyncio.get_running_loop()
        t0 = loop.time()
        latencies: List[float] = []
        write_latencies: List[float] = []

        async def fire(ev: _Event) -> None:
            sched = t0 + ev.at
            if ev.op == "commit":
                await svc.commit(ev.tree, message="bench append")
                write_latencies.append(loop.time() - sched)
            else:
                await svc.checkout(ev.vid)
                latencies.append(loop.time() - sched)

        tasks = []
        for ev in events:
            delay = t0 + ev.at - loop.time()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(loop.create_task(fire(ev)))
        await asyncio.gather(*tasks)
        makespan = loop.time() - t0
        snap = svc.stats()

    c = snap["counters"]
    hits = c.get("checkout.warm_hits", 0)
    misses = c.get("checkout.warm_misses", 0)

    def _pct(xs: List[float], q: float) -> float:
        from repro.service.metrics import percentile

        return round(percentile(xs, q) * 1e3, 4) if xs else 0.0

    stages: Dict[str, float] = {}
    recon: Dict[str, Optional[float]] = {}
    if tracer is not None:
        spansum = tracer.summary()

        def span_total(name: str) -> float:
            return spansum.get(name, {}).get("total_s", 0.0)

        def track_total(name: str) -> float:
            tr = snap["tracks"].get(name, {})
            return tr.get("mean_ms", 0.0) * tr.get("count", 0) / 1e3

        def ratio(a: float, b: float) -> Optional[float]:
            return round(a / b, 4) if b > 0 else None

        stages = {
            "queue_wait_ms": round(span_total("svc.queue_wait") * 1e3, 4),
            "decode_ms": round(span_total("svc.decode") * 1e3, 4),
            "delta_apply_ms": round(
                span_total("delta.apply_chains") * 1e3, 4
            ),
            "spans": len(tracer),
            "spans_dropped": tracer.dropped,
        }
        # spans and metrics are written from the same monotonic timestamps,
        # so these must sit at 1.0 within float noise — the benchmark's
        # acceptance gate pins them to ±5%
        recon = {
            "queue_wait": ratio(
                span_total("svc.queue_wait"), track_total("queue_wait")
            ),
            "decode": ratio(span_total("svc.decode"), track_total("decode")),
        }

    return {
        "requests": len(events),
        "reads": len(latencies),
        "commits": len(write_latencies),
        "makespan_s": round(makespan, 4),
        "qps": round(len(events) / makespan, 2),
        "read_p50_ms": _pct(latencies, 50),
        "read_p99_ms": _pct(latencies, 99),
        "commit_p50_ms": _pct(write_latencies, 50),
        "commit_p99_ms": _pct(write_latencies, 99),
        "hit_rate": round(hits / max(1, hits + misses), 4),
        "coalesced": c.get("checkout.coalesced", 0),
        "batches": c.get("checkout.batches", 0),
        "batched_refs": c.get("checkout.batched_refs", 0),
        "invalidations": snap["store"]["invalidations"],
        "purges": snap["store"]["purges"],
        "stages": stages,
        "span_reconciliation": recon,
    }


def run_benchmark(
    n: int = DEFAULT_N,
    *,
    requests: int = DEFAULT_REQUESTS,
    qps: float = DEFAULT_QPS,
    write_fraction: float = DEFAULT_WRITE_FRACTION,
    zipf_s: float = DEFAULT_ZIPF_S,
    readers: int = 4,
    seed: int = 0,
    trace_out: Optional[Path] = TRACE_PATH,
) -> Dict:
    """Build one store and replay one workload over it, warm, under an
    enabled tracer whose Chrome trace goes to ``trace_out`` (``None`` skips
    the artifact)."""
    with tempfile.TemporaryDirectory(prefix="repro_qps_") as d:
        root = Path(d) / "store"
        store = build_store(str(root), n, seed=seed)
        vids = sorted(store.versions)
        store.close()
        events = make_workload(
            vids,
            requests,
            qps=qps,
            write_fraction=write_fraction,
            zipf_s=zipf_s,
            seed=seed + 3,
        )
        repo = Repository(str(root), access_flush_every=_NO_FLUSH)
        # build_store commits at the store layer; give the service a branch
        # tip for its write traffic to advance
        if "main" not in repo.branches():
            repo.branch("main", at=vids[-1])
        # one warmup pass over the read set so the run starts hot; the
        # measured pass then shows what write traffic costs (before the
        # tracer installs — warmup decodes aren't the run)
        repo.store.checkout_many(
            sorted({e.vid for e in events if e.op == "checkout"})
        )
        tracer = Tracer(enabled=True, capacity=1 << 18)
        old = set_tracer(tracer)
        try:
            run = asyncio.run(
                run_traffic(repo, events, readers=readers, tracer=tracer)
            )
        finally:
            set_tracer(old)
        repo.close()

    artifact = None
    if trace_out is not None:
        chrome_trace(tracer, trace_out, process_name="serving_qps")
        artifact = str(trace_out)

    return {
        "n": n,
        "target_qps": qps,
        "write_fraction": write_fraction,
        "zipf_s": zipf_s,
        "readers": readers,
        **run,
        "trace_artifact": artifact,
    }


def record(result: Dict, path: Path = BENCH_PATH) -> None:
    history = []
    if path.exists():
        history = json.loads(path.read_text())
    history.append(
        {"timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"), "result": result}
    )
    path.write_text(json.dumps(history, indent=2) + "\n")


def serving_qps(n: int = 120, requests: int = 300, qps: float = 300.0) -> Iterable[Row]:
    """``benchmarks.run`` suite adapter — small n / short duration so the
    orchestrator and CI smoke stay bounded; the CLI runs the full sweep."""
    r = run_benchmark(n, requests=requests, qps=qps)
    record(r)
    yield Row(
        name=f"serving_qps/n{n}",
        us_per_call=1e6 / max(r["qps"], 1e-9),
        derived=(
            f"qps={r['qps']};p50={r['read_p50_ms']}ms;"
            f"p99={r['read_p99_ms']}ms;hit={r['hit_rate']};"
            f"coalesced={r['coalesced']};batches={r['batches']}"
        ),
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=DEFAULT_N)
    ap.add_argument("--requests", type=int, default=DEFAULT_REQUESTS)
    ap.add_argument("--qps", type=float, default=DEFAULT_QPS)
    ap.add_argument(
        "--write-fraction", type=float, default=DEFAULT_WRITE_FRACTION
    )
    ap.add_argument("--zipf", type=float, default=DEFAULT_ZIPF_S)
    ap.add_argument("--readers", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", type=Path, default=TRACE_PATH,
                    help="merged Chrome trace artifact path "
                         f"(default {TRACE_PATH.name})")
    args = ap.parse_args()
    result = run_benchmark(
        args.n,
        requests=args.requests,
        qps=args.qps,
        write_fraction=args.write_fraction,
        zipf_s=args.zipf,
        readers=args.readers,
        seed=args.seed,
        trace_out=args.trace_out,
    )
    record(result)
    print(json.dumps(result, indent=2))
    ok = result["commits"] > 0 and result["invalidations"] == 0
    ok_qps = result["qps"] > 0 and result["batches"] > 0
    print(
        f"# {result['invalidations']} warm entries invalidated across "
        f"{result['commits']} commits, hit rate {result['hit_rate']} "
        f"({'OK: commits kept the cache warm' if ok else 'REGRESSION'})"
    )
    ok_trace = True
    if result["trace_artifact"]:
        problems = validate_chrome_trace(result["trace_artifact"])
        ok_trace = not problems
        print(f"# trace artifact {result['trace_artifact']}: "
              f"{'Perfetto-loadable' if ok_trace else problems}")
    # span totals and ServiceMetrics tracks share one clock: ±5% or a stage
    # is being measured twice / not at all
    ok_recon = True
    for stage, r in result["span_reconciliation"].items():
        if r is not None and not (0.95 <= r <= 1.05):
            ok_recon = False
            print(f"# RECONCILIATION FAILURE {stage}: {r}")
    if not (ok and ok_qps and ok_trace and ok_recon):
        raise SystemExit(1)


if __name__ == "__main__":
    enable_compile_cache()
    main()
