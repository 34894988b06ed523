"""Solver wall-clock scaling on array-native synthetic instances.

Sweeps instance size n over 1k → 1M versions (past the paper's §6 LF/DC
scale, into mergeable-heap Edmonds territory),
generating each instance with :func:`repro.core.generate_flat` — edges land
directly in the flat ``EdgeArrays`` representation, no per-edge dict traffic
— and times every heuristic end to end through the declarative spec API
(``optimize(g, OptimizeSpec.problem(n, ...))`` — the surface production
callers use, so the numbers include spec validation):

* MCA (Problem 1), SPT (Problem 2), GitH;
* LMG at budget 1.05 × C_min (Problem 3);
* MP at θ = 1.5 × max SPT recreation (Problem 6).

Both solver backends are recorded: ``solvers`` holds the NumPy (Python-heap)
timings, ``solvers_jax`` the jitted backend (SPT Bellman-Ford relaxation, MP
scan, LMG device scoring).  The jax column measures the steady-state jitted
XLA path — ``pallas=False`` (on CPU the Pallas kernels run under the
interpreter, which benchmarks the interpreter, not the kernel) and a warmup
call per (solver, shape-bucket) so compile time is excluded.  MCA is
host-only (directed instances use Edmonds) and appears only under
``solvers``.

``BENCH_solver_scale.json`` in the repo root holds ``{"bounds", "history"}``:
``history`` accumulates one entry per run carrying the whole (n → seconds)
trajectory per solver (plus the process peak-RSS high-water mark after each
row), and ``bounds`` records a per-(solver, n) wall-clock reference in
seconds.  Every run doubles as a **timing-regression gate**: any timing above
``GATE_MULT`` (3×) its recorded bound fails the run — both standalone and as
the ``benchmarks.run`` suite (CSV rows, capped at 20k versions to keep the
orchestrator fast).  The 3× margin rides out scheduler noise on shared CI
boxes while still catching complexity-class regressions (the quadratic
regimes this sweep exists to guard against are 10–100× at the top sizes).
Refresh the references after an intentional perf change with
``--update-bounds``.

The default sweep ends at 500k and 1M versions (5.8M / 11.6M edges) — the
mergeable-heap Edmonds scale targets.  Pass ``--backends numpy`` for those
sizes: the jitted MP is a sequential O(n²) scan and the padded device layout
hits its cell cap near 1M versions.

Run standalone:
    PYTHONPATH=src python -m benchmarks.solver_scale [--ns 1000,5000,50000]
        [--backends numpy,jax] [--update-bounds]
"""

from __future__ import annotations

import argparse
import json
import resource
import sys
import time
from pathlib import Path
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

from repro.compile_cache import enable_compile_cache
from repro.core import OptimizeSpec, WorkloadSpec, generate_flat, optimize

from .common import Row

DEFAULT_NS = (1_000, 5_000, 20_000, 50_000, 500_000, 1_000_000)
DEFAULT_BACKENDS = ("numpy", "jax")
BENCH_PATH = Path(__file__).resolve().parent.parent / "BENCH_solver_scale.json"

#: a timing may drift up to this factor above its recorded bound before the
#: gate fails the run
GATE_MULT = 3.0


def _spec(n: int, seed: int = 0) -> WorkloadSpec:
    """DC-like shape with a bounded reveal ball (edges ≈ 20–30 per version)."""
    return WorkloadSpec(
        commits=n, branch_interval=3, branch_prob=0.7, branch_limit=4,
        branch_length=4, reveal_hops=3, seed=seed,
    )


def _timed(fn, *, warmup: bool = False) -> tuple:
    """(result, seconds); ``warmup=True`` runs once untimed first (jit)."""
    if warmup:
        fn()
    t0 = time.monotonic()
    out = fn()
    return out, time.monotonic() - t0


def sweep(
    ns: Iterable[int],
    seed: int = 0,
    backends: Sequence[str] = DEFAULT_BACKENDS,
) -> List[Dict]:
    results: List[Dict] = []
    for n in ns:
        t0 = time.monotonic()
        wl = generate_flat(_spec(n, seed=seed))
        g = wl.graph
        g.arrays()  # finalize the flat representation inside the gen timing
        gen_s = time.monotonic() - t0
        entry: Dict = {
            "n": n,
            "edges": g.n_edges,
            "generate_s": round(gen_s, 4),
            "solvers": {},
        }

        # the whole sweep speaks the declarative spec API (what production
        # callers hit); timings therefore include optimize()'s validation
        # and diagnostics pass, identically for both backends
        res, t = _timed(lambda: optimize(g, OptimizeSpec.problem(1)))
        mst = res.solution
        entry["solvers"]["mca"] = round(t, 4)

        res, t = _timed(lambda: optimize(g, OptimizeSpec.problem(2)))
        spt = res.solution
        entry["solvers"]["spt"] = round(t, 4)

        _, t = _timed(
            lambda: optimize(
                g, OptimizeSpec.heuristic("gith", window=10, max_depth=50)
            )
        )
        entry["solvers"]["gith"] = round(t, 4)

        budget = mst.storage_cost() * 1.05
        p3 = OptimizeSpec.problem(3, beta=budget, base=mst, spt=spt)
        lmg, t = _timed(lambda: optimize(g, p3))
        entry["solvers"]["lmg"] = round(t, 4)
        entry["lmg_budget_mult"] = 1.05
        entry["lmg_sum_rec_vs_mst"] = round(
            lmg.objective_value / max(mst.sum_recreation(), 1e-12), 6
        )

        theta = spt.max_recreation() * 1.5
        p6 = OptimizeSpec.problem(6, theta=theta)
        _, t = _timed(lambda: optimize(g, p6))
        entry["solvers"]["mp"] = round(t, 4)

        if "jax" in backends:
            jx: Dict[str, float] = {}
            res, t = _timed(
                lambda: optimize(g, OptimizeSpec.problem(2, backend="jax")),
                warmup=True,
            )
            spt_j = res.solution
            jx["spt"] = round(t, 4)
            _, t = _timed(
                lambda: optimize(
                    g,
                    OptimizeSpec.problem(
                        3, beta=budget, base=mst, spt=spt_j, backend="jax"
                    ),
                ),
                warmup=True,
            )
            jx["lmg"] = round(t, 4)
            _, t = _timed(
                lambda: optimize(
                    g, OptimizeSpec.problem(6, theta=theta, backend="jax")
                ),
                warmup=True,
            )
            jx["mp"] = round(t, 4)
            entry["solvers_jax"] = jx
            entry["spt_jax_speedup"] = round(
                entry["solvers"]["spt"] / max(jx["spt"], 1e-9), 3
            )

        # ru_maxrss is the process lifetime high-water mark (KiB on Linux),
        # monotone across rows — the per-row value says "solving up to this n
        # fit in this much memory", which is the capacity-planning question
        entry["peak_rss_mib"] = round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0, 1
        )
        results.append(entry)
    return results


def _timing_items(results: List[Dict]) -> Iterator[Tuple[str, float]]:
    """Flatten a sweep into ``("mca/n1000", seconds)`` bound-key pairs."""
    for entry in results:
        for col, suffix in (("solvers", ""), ("solvers_jax", "_jax")):
            for solver, seconds in entry.get(col, {}).items():
                yield f"{solver}{suffix}/n{entry['n']}", float(seconds)


def _load_bench(path: Path = BENCH_PATH) -> Dict:
    if not path.exists():
        return {"bounds": {}, "history": []}
    data = json.loads(path.read_text())
    if isinstance(data, list):
        # legacy layout: a bare run-history list from before the bounds gate
        return {"bounds": {}, "history": data}
    return data


def check_bounds(
    results: List[Dict], bounds: Dict[str, float], mult: float = GATE_MULT
) -> List[Tuple[str, float, float]]:
    """Timing-regression violations: ``(key, seconds, bound)`` for every
    swept timing above ``mult ×`` its recorded bound (unbounded keys pass)."""
    return [
        (key, seconds, bounds[key])
        for key, seconds in _timing_items(results)
        if key in bounds and seconds > mult * bounds[key]
    ]


def record(
    results: List[Dict], path: Path = BENCH_PATH, update_bounds: bool = False
) -> Dict[str, float]:
    """Append ``results`` to the history; returns the bounds table (refreshed
    from this run's timings when ``update_bounds``)."""
    data = _load_bench(path)
    data["history"].append(
        {"timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"), "results": results}
    )
    if update_bounds:
        for key, seconds in _timing_items(results):
            data["bounds"][key] = seconds
        data["bounds"] = dict(sorted(data["bounds"].items()))
    path.write_text(json.dumps(data, indent=2) + "\n")
    return data["bounds"]


def solver_scale(ns: Optional[Iterable[int]] = None) -> Iterable[Row]:
    """``benchmarks.run`` suite adapter: CSV rows, 20k cap for CI speed.

    Doubles as the timing-regression gate: raises after emitting its rows if
    any timing exceeds ``GATE_MULT ×`` its recorded bound.
    """
    ns = tuple(ns) if ns is not None else tuple(
        n for n in DEFAULT_NS if n <= 20_000
    )
    results = sweep(ns)
    bounds = record(results)
    for entry in results:
        for col, suffix in (("solvers", ""), ("solvers_jax", "_jax")):
            for solver, seconds in entry.get(col, {}).items():
                yield Row(
                    name=f"solver_scale/{solver}{suffix}/n{entry['n']}",
                    us_per_call=seconds * 1e6,
                    derived=f"edges={entry['edges']}",
                )
    violations = check_bounds(results, bounds)
    if violations:
        raise RuntimeError(
            "timing regression: " + "; ".join(
                f"{k} took {s:.3f}s > {GATE_MULT:g}x bound {b:.3f}s"
                for k, s, b in violations
            )
        )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--ns", default=",".join(str(n) for n in DEFAULT_NS),
        help="comma-separated instance sizes",
    )
    ap.add_argument(
        "--backends", default=",".join(DEFAULT_BACKENDS),
        help="comma-separated backends to time (numpy is always run; "
        "'jax' adds the jitted columns)",
    )
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--update-bounds", action="store_true",
        help="refresh the per-(solver, n) timing bounds from this run "
        "instead of gating against them",
    )
    args = ap.parse_args()
    try:
        ns = [int(x) for x in args.ns.split(",") if x.strip()]
    except ValueError:
        ap.error(f"--ns must be comma-separated integers, got {args.ns!r}")
    if not ns:
        ap.error("--ns is empty: nothing to sweep")
    backends = tuple(b.strip() for b in args.backends.split(",") if b.strip())
    bad = set(backends) - {"numpy", "jax"}
    if bad:
        ap.error(f"unknown backends: {sorted(bad)}")
    results = sweep(ns, seed=args.seed, backends=backends)
    bounds = record(results, update_bounds=args.update_bounds)
    print(json.dumps(results, indent=2))
    if not args.update_bounds:
        violations = check_bounds(results, bounds)
        for key, seconds, bound in violations:
            print(
                f"TIMING REGRESSION: {key} took {seconds:.3f}s "
                f"> {GATE_MULT:g}x bound {bound:.3f}s",
                file=sys.stderr,
            )
        if violations:
            sys.exit(1)


if __name__ == "__main__":
    enable_compile_cache()
    main()
