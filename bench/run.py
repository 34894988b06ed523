"""Run one cell of the benchmark once and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine whose JAX holds the TPU chips the
cell asks for.  It exits nonzero, printing no result, where JAX finds no TPU,
too few chips, a device kind missing from ``bench/peaks.json``, or a store
codec other than zstd.  Otherwise the last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones), ``device``,
with ``--trace 1`` a ``breakdown``, and last ``checks``: each number the
comparison held against its limit, which also close standard error.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness, verify

    cell = harness.Cell.load(args.workload)
    harness.enable_compile_cache()
    try:
        device, peaks = harness.require_chips(cell.chips)
        harness.require_zstd()
    except (RuntimeError, KeyError, ImportError) as exc:
        log(f"refusing to run: {exc}")
        return 3
    import jax

    log(f"{cell.name} on {jax.device_count()} x {device.device_kind}, seed {args.seed}, "
        f"{args.seconds} s window, trace {args.trace}")
    with tempfile.TemporaryDirectory(prefix="bench-run-") as work:
        out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace), STARTED,
                               peaks, Path(work), device=device, log=log)
    w = out.window
    result = {
        "correct": verify.passed(out.numbers),
        "attempted": out.attempted,
        "failed": out.failed,
        "metrics": harness.metrics_of(cell, w, bool(args.trace)),
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": cell.chips, "memory_peak_bytes": out.memory_peak_bytes},
    }
    if args.trace:
        result["device"].update(busy_s=w.trace.busy_s, window_s=w.trace.window_s)
        result["breakdown"] = w.trace.breakdown()
    result["checks"] = verify.checks(out.numbers)
    log(f"window {w.seconds:.3f} s, {out.attempted} requests, {out.failed} failed; "
        f"set-up {w.setup_s:.3f} s; compiles in window {out.compiles}")
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
