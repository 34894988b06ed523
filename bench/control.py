"""The control of a cell, on the chip: runs in which the reference's control
answer (``control`` of ``bench/configs/<config>.py``: one precision below the
stated one) stands in the program's place.  Each must come
out as not correct.

    python3 -m bench.control --workload <cell> --seeds 1,2,3 --seconds <s>

Prints one JSON line per seed with the compared numbers; exits nonzero if
any seed's control passed the comparison.  The benchmark's own runs never
run it.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import harness, verify

    cell = harness.Cell.load(args.workload)
    harness.enable_compile_cache()
    device, peaks = harness.require_chips(cell.chips)
    caught = True
    for seed in (int(s) for s in args.seeds.split(",")):
        with tempfile.TemporaryDirectory(prefix="bench-run-") as work:
            out = harness.run_cell(cell, seed, args.seconds, False, time.perf_counter(),
                                   peaks, Path(work), device=device, control=True)
        caught &= not verify.passed(out.numbers)
        print(json.dumps({"workload": cell.name, "seed": seed, "control": True,
                          "correct": verify.passed(out.numbers), **out.numbers}), flush=True)
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
