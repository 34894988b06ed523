"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

A TPU trace has one plane per chip, ``/device:TPU:<n>``.  Its line
``XLA Modules`` holds one event per program launch, named
``jit_<function>(<fingerprint>)``; its line ``XLA Ops`` holds the HLO ops
inside them, each named by its HLO text, operand shapes included.  Host
threads are lines of the plane ``/host:CPU``, where the harness's own
``TraceAnnotation``\\ s (names starting ``bench.``) land.  Device and host
events share one nanosecond clock.

* busy time: the union of a chip's program intervals inside the traced
  window (the host annotation ``bench.window``), averaged over the chips;
* per-program device time: summed program durations, by function name;
* idle gaps: the stretches of the window in which no program ran, each
  named by the ``bench.*`` annotations that were open at its midpoint;
* roofline share: the time the least bytes the work needs would take at
  the peak, over the device time measured.
"""

from __future__ import annotations

import dataclasses
import json
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_ANNOTATION = "bench.window"
BLOCK_BYTES = 4096
_PROGRAM = re.compile(r"^(.*?)\(\d+\)$")
_SHAPE = re.compile(r"s32\[(\d+(?:,\d+)*)\]")

Interval = Tuple[int, int]  # [start_ns, end_ns)


@dataclasses.dataclass
class DeviceOp:
    name: str
    start_ns: int
    end_ns: int


@dataclasses.dataclass
class TraceSummary:
    """What the reduction keeps of one trace (all times in seconds)."""

    window_s: float
    busy_s: float                       # mean over chips
    chips: int
    programs: Dict[str, float]          # function -> device seconds
    program_calls: Dict[str, int]
    ops: List[DeviceOp]                 # HLO ops inside the window, chip 0
    idle_gaps: List[Tuple[str, float]]  # longest first
    gap_at: List[float] = dataclasses.field(default_factory=list)  # midpoints, s into the window

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def program_seconds(self, *functions: str) -> float:
        return sum(self.programs.get(f, 0.0) for f in functions)

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        ranked = sorted(self.programs.items(), key=lambda kv: -kv[1])
        return {
            "device_ops": [[k, v] for k, v in ranked[:top]],
            "idle_gaps": [[k, v] for k, v in self.idle_gaps[:top]],
        }


def program_name(event_name: str) -> str:
    """``jit_chain_delta_apply(8802...)`` -> ``jit_chain_delta_apply``."""
    m = _PROGRAM.match(event_name)
    return m.group(1) if m else event_name


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < hi:
        out.append((at, hi))
    return out


def _events(line) -> List[Tuple[str, int, int]]:
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def reduce_profile(profile, window: str = WINDOW_ANNOTATION) -> TraceSummary:
    """Reduce a loaded :class:`jax.profiler.ProfileData` over the span of
    the host annotation named ``window``."""
    host: List[Tuple[str, int, int]] = []
    devices = []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            mods = _events(lines["XLA Modules"]) if "XLA Modules" in lines else []
            ops = _events(lines["XLA Ops"]) if "XLA Ops" in lines else []
            devices.append((plane.name, mods, ops))
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                host.extend(ev for ev in _events(ln) if ev[0].startswith("bench."))
    windows = [(s, e) for n, s, e in host if n == window]
    if not windows:
        raise ValueError(f"no {window!r} annotation in the trace")
    if not devices:
        raise ValueError("no /device:TPU:* plane in the trace")
    lo, hi = windows[0]
    devices.sort(key=lambda d: d[0])

    programs: Dict[str, float] = {}
    calls: Dict[str, int] = {}
    busy_ns = []
    first_busy: List[Interval] = []
    for i, (_, mods, _) in enumerate(devices):
        inside = [(n, s, e) for n, s, e in mods if e > lo and s < hi]
        for n, s, e in inside:
            p = program_name(n)
            programs[p] = programs.get(p, 0.0) + (min(e, hi) - max(s, lo)) * 1e-9
            calls[p] = calls.get(p, 0) + 1
        busy = union(clip([(s, e) for _, s, e in inside], lo, hi))
        busy_ns.append(sum(e - s for s, e in busy))
        if i == 0:
            first_busy = busy
    ops = [DeviceOp(n, s, e) for n, s, e in devices[0][2] if e > lo and s < hi]

    labelled = []
    for s, e in gaps(first_busy, lo, hi):
        mid = (s + e) // 2
        open_at = sorted({n for n, hs, he in host
                          if hs <= mid < he and n != window})
        labelled.append(("+".join(open_at) or "no bench call open", (e - s) * 1e-9,
                         (mid - lo) * 1e-9))
    labelled.sort(key=lambda g: -g[1])
    return TraceSummary(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy_ns) / len(busy_ns) * 1e-9,
        chips=len(devices),
        programs=programs,
        program_calls=calls,
        ops=ops,
        idle_gaps=[(n, d) for n, d, _ in labelled],
        gap_at=[m for _, _, m in labelled],
    )


def name_gaps(summary: TraceSummary, spans: Sequence, window_start: float) -> None:
    """Add to each idle gap's name the program's innermost ``repro.obs``
    spans open at its midpoint (``window_start``: the spans' clock when the
    window's annotation opened), so that a gap says what the host was doing
    inside the harness's call."""
    named = []
    for (label, seconds), at in zip(summary.idle_gaps, summary.gap_at):
        t = window_start + at
        open_now = [sp for sp in spans if sp.t0 <= t < (sp.t1 or sp.t0)]
        parents = {sp.parent_id for sp in open_now}
        inner = sorted({sp.name for sp in open_now if sp.span_id not in parents})
        named.append((f"{label}: {'+'.join(inner)}" if inner else label, seconds))
    summary.idle_gaps = named


def reduce_file(path: Path, window: str = WINDOW_ANNOTATION) -> TraceSummary:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(str(path)), window)


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


# --------------------------------------------------------------- peaks
PEAKS_PATH = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks; a kind not in the table is an error."""
    table = json.loads(PEAKS_PATH.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {PEAKS_PATH.name}")
    return table[device_kind]


# ------------------------------------------------- kernel bytes and roofline
def op_block_counts(ops: Sequence[DeviceOp], kernel: str) -> List[int]:
    """Block count of the first operand of each ``%<kernel>`` custom call:
    the ``s32[num_blocks,8,128]`` view of the leaf the kernel reads."""
    out = []
    prefix = f"%{kernel}"
    for op in ops:
        if op.name.startswith(prefix) and "custom-call(" in op.name:
            m = _SHAPE.search(op.name.split("custom-call(", 1)[1])
            if m:
                out.append(int(m.group(1).split(",")[0]))
    return out


def block_diff_bytes(num_blocks: int) -> int:
    """Least HBM traffic of one changed-block mask: both leaves read once."""
    return 2 * num_blocks * BLOCK_BYTES


def roofline_share(least_bytes: float, seconds: float,
                   hbm_bytes_per_s: float) -> Optional[float]:
    """Percent of the HBM roofline: the least time the bytes need at the
    peak over the measured device time.  None where nothing was measured."""
    if seconds <= 0 or least_bytes <= 0:
        return None
    return 100.0 * least_bytes / hbm_bytes_per_s / seconds

