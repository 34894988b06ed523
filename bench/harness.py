"""One run of one cell: set-up, warm-up, the measured window, the comparison.

The phases are plain functions of a :class:`Run`, so the tests drive them at
a tiny size on the CPU without the command line and its look for a chip.
What is measured is read by the metric readers the cell names:
``bench/end_to_end/<metric>.py`` and ``bench/layer_metrics/<metric>.py``, each
``read(window) -> float | None`` over the :class:`Window` below.
"""

from __future__ import annotations

import asyncio
import collections
import dataclasses
import importlib.util
import json
import math
import shutil
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from . import loadgen, trace, verify

ROOT = Path(__file__).resolve().parents[1]
HERE = ROOT / "bench"
#: the persistent compile cache: a fixed path inside the checkout
CACHE_DIR = ROOT / ".bench_jax_cache"
#: warm-up rounds at most (a round that builds no program ends warm-up)
MAX_WARM_ROUNDS = 4
#: versions made before the window: its length at the warm-up's rate, times this
PREPARE_MARGIN = 1.5

Tree = Dict[str, np.ndarray]


# ------------------------------------------------------------ finding by name
def benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _module(path: Path):
    if not path.exists():
        raise FileNotFoundError(f"{path} not found")
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """A workload entry with everything its names point at."""

    name: str
    chips: int
    config: dict
    reference: Any
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @classmethod
    def load(cls, name: str, bench_dir: Path = HERE) -> "Cell":
        """The workload ``name`` of ``BENCHMARK.json``."""
        bm = benchmark(bench_dir.parent)
        entry = next((w for w in bm["workloads"] if w["name"] == name), None)
        if entry is None:
            known = ", ".join(w["name"] for w in bm["workloads"])
            raise KeyError(f"no workload {name!r} in BENCHMARK.json (known: {known})")
        return cls.build(name, entry["config"], entry["traffic"], int(entry["chips"]), bench_dir)

    @classmethod
    def build(cls, name: str, config: str, traffic: str, chips: int = 1,
              bench_dir: Path = HERE) -> "Cell":
        """A cell of configuration ``config`` under mix ``traffic``, with
        the metrics ``BENCHMARK.json`` gives a cell of that name."""
        bm = benchmark(bench_dir.parent)
        cfg_path = bench_dir / "configs" / f"{config}.json"

        def mine(m: dict) -> bool:
            return "workloads" not in m or name in m["workloads"]

        return cls(
            name=name,
            chips=chips,
            config=json.loads(cfg_path.read_text()),
            reference=_module(cfg_path.with_suffix(".py")),
            traffic=json.loads((bench_dir / "traffic" / f"{traffic}.json").read_text()),
            end_to_end=[m for m in bm["end_to_end"] if mine(m)],
            per_layer=[m for m in bm["per_layer"] if mine(m)],
        )


def reader(kind: str, metric: str, bench_dir: Path = HERE) -> Callable:
    """The ``read`` function of ``bench/<kind>/<metric>.py``."""
    return _module(bench_dir / kind / f"{metric}.py").read


# ------------------------------------------------------------------ the chip
def require_chips(chips: int) -> Tuple[Any, Dict[str, float]]:
    """The first device and its peaks; raises unless JAX holds at least
    ``chips`` TPU chips of a kind the peaks table knows."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(
            f"needs a TPU, but JAX's default platform is {devs[0].platform!r} "
            f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise RuntimeError(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[0], trace.peaks_for(devs[0].device_kind)


def require_zstd() -> None:
    from repro.store.objectstore import Codec

    backend = Codec().backend
    if backend != "zstd":
        raise RuntimeError(f"the store's codec is {backend!r}, the configuration states zstd")


def enable_compile_cache(path: Path = CACHE_DIR) -> None:
    """JAX's persistent compile cache at a fixed path in the checkout,
    keeping every program however fast it compiled."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileLog:
    """Monotonic times of program builds (compiled or loaded from the
    persistent cache) and of persistent-cache misses (fresh compiles)."""

    def __init__(self) -> None:
        import jax
        from jax._src import dispatch

        self.builds: List[Tuple[float, float]] = []  # (time, seconds)
        self.misses: List[float] = []
        self._event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_: Any) -> None:
        if event == self._event:
            self.builds.append((time.monotonic(), duration))

    def _on_event(self, event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_misses":
            self.misses.append(time.monotonic())

    def between(self, t0: float, t1: float) -> Dict[str, int]:
        inside = [d for t, d in self.builds if t0 <= t <= t1]
        return {"programs_built": len(inside), "build_seconds": sum(inside),
                "compiled": sum(t0 <= t <= t1 for t in self.misses)}


# ------------------------------------------------------------------ the window
@dataclasses.dataclass
class Window:
    """What one measured window did: what the metric readers read."""

    requests: List[loadgen.Request]
    seconds: float
    setup_s: float
    stored_bytes: int                 # object bytes the window's commits wrote
    peaks: Dict[str, float]
    spans: list = dataclasses.field(default_factory=list)
    trace: Optional[trace.TraceSummary] = None
    started: float = 0.0              # time.monotonic() as the window opened

    def of(self, op: str) -> List[loadgen.Request]:
        return [r for r in self.requests if r.op == op and r.error is None]


def objects_bytes(store_root: Path) -> int:
    return sum(f.stat().st_size for f in (store_root / "objects").glob("*/*.zst"))


def tree_bytes(tree: Tree) -> int:
    return sum(a.nbytes for a in tree.values())


class Run:
    """The state of one run of one cell on one store: versions are numbered
    by the harness in commit order on ``main``, each derived from the one
    before by the reference."""

    def __init__(self, cell: Cell, seed: int, store_root: Path,
                 log: Callable[[str], None] = lambda s: None) -> None:
        self.cell, self.cfg, self.ref = cell, cell.config, cell.reference
        self.traffic, self.seed, self.store_root, self.log = cell.traffic, seed, store_root, log
        loadgen.check_mix(self.traffic)
        self.tip: Optional[Tuple[int, Tree]] = None
        self.pending: collections.deque = collections.deque()
        self.made_late = 0                # trees made after the window opened
        self.rate_hz = 0.0                # requests per second in the last warm-up round
        self.window_commits: List[Tuple[int, int]] = []

    # -- versions ----------------------------------------------------------
    def _make_commit(self) -> Tuple[int, Tree]:
        if self.tip is None:
            index, tree = 0, self.ref.base_tree(self.cfg, self.seed)
        else:
            index = self.tip[0] + 1
            tree = self.ref.apply(self.cfg, self.tip[1], self.ref.edit(self.cfg, self.seed, index))
        self.tip = (index, tree)
        return index, tree

    def next_commit(self, in_window: bool) -> Tuple[int, Tree]:
        """The next version: made before the window, or made now."""
        if self.pending:
            return self.pending.popleft()
        self.made_late += in_window
        return self._make_commit()

    def prepare_commits(self, n: int) -> None:
        """Make the next ``n`` versions' trees before the window."""
        for _ in range(n):
            self.pending.append(self._make_commit())

    async def build_history(self, svc) -> None:
        """Commit the configuration's set-up history through the service:
        the base and ``history.saves`` saves."""
        t0 = time.perf_counter()
        for _ in range(1 + self.cfg["history"]["saves"]):
            index, tree = self._make_commit()
            await svc.commit(tree, message=f"v{index}", branch="main")
        self.log(f"history: {index + 1} versions of {tree_bytes(tree)} B "
                 f"committed in {time.perf_counter() - t0:.3f} s")

    # -- requests ----------------------------------------------------------
    def issuer(self, svc, in_window: bool):
        import jax

        async def issue(req: loadgen.Request) -> None:
            index, tree = self.next_commit(in_window)
            with jax.profiler.TraceAnnotation("bench.commit"):
                vid = await svc.commit(tree, message=f"v{index}", branch="main")
            self.window_commits.append((index, vid))
            req.vid, req.index, req.nbytes = vid, index, tree_bytes(tree)

        return issue

    async def warm(self, svc, built: Callable[[], int] = lambda: 0) -> None:
        """The cell's own traffic before the window: rounds of
        ``warm_seconds`` until a round builds no program (``built`` counts
        the programs built so far) or ``MAX_WARM_ROUNDS`` have run.  Every
        shape the traffic uses is then compiled before the window, and the
        last round's rate says how many versions the window will commit."""
        t0 = time.perf_counter()
        for rnd in range(MAX_WARM_ROUNDS):
            before = built()
            reqs, seconds = await loadgen.run_closed(
                self.traffic, float(self.traffic["warm_seconds"]), self.issuer(svc, False))
            bad = [r.error for r in reqs if r.error]
            if bad:
                raise RuntimeError(f"warm-up requests failed: {bad[:3]}")
            self.rate_hz = len(reqs) / seconds
            if built() == before:
                break
        self.window_commits.clear()
        self.log(f"warm-up: {rnd + 1} rounds in {time.perf_counter() - t0:.3f} s, "
                 f"{self.rate_hz:.3f} requests/s in the last")

    def window_traffic(self, svc, seconds: float, on_start: Callable[[], None]):
        """The window: its versions' trees made first (the warm-up's rate
        with ``PREPARE_MARGIN`` to spare, and one more per client), then
        ``on_start`` as it opens, then the cell's traffic for ``seconds``."""
        need = math.ceil(seconds * self.rate_hz * PREPARE_MARGIN) + int(self.traffic["clients"])
        self.prepare_commits(need)
        on_start()
        return loadgen.run_closed(self.traffic, seconds, self.issuer(svc, True))

    # -- the comparison ----------------------------------------------------
    def compare(self, failed: int, control: bool = False) -> Dict[str, int]:
        """Reopen the store from disk, read back every commit the window
        acknowledged, and compare it with the reference."""
        from repro.store import Repository

        self.pending.clear()
        self.tip = None
        repo = Repository(self.store_root,
                          cache_budget_bytes=self.cfg["store"]["cache_budget_bytes"])
        try:
            commits = [(i, (lambda v=v: repo.checkout(v))) for i, v in self.window_commits]
            return verify.compare(self.ref, self.cfg, self.seed, commits, failed,
                                  control=control)
        finally:
            repo.close()


# ------------------------------------------------------------------ one run
@dataclasses.dataclass
class Outcome:
    window: Window
    numbers: Dict[str, int]
    compiles: Dict[str, int]
    memory_peak_bytes: Optional[int]
    attempted: int
    failed: int


async def _measure(run: Run, svc, seconds: float, traced: bool, started: float,
                   peaks: Dict[str, float], trace_dir: Optional[Path]) -> Window:
    import jax

    obj0 = objects_bytes(run.store_root)
    setup_end = []
    tracer = None
    if traced:
        from repro import obs

        tracer = obs.Tracer(enabled=True, capacity=4_000_000)
        old = obs.set_tracer(tracer)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        traffic = run.window_traffic(svc, seconds, lambda: setup_end.append(time.perf_counter()))
        started_mono = time.monotonic()
        with jax.profiler.TraceAnnotation(trace.WINDOW_ANNOTATION):
            reqs, window_s = await traffic
    finally:
        if traced:
            jax.profiler.stop_trace()
            obs.set_tracer(old)
    return Window(
        requests=reqs, seconds=window_s, setup_s=setup_end[0] - started,
        stored_bytes=objects_bytes(run.store_root) - obj0,
        peaks=peaks,
        spans=tracer.spans() if tracer else [],
        started=started_mono,
    )


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, started: float,
             peaks: Dict[str, float], work_dir: Path, device=None,
             log: Callable[[str], None] = lambda s: None,
             control: bool = False) -> Outcome:
    """Set-up, warm-up, the window and the comparison, on a store under
    ``work_dir``.  ``device`` is read for the peak memory after the window."""
    from repro.store import Repository

    compile_log = CompileLog()
    store_root = work_dir / "store"
    trace_dir = work_dir / "trace"
    run = Run(cell, seed, store_root, log)
    repo = Repository(store_root, cache_budget_bytes=cell.config["store"]["cache_budget_bytes"])

    async def serve() -> Tuple[Window, float, float]:
        async with repo.serve(**cell.config["service"]) as svc:
            await run.build_history(svc)
            await run.warm(svc, lambda: len(compile_log.builds))
            t0 = time.monotonic()
            w = await _measure(run, svc, seconds, traced, started, peaks, trace_dir)
            return w, t0, time.monotonic()

    try:
        window, t0, t1 = asyncio.run(serve())
    finally:
        repo.close()
    stats = device.memory_stats() if device is not None else None
    peak = (stats or {}).get("peak_bytes_in_use")
    compiles = compile_log.between(t0, t1)
    log(f"compiles inside the window: {compiles['programs_built']} programs built "
        f"in {compiles['build_seconds']:.3f} s, {compiles['compiled']} of them compiled afresh")
    log(f"trees made inside the window: {run.made_late}; store on disk: "
        f"{objects_bytes(store_root)} B")
    log("request seconds, in issue order: "
        + " ".join(f"{r.done - r.at:.3f}" for r in window.requests))
    if traced:
        window.trace = trace.reduce_file(trace.find_xplane(trace_dir))
        trace.name_gaps(window.trace, window.spans, window.started)
        shutil.rmtree(trace_dir, ignore_errors=True)
    failed = sum(r.error is not None for r in window.requests)
    for r in window.requests:
        if r.error:
            log(f"request failed: {r.op} v{r.index}: {r.error}")
    del repo
    numbers = run.compare(failed, control=control)
    return Outcome(window, numbers, compiles, peak, len(window.requests), failed)


def metrics_of(cell: Cell, window: Window, traced: bool,
               bench_dir: Path = HERE) -> Dict[str, Dict[str, Any]]:
    """The cell's end-to-end metrics (untraced) or per-layer ones (traced),
    each from its own reader; a reader that finds nothing is left out."""
    kind, entries = (("layer_metrics", cell.per_layer) if traced
                     else ("end_to_end", cell.end_to_end))
    out = {}
    for m in entries:
        value = reader(kind, m["name"], bench_dir)(window)
        if value is not None and math.isfinite(value):
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
