"""Observability tests: tracer concurrency, exporters, tradeoff telemetry.

* **tracer** — context-manager nesting and parent/trace ids; propagation
  across ``await`` within a task; spans started on the event loop and
  closed from a pool thread; ``attach`` bridging parenthood onto executor
  threads; ring-buffer wraparound with the ``dropped`` counter; the
  disabled tracer recording nothing and returning the falsy null span.
* **exporters** — Chrome trace structural validity (and the validator
  catching broken traces), multi-pid merge, Prometheus text over a service
  snapshot.
* **tradeoff** — monitor samples on commit, the baseline flip on repack,
  drift ratios and the human drift line.
* **percentile** — floor-half-up pins (the banker's-rounding regression).
* **integration** — service traffic under ``obs.tracing()``: every layer's
  spans present, span totals reconciling with the ``ServiceMetrics``
  queue-wait/decode tracks within 5%, monitor attach/detach across the
  service lifecycle.

No pytest-asyncio in the image: async tests drive their own loop via
``asyncio.run``.
"""

import asyncio
import json
import math
import threading

import numpy as np
import pytest

from repro import obs
from repro.obs import (
    NULL_SPAN,
    Span,
    Tracer,
    TradeoffMonitor,
    chrome_trace,
    prometheus_text,
    validate_chrome_trace,
)
from repro.service import DatasetService, percentile
from repro.store import Repository


def payload(seed: int, shape=(32, 24)):
    rng = np.random.RandomState(seed)
    return {"w": rng.randn(*shape).astype(np.float32)}


def build_repo(tmp_path, versions=5):
    repo = Repository(tmp_path)
    for i in range(versions):
        repo.commit(payload(i), message=f"v{i}")
    return repo


# --------------------------------------------------------------- tracer core
class TestTracer:
    def test_nesting_and_ids(self):
        tr = Tracer(enabled=True)
        with tr.span("outer") as o:
            with tr.span("inner") as i:
                assert i.parent_id == o.span_id
                assert i.trace_id == o.trace_id == o.span_id
        spans = tr.spans()
        assert [s.name for s in spans] == ["inner", "outer"]  # close order
        assert spans[1].t0 <= spans[0].t0
        assert all(s.t1 >= s.t0 for s in spans)

    def test_attrs_and_duration(self):
        tr = Tracer(enabled=True)
        with tr.span("op", a=1) as sp:
            sp.set(b=2)
        (got,) = tr.spans()
        assert got.attrs == {"a": 1, "b": 2}
        assert got.duration >= 0.0

    def test_context_propagates_across_await(self):
        tr = Tracer(enabled=True)

        async def inner():
            await asyncio.sleep(0)
            with tr.span("child"):
                await asyncio.sleep(0)

        async def main():
            with tr.span("root"):
                await asyncio.sleep(0.001)  # force a real suspension
                await inner()

        asyncio.run(main())
        by_name = {s.name: s for s in tr.spans()}
        assert by_name["child"].parent_id == by_name["root"].span_id

    def test_concurrent_tasks_get_separate_tracks(self):
        tr = Tracer(enabled=True)

        async def req(i):
            with tr.span("req"):
                await asyncio.sleep(0.001)

        async def main():
            await asyncio.gather(*(req(i) for i in range(3)))

        asyncio.run(main())
        tracks = {s.track for s in tr.spans()}
        assert len(tracks) == 3  # one per asyncio task
        assert all(t.startswith("task:") for t in tracks)

    def test_open_on_loop_close_on_thread(self):
        """A span started in one context may be ended from another thread;
        only the thread-safe idempotent end() records it."""
        tr = Tracer(enabled=True)
        sp = tr.start("crossing")
        done = threading.Event()

        def worker():
            sp.end()
            sp.end()  # idempotent: second end must not double-record
            done.set()

        threading.Thread(target=worker).start()
        assert done.wait(5.0)
        assert len(tr) == 1
        assert tr.spans()[0].name == "crossing"

    def test_attach_bridges_pool_threads(self):
        tr = Tracer(enabled=True)

        async def main():
            loop = asyncio.get_running_loop()
            parent = tr.start("dispatch")

            def work():
                # pool threads do NOT inherit the submitting context...
                with tr.attach(parent):
                    with tr.span("decode"):
                        pass

            await loop.run_in_executor(None, work)
            parent.end()

        asyncio.run(main())
        by_name = {s.name: s for s in tr.spans()}
        assert by_name["decode"].parent_id == by_name["dispatch"].span_id
        assert by_name["decode"].track.startswith("thread:")

    def test_ring_wraparound_counts_dropped(self):
        tr = Tracer(enabled=True, capacity=8)
        for i in range(20):
            with tr.span(f"s{i}"):
                pass
        assert len(tr) == 8
        assert tr.dropped == 12
        assert [s.name for s in tr.spans()] == [f"s{i}" for i in range(12, 20)]

    def test_disabled_records_nothing(self):
        tr = Tracer(enabled=False)
        sp = tr.span("x")
        assert sp is NULL_SPAN and not sp
        with tr.span("y") as y:
            y.set(k=1)  # all no-ops
        tr.add_event("z", 0.0, 1.0)
        assert len(tr) == 0
        # and a null parent never poisons an enabled tracer's lineage
        tr.enable()
        real = tr.start("real", parent=NULL_SPAN)
        real.end()
        assert tr.spans()[0].parent_id is None

    def test_retroactive_add_event(self):
        tr = Tracer(enabled=True)
        tr.add_event("queue_wait", 10.0, 10.5, vid=7)
        (sp,) = tr.spans()
        assert (sp.t0, sp.t1) == (10.0, 10.5)
        assert sp.attrs["vid"] == 7

    def test_tracing_contextmanager_restores_global(self):
        before = obs.get_tracer()
        with obs.tracing() as tr:
            assert obs.get_tracer() is tr and tr.enabled
            with obs.span("inside"):
                pass
        assert obs.get_tracer() is before
        assert {s.name for s in tr.spans()} == {"inside"}

    def test_summary_rollup(self):
        tr = Tracer(enabled=True)
        for _ in range(3):
            with tr.span("op"):
                pass
        s = tr.summary()["op"]
        assert s["count"] == 3
        assert s["total_s"] >= s["max_s"] >= s["mean_s"] >= 0


# ---------------------------------------------------------------- exporters
class TestExporters:
    def _traced(self):
        tr = Tracer(enabled=True)
        with tr.span("svc.request", vid=1):
            with tr.span("mat.decode"):
                pass
        return tr

    def test_chrome_trace_valid_and_loadable(self, tmp_path):
        tr = self._traced()
        path = tmp_path / "trace.json"
        chrome_trace(tr, path)
        assert validate_chrome_trace(path) == []
        doc = json.loads(path.read_text())
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert {e["name"] for e in xs} == {"svc.request", "mat.decode"}
        # child nests inside parent on the timeline
        by = {e["name"]: e for e in xs}
        parent, child = by["svc.request"], by["mat.decode"]
        assert parent["ts"] <= child["ts"]
        assert child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 1
        assert child["args"]["parent_id"] == parent["args"]["span_id"]
        assert "_origin_s" not in doc  # private keys stripped on write

    def test_chrome_trace_merges_pids(self, tmp_path):
        a, b = self._traced(), self._traced()
        merged = chrome_trace(a, pid=1, process_name="chain")
        path = tmp_path / "merged.json"
        chrome_trace(b, path, pid=2, process_name="global", base=merged)
        assert validate_chrome_trace(path) == []
        doc = json.loads(path.read_text())
        assert {e["pid"] for e in doc["traceEvents"]} == {1, 2}
        names = {
            (e["pid"], e["args"]["name"])
            for e in doc["traceEvents"]
            if e.get("name") == "process_name"
        }
        assert names == {(1, "chain"), (2, "global")}

    def test_validator_rejects_broken_traces(self):
        assert validate_chrome_trace({"nope": 1})
        assert validate_chrome_trace(
            {"traceEvents": [{"ph": "X", "name": "a", "pid": 1, "tid": 1,
                              "ts": -5, "dur": 1}]}
        )
        # X events on a tid with no thread_name metadata
        probs = validate_chrome_trace(
            {"traceEvents": [{"ph": "X", "name": "a", "pid": 1, "tid": 3,
                              "ts": 0, "dur": 1}]}
        )
        assert any("thread_name" in p for p in probs)

    def test_prometheus_text(self):
        snapshot = {
            "counters": {"requests.checkout": 4},
            "tracks": {"latency.checkout":
                       {"count": 4, "mean_ms": 2.0, "p50_ms": 1.5,
                        "p99_ms": 3.0, "max_ms": 3.1}},
            "gauges": {"tradeoff.storage_ratio": 1.25},
            "store": {"hits": 3},
            "tradeoff": {"latest": {
                "storage_bytes_full": 100, "storage_bytes_delta": 40,
                "full_objects": 1, "delta_objects": 4,
                "recreation_p50_s": 0.1, "recreation_p99_s": 0.2,
                "recreation_max_s": 0.3, "recreation_sum_s": 0.7,
                "access_weighted_recreation_s": 0.5,
            }, "drift": {"storage_ratio": 1.25,
                         "access_weighted_recreation_ratio": 2.3}},
        }
        text = prometheus_text(snapshot)
        assert "repro_requests_checkout_total 4" in text
        assert 'repro_latency_checkout_seconds{quantile="0.5"} 0.0015' in text
        assert "repro_latency_checkout_seconds_count 4" in text
        assert "repro_latency_checkout_seconds_sum 0.008" in text
        assert 'repro_tradeoff_storage_bytes{kind="delta"} 40' in text
        assert (
            "repro_tradeoff_drift_access_weighted_recreation_ratio 2.3"
            in text
        )
        # every line is a comment or "name{labels} value"
        for line in text.strip().splitlines():
            assert line.startswith("# ") or len(line.rsplit(" ", 1)) == 2


# -------------------------------------------------------------- percentile
class TestPercentile:
    def test_half_ranks_round_up(self):
        # round() banker's rounding would give xs[0] here (0.5 -> 0)
        assert percentile([1.0, 2.0], 50) == 2.0
        # rank 2.5 -> index 3 (round() would give 2)
        assert percentile([1, 2, 3, 4, 5, 6], 50) == 4

    def test_edges(self):
        xs = list(range(1, 102))
        assert percentile(xs, 0) == 1
        assert percentile(xs, 100) == 101
        assert percentile(xs, 99) == 100
        assert percentile([7.0], 50) == 7.0
        with pytest.raises(ValueError):
            percentile([], 50)

    def test_matches_numpy_nearest_on_odd_windows(self):
        xs = [float(x) for x in np.random.RandomState(0).randn(101)]
        for q in (1, 25, 50, 75, 99):
            idx = int(math.floor(q / 100 * 100 + 0.5))
            assert percentile(xs, q) == sorted(xs)[idx]


# ---------------------------------------------------------------- tradeoff
class TestTradeoffMonitor:
    def test_samples_on_commit_and_repack_baseline(self, tmp_path):
        from repro.core import OptimizeSpec

        repo = build_repo(tmp_path, versions=3)
        mon = TradeoffMonitor(repo.store)
        repo.store.tradeoff_monitor = mon
        s0 = mon.sample("start")
        assert s0.versions == 3
        assert s0.storage_bytes_total == repo.store.storage_bytes()
        assert mon.baseline is not None and mon.baseline.event == "start"

        repo.commit(payload(10), message="more")
        assert mon.latest.event == "commit"
        assert mon.latest.versions == 4

        repo.repack(OptimizeSpec.problem(1))
        assert mon.latest.event == "repack"
        assert mon.baseline.event == "repack"  # baseline flipped

        d = mon.drift()
        assert d["baseline_event"] == "repack"
        assert d["storage_ratio"] == pytest.approx(1.0)
        assert d["versions_added"] == 0
        line = mon.describe_drift()
        assert "post-repack baseline" in line and "1.00x" in line

    def test_recreation_side_matches_store_model(self, tmp_path):
        repo = build_repo(tmp_path, versions=4)
        mon = TradeoffMonitor(repo.store)
        s = mon.sample()
        store = repo.store
        costs = [store.recreation_cost(v) for v in store.versions]
        assert s.recreation_max_s == pytest.approx(max(costs))
        assert s.recreation_sum_s == pytest.approx(sum(costs))
        w = store.access_weights()
        awr = sum(
            w[v] * store.recreation_cost(v) for v in store.versions
        )
        assert s.access_weighted_recreation_s == pytest.approx(awr)
        full = [m for m in store.versions.values() if m.stored_base is None]
        assert s.full_objects == len(full)
        assert s.storage_bytes_full == sum(m.stored_bytes for m in full)

    def test_bounded_history(self, tmp_path):
        repo = build_repo(tmp_path, versions=2)
        mon = TradeoffMonitor(repo.store, capacity=4)
        for _ in range(10):
            mon.sample()
        assert len(mon.history) == 4
        assert mon.snapshot()["samples"] == 4

    def test_empty_store(self, tmp_path):
        from repro.store.version_store import VersionStore

        mon = TradeoffMonitor(VersionStore(tmp_path))
        s = mon.sample()
        assert s.versions == 0 and s.storage_bytes_total == 0
        assert mon.drift()["storage_ratio"] is None
        assert "n/a" in mon.describe_drift()


# -------------------------------------------------------------- integration
class TestServiceIntegration:
    def test_spans_cover_layers_and_reconcile(self, tmp_path):
        repo = build_repo(tmp_path, versions=4)
        vids = sorted(repo.store.versions)

        async def go():
            async with DatasetService(
                repo, readers=2, batch_window_s=0.001
            ) as svc:
                await svc.checkout_many(vids)
                await svc.checkout_many([vids[0]] * 3)  # coalesced
                await svc.commit(payload(50), message="append")
                return svc.stats()

        with obs.tracing() as tr:
            stats = asyncio.run(go())

        names = {s.name for s in tr.spans()}
        assert {"svc.checkout", "svc.batch", "svc.queue_wait", "svc.decode",
                "svc.commit", "store.commit", "mat.checkout_many",
                "mat.plan"} <= names

        # span totals share the clock with the metrics tracks: within 5%
        summary = tr.summary()
        for span_name, track in (("svc.queue_wait", "queue_wait"),
                                 ("svc.decode", "decode")):
            tk = stats["tracks"][track]
            track_total_s = tk["mean_ms"] * tk["count"] / 1e3
            span_total_s = summary[span_name]["total_s"]
            assert summary[span_name]["count"] == tk["count"]
            assert span_total_s == pytest.approx(track_total_s, rel=0.05)

        # parenting: every queue_wait hangs off a request root span
        by_id = {s.span_id: s for s in tr.spans()}
        for s in tr.spans():
            if s.name == "svc.queue_wait":
                assert by_id[s.parent_id].name == "svc.checkout"
            if s.name == "mat.checkout_many" and s.parent_id in by_id:
                assert by_id[s.parent_id].name in ("svc.batch", "store.parent")

    def test_disabled_tracer_traffic_records_nothing(self, tmp_path):
        repo = build_repo(tmp_path, versions=3)

        async def go():
            async with DatasetService(repo, readers=2) as svc:
                await svc.checkout_many(sorted(repo.store.versions))
                await svc.commit(payload(9), message="x")

        assert not obs.get_tracer().enabled  # the default global
        before = len(obs.get_tracer())
        asyncio.run(go())
        assert len(obs.get_tracer()) == before

    def test_monitor_lifecycle_and_stats(self, tmp_path):
        repo = build_repo(tmp_path, versions=3)

        async def go():
            svc = DatasetService(repo, readers=1)
            await svc.start()
            assert repo.store.tradeoff_monitor is not None
            await svc.commit(payload(20), message="append")
            stats = svc.stats()
            await svc.stop()
            assert repo.store.tradeoff_monitor is None  # detached
            return stats, svc.stats()

        stats, after = asyncio.run(go())
        trade = stats["tradeoff"]
        assert trade["latest"]["event"] == "commit"
        assert trade["latest"]["versions"] == 4
        assert trade["drift"]["baseline_event"] == "start"
        assert trade["drift"]["versions_added"] == 1
        # history stays readable through stats() after stop
        assert after["tradeoff"]["samples"] == trade["samples"]

    def test_tradeoff_opt_out(self, tmp_path):
        repo = build_repo(tmp_path, versions=2)

        async def go():
            async with DatasetService(repo, tradeoff=False) as svc:
                assert repo.store.tradeoff_monitor is None
                return svc.stats()

        stats = asyncio.run(go())
        assert "tradeoff" not in stats

    def test_sweeper_publishes_drift_gauges(self, tmp_path):
        repo = build_repo(tmp_path, versions=3)

        async def go():
            async with DatasetService(repo, readers=1) as svc:
                await svc.commit(payload(30), message="drifty")
                await svc.fsck()
                return svc.stats()

        stats = asyncio.run(go())
        g = stats["gauges"]
        assert g["tradeoff.storage_ratio"] >= 1.0
        assert g["tradeoff.versions_added"] == 1
        assert "tradeoff.access_weighted_recreation_ratio" in g
        assert stats["tradeoff"]["latest"]["event"] == "sweep"


class TestMetricsGauges:
    def test_set_and_snapshot(self):
        from repro.service import ServiceMetrics

        m = ServiceMetrics()
        m.set_gauge("x", 1.5)
        m.set_gauge("x", 2.5)  # last write wins
        assert m.gauge("x") == 2.5
        assert m.gauge("missing", -1.0) == -1.0
        assert m.snapshot()["gauges"] == {"x": 2.5}
