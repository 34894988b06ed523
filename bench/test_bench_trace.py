"""Trace reduction on a small trace recorded on a TPU v5e (two programs of
each main-path kernel, annotation ``bench.tiny``), and the load generator."""

from __future__ import annotations

from pathlib import Path

import pytest

from bench import loadgen, trace

TINY = Path(__file__).resolve().parent / "testdata" / "tiny.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return trace.reduce_file(TINY, window="bench.tiny")


def test_bench_trace_busy_and_programs(summary):
    assert summary.chips == 1
    assert 0 < summary.busy_s < summary.window_s
    assert summary.program_calls["jit_changed_block_mask"] == 1
    assert summary.program_calls["jit_chain_delta_apply_batched"] == 1
    # busy time is the union of the programs' intervals: never more than their sum
    assert summary.busy_s <= sum(summary.programs.values()) + 1e-12
    gaps = sum(s for _, s in summary.idle_gaps)
    assert gaps + summary.busy_s == pytest.approx(summary.window_s, rel=1e-6)


def test_bench_trace_kernel_shapes_and_roofline(summary):
    assert trace.op_block_counts(summary.ops, "changed_block_mask") == [64]
    seconds = summary.program_seconds("jit_changed_block_mask")
    share = trace.roofline_share(trace.block_diff_bytes(64), seconds, 819e9)
    assert 0 < share < 100
    assert trace.roofline_share(0, seconds, 819e9) is None


def test_bench_trace_breakdown_shape(summary):
    b = summary.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][1] == max(summary.programs.values())


def test_bench_trace_interval_arithmetic():
    assert trace.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert trace.gaps([(0, 3), (5, 7)], 0, 10) == [(3, 5), (7, 10)]
    assert trace.clip([(0, 3), (5, 12)], 2, 10) == [(2, 3), (5, 10)]
    assert trace.program_name("jit_chain_delta_apply(8802382355063944230)") == "jit_chain_delta_apply"


def test_bench_peaks_table():
    assert trace.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        trace.peaks_for("cpu")


def test_bench_closed_loop_runs_whole_requests():
    """Each client issues its next request when the last returns; the
    window closes at the last return, past its nominal end."""
    import asyncio

    async def issue(req):
        await asyncio.sleep(0.03)

    reqs, seconds = asyncio.run(loadgen.run_closed({"clients": 2}, 0.1, issue))
    assert len(reqs) >= 4 and len(reqs) % 2 == 0
    assert all(r.error is None and r.done > r.at for r in reqs)
    assert seconds == max(r.done for r in reqs) >= 0.1


def test_bench_closed_loop_counts_a_failed_request():
    import asyncio

    async def issue(req):
        raise OSError("disk full")

    reqs, _ = asyncio.run(loadgen.run_closed({"clients": 1}, 0.01, issue))
    assert reqs and all(r.error == "OSError: disk full" for r in reqs)


@pytest.mark.parametrize("mix", [
    {"loop": "open", "ops": {"commit": 1.0}},
    {"loop": "closed", "ops": {"checkout": 0.5, "commit": 0.5}}])
def test_bench_mix_the_generator_cannot_send_is_refused(mix):
    with pytest.raises(ValueError):
        loadgen.check_mix(mix)


def test_bench_gaps_named_by_innermost_program_span():
    from types import SimpleNamespace as S

    summary = trace.TraceSummary(window_s=2.0, busy_s=0.1, chips=1, programs={},
                                 program_calls={}, ops=[],
                                 idle_gaps=[("bench.commit", 1.0), ("bench.commit", 0.2)],
                                 gap_at=[0.5, 1.95])
    spans = [S(name="svc.commit", span_id=1, parent_id=None, t0=100.0, t1=102.0),
             S(name="store.commit", span_id=2, parent_id=1, t0=100.1, t1=101.9)]
    trace.name_gaps(summary, spans, window_start=100.0)
    assert summary.idle_gaps == [("bench.commit: store.commit", 1.0),
                                 ("bench.commit: svc.commit", 0.2)]
