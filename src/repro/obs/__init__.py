"""Observability: end-to-end span tracing + live tradeoff telemetry.

Three pieces, one contract (details in each module's docstring):

* :mod:`.tracer` — the span tracer the whole request path records into:
  ``DatasetService`` request lifecycle (enqueue → queue wait → coalesce /
  batch fold → dispatch), ``Materializer`` plan/decode with cache-hit and
  fused-launch attribution, ``apply_delta_chains`` device launches,
  ``optimize()`` solver runs, and repack/fsck quiesce windows.  Spans
  propagate through ``contextvars`` (nesting survives ``await`` and, via
  :meth:`Tracer.attach`, the hop onto reader/writer pool threads), land in
  a bounded ring buffer, and cost one attribute check when tracing is
  disabled — the default.
* :mod:`.export` — Chrome trace-event JSON (Perfetto-loadable, one track
  per thread/task) and Prometheus-style text exposition merging
  ``ServiceMetrics`` with the store/tradeoff gauges.
* :mod:`.tradeoff` — :class:`TradeoffMonitor`: live (C, R) samples on every
  commit/repack (storage bytes by full/delta object, per-version
  recreation-cost percentiles, the access-weighted recreation sum of
  Problems 5/6) with a post-repack baseline, so drift is a *number* the
  ``FsckSweeper`` can put in its repack recommendation.

Quick start::

    from repro import obs

    with obs.tracing() as tracer:            # enabled tracer, auto-restored
        ...  # run service traffic
    obs.chrome_trace(tracer, "trace.json")   # load in ui.perfetto.dev

CLI: ``python -m repro.obs {summary,trace,prom,overhead}``
(``--synthetic`` self-exercises a throwaway store end to end).
"""

from __future__ import annotations

import contextlib
from typing import Iterator

from .export import (
    chrome_trace,
    prometheus_text,
    validate_chrome_trace,
)
from .tracer import (
    NULL_SPAN,
    Span,
    Tracer,
    add_event,
    enabled,
    get_tracer,
    set_tracer,
    span,
    start,
)
from .tradeoff import TradeoffMonitor, TradeoffSample

__all__ = [
    "Span",
    "Tracer",
    "NULL_SPAN",
    "get_tracer",
    "set_tracer",
    "span",
    "start",
    "add_event",
    "enabled",
    "tracing",
    "chrome_trace",
    "validate_chrome_trace",
    "prometheus_text",
    "TradeoffMonitor",
    "TradeoffSample",
]


@contextlib.contextmanager
def tracing(*, capacity: int = 65536) -> Iterator[Tracer]:
    """Install a fresh enabled tracer as the process global for the block;
    restores the previous tracer on exit and yields the new one (its spans
    stay readable after the block ends)."""
    tracer = Tracer(enabled=True, capacity=capacity)
    old = set_tracer(tracer)
    try:
        yield tracer
    finally:
        set_tracer(old)
