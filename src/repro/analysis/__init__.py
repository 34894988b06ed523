"""repro.analysis — static analyses gating CI as a *regression* framework.

Two passes share one :class:`~repro.analysis.findings.Finding` / baseline
framework:

* :func:`~repro.analysis.kernel_audit.run_audit` — the **kernel/dispatch
  auditor**: traces every registered Pallas kernel and jitted solver entry
  point to a jaxpr (abstractly, via ``jax.make_jaxpr`` — no accelerator and
  no execution; the ``pallas_call`` equations the auditor inspects appear in
  the trace whether a kernel would be interpreted or compiled at runtime)
  and lints jaxprs + module ASTs for TPU-readiness and dispatch-efficiency
  hazards.
* :func:`~repro.analysis.fsck.fsck_store` — the **storage-graph fsck**:
  walks a ``VersionStore`` like ``git fsck`` walks an object database
  (also surfaced as ``VersionStore.fsck()`` / ``Repository.fsck()``).

Rule catalog
============

Auditor (``python -m repro.analysis audit``):

``audit.trace`` (ERROR)
    Registered target failed to trace at all — it would silently drop out
    of every jaxpr rule.
``audit.dtype64`` (ERROR)
    Non-weak 64-bit values in a jaxpr traced under ``jax_enable_x64`` with
    the target's production input dtypes.  TPUs have no 64-bit lanes; in
    default x64-off mode the same code silently downcasts, so explicit
    64-bit intent (``astype(int64)``, default argmin index dtypes,
    promoting sums) is a latent porting bug.  Weak-typed Python scalars are
    exempt — they lower to the operand dtype.
``audit.dtype64-source`` (ERROR)
    ``int64``/``float64``/``uint64``/``complex128`` attribute tokens in
    kernel/hot-path module ASTs (catches paths the example trace misses;
    docstrings and comments don't count).
``audit.host-sync`` (ERROR)
    ``np.asarray`` / ``np.array`` / ``jax.device_get`` / ``.item()`` /
    ``.block_until_ready()`` inside a ``for``/``while`` loop of the
    materializer decode hot path (``store/delta.py`` appliers, the
    ``Materializer`` executors) — one blocking device→host sync per leaf.
    Batch: accumulate device results, one ``jax.device_get`` after the loop.
``audit.shape-bucket`` (ERROR)
    Two sub-checks: the bucket functions (``_slot_bucket``,
    ``_round_capacity``, ``grown_blocks``, ``_bucket_rows``,
    ``_bucket_width``) must cover, quantize (pow2 / multiple-of-8 / under an
    eighth of padding), and be idempotent + monotone; and
    same-bucket sizes must trace to identical Pallas kernel shapes —
    otherwise jit/kernel compile caches fragment per size.
``audit.io-alias`` (WARNING)
    A ``pallas_call`` output ≥ 1 MiB matching an input's shape+dtype must
    be aliased (``input_output_aliases``); otherwise the dispatch allocates
    a second full-size HBM buffer on the checkout hot path.

Fsck (``python -m repro.analysis fsck ROOT | --synthetic``): see
:mod:`repro.analysis.fsck` — dangling parents/bases, ``stored_base``
cycles, missing/orphaned objects, independent chain re-decode with content
fingerprint recomputation (cache-bypassing, so bit flips at rest are
caught), ref validity, and re-validation of the constraint bounds recorded
by the last ``repack`` against the current storage graph.

Baseline workflow
=================

Findings gate CI only when **new**.  ``Finding.key()`` (``rule::subject``,
deliberately line-number-free) identifies a finding across unrelated edits;
the committed ``analysis_baseline.json`` lists accepted keys.  CI runs::

    python -m repro.analysis audit                # exit 1 on new findings
    python -m repro.analysis fsck --synthetic     # exit 1 on any finding

To accept a finding deliberately::

    python -m repro.analysis audit --write-baseline
    git add analysis_baseline.json               # review the note, commit

NOTE-level findings (e.g. the allowlisted 64-bit solver math of
``core/solvers/jax_backend.py``, documented until the real-accelerator f32
flip) never gate and never need baselining; a baselined WARNING that later
escalates to ERROR re-gates.
"""

from .findings import (
    GATE_SEVERITY,
    Finding,
    Report,
    Severity,
    load_baseline,
    partition,
    write_baseline,
)

__all__ = [
    "Finding",
    "Severity",
    "Report",
    "GATE_SEVERITY",
    "load_baseline",
    "write_baseline",
    "partition",
    "run_audit",
    "fsck_store",
]


def run_audit():
    """Lazy re-export of :func:`repro.analysis.kernel_audit.run_audit`
    (importing jax only when the auditor actually runs)."""
    from .kernel_audit import run_audit as _run

    return _run()


def fsck_store(store, **kwargs):
    """Lazy re-export of :func:`repro.analysis.fsck.fsck_store`."""
    from .fsck import fsck_store as _fsck

    return _fsck(store, **kwargs)
