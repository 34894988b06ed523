"""Jaxpr-level kernel/dispatch auditor — the TPU-readiness lint.

Every registered kernel and jitted solver entry point is traced abstractly
with :func:`jax.make_jaxpr` (no accelerator, no execution; the Pallas calls
appear as ``pallas_call`` equations whether or not they would interpret at
runtime)
and the resulting jaxprs are linted against the rule catalog in
:mod:`repro.analysis`.  Traces run under ``jax_enable_x64`` with each
target's *production input dtypes*: explicit 64-bit intent (``astype(int64)``,
default ``argmin`` index dtypes, promoting ``sum``\\ s) then surfaces as real
64-bit avals, while in the default x64-off mode the very same code silently
downcasts — which is exactly the hazard class the rule exists to catch.

Source-level rules (the 64-bit token scan and the host-sync lint) parse the
module ASTs instead: some hazards — a ``np.asarray`` device→host sync inside
a per-leaf loop — are invisible to a jaxpr but obvious in the source.

Allowlist: the solver entry points of ``core/solvers/jax_backend.py`` run
under ``enable_x64`` *by contract* (float64 cost matrices, bit-identity with
the NumPy oracles); their 64-bit findings are downgraded to NOTE with the
reason attached.  Flipping them to f32 is the ROADMAP real-accelerator item,
at which point the allowlist entries should be deleted and the auditor keeps
them honest.
"""

from __future__ import annotations

import ast
import dataclasses
import functools
import inspect
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .findings import Finding, Report, Severity

S = jax.ShapeDtypeStruct

#: outputs at or above this many bytes must be aliased/donated when an input
#: of identical shape+dtype exists (rule audit.io-alias)
ALIAS_BYTES_THRESHOLD = 1 << 20

_BAD64 = ("int64", "float64", "uint64", "complex128")

#: {rule: {subject prefix: reason}} — matches are downgraded to NOTE
ALLOWLIST: Dict[str, Dict[str, str]] = {
    "audit.dtype64-source": {
        "repro.kernels.block_diff": (
            "hash_coefficients builds its table with host-side NumPy int64 "
            "RNG draws and bit-casts to int32 before any device upload; no "
            "64-bit value reaches a jaxpr (the jaxpr rule confirms)"
        ),
    },
}


# ------------------------------------------------------------------ targets
@dataclasses.dataclass(frozen=True)
class AuditTarget:
    """One traceable entry point: ``build()`` returns ``(fn, args)`` such
    that ``jax.make_jaxpr(fn)(*args)`` reproduces the production dispatch."""

    name: str                      # stable subject, e.g. "kernels.ops._compact"
    build: Callable[[], Tuple[Callable, Tuple[Any, ...]]]
    description: str = ""


def _kernel_targets() -> List[AuditTarget]:
    from ..kernels import block_diff, chain_apply, ops, segment_ops, \
        sparse_apply, xor_delta

    nb = 1024  # 4 MiB of 4 KiB blocks: big enough to trip the alias rule
    blocks = S((nb, 8, 128), jnp.int32)

    def t(name, build, description=""):
        return AuditTarget(name, build, description)

    return [
        t("kernels.segment_ops.segment_min_rows",
          lambda: (lambda x: segment_ops.segment_min_rows(x),
                   (S((256, 128), jnp.float32),)),
          "per-row min reduction (SSSP relaxation)"),
        t("kernels.segment_ops.segment_argmin_rows",
          lambda: (lambda x: segment_ops.segment_argmin_rows(x),
                   (S((256, 128), jnp.float32),)),
          "per-row first-argmin (parent selection)"),
        t("kernels.segment_ops.min_argmin_1d",
          lambda: (lambda x: segment_ops.min_argmin_1d(x),
                   (S((1000,), jnp.float32),)),
          "global (min, argmin) vertex pick"),
        t("kernels.segment_ops.min_argmin_1d[xla]",
          lambda: (lambda x: segment_ops.min_argmin_1d(x, use_pallas=False),
                   (S((1000,), jnp.float32),)),
          "XLA lowering of the same reduction (CPU fast path)"),
        t("kernels.xor_delta.xor_delta",
          lambda: (lambda a, b: xor_delta.xor_delta(a, b), (blocks, blocks)),
          "XOR delta encode/apply"),
        t("kernels.block_diff.changed_block_mask",
          lambda: (lambda a, b: block_diff.changed_block_mask(a, b),
                   (blocks, blocks)),
          "changed-block detection (delta encoder)"),
        t("kernels.block_diff.block_hash",
          lambda: (lambda x: block_diff.block_hash(
              x, jnp.asarray(block_diff.hash_coefficients())), (blocks,)),
          "per-block content hash (dedup hints)"),
        t("kernels.sparse_apply.sparse_delta_apply",
          lambda: (lambda b, p, i: sparse_apply.sparse_delta_apply(b, p, i),
                   (blocks, S((64, 8, 128), jnp.int32), S((64,), jnp.int32))),
          "block-sparse delta apply (one-hop recreation)"),
        t("kernels.chain_apply.chain_delta_apply",
          lambda: (lambda b, p, i: chain_apply.chain_delta_apply(b, p, i),
                   (blocks, S((4, 16, 8, 128), jnp.int32),
                    S((4, 16), jnp.int32))),
          "fused K-step chain apply (whole-chain checkout)"),
        t("kernels.chain_apply.chain_delta_apply_batched",
          lambda: (lambda b, p, i: chain_apply.chain_delta_apply_batched(
              b, p, i),
                   (S((4, 256, 8, 128), jnp.int32),
                    S((4, 16, 8, 128), jnp.int32), S((4, 16), jnp.int32))),
          "batched fused chain apply (many leaves, one launch)"),
        t("kernels.ops._compact",
          lambda: (functools.partial(ops._compact, capacity=16),
                   (S((256, 1), jnp.int32), S((256, 8, 128), jnp.int32))),
          "changed-block compaction (sparse encode)"),
        t("kernels.ops.to_blocks",
          lambda: (lambda x: ops.to_blocks(x)[0], (S((4096,), jnp.float32),)),
          "byte-preserving layout conversion (encode side)"),
    ]


def _solver_targets() -> List[AuditTarget]:
    from ..core.solvers import jax_backend as jb

    nvp, d = 16, 8
    ids = S((nvp, d), jnp.int32)
    w = S((nvp, d), jnp.float32)
    vec_i = S((nvp,), jnp.int32)
    vec_f = S((nvp,), jnp.float32)

    return [
        AuditTarget(
            "core.solvers.jax_backend._sssp_jit",
            lambda: (lambda ps, pw: jb._sssp_jit(ps, pw, True), (ids, w)),
            "jitted Bellman-Ford SSSP (Problem 2 / SPT)"),
        AuditTarget(
            "core.solvers.jax_backend._prim_jit",
            lambda: (lambda pd, pw, rd, rw, n: jb._prim_jit(
                pd, pw, rd, rw, n, True),
                (ids, w, vec_i, vec_f, S((), jnp.int32))),
            "jitted Prim (Problem 1, undirected)"),
        AuditTarget(
            "core.solvers.jax_backend._mp_jit",
            lambda: (lambda pd, pdl, pph, rd, rdl, rph, n, th: jb._mp_jit(
                pd, pdl, pph, rd, rdl, rph, n, th, True),
                (ids, w, w, vec_i, vec_f, vec_f, S((), jnp.int32),
                 S((), jnp.float32))),
            "jitted Modified Prim (Problems 4/6)"),
        AuditTarget(
            "core.solvers.jax_backend._lmg_score_jit",
            lambda: (lambda cu, cv, cd, cp, act, cur, dd, mm, ti, sz, wt, bu:
                     jb._lmg_score_jit(cu, cv, cd, cp, act, cur, dd, mm, ti,
                                       sz, wt, bu, True),
                (vec_i, vec_i, vec_f, vec_f, S((nvp,), jnp.bool_), vec_f,
                 vec_f, vec_f, vec_i, vec_i, S((), jnp.float32),
                 S((), jnp.float32))),
            "jitted LMG candidate scoring round (Problems 3/5)"),
    ]


def audit_targets() -> List[AuditTarget]:
    """Every registered kernel + jitted solver entry point, in audit order."""
    return _kernel_targets() + _solver_targets()


# ------------------------------------------------------------ jaxpr helpers
def trace_target(target: AuditTarget):
    """Abstractly trace a target under x64 (see module docstring)."""
    fn, args = target.build()
    with jax.enable_x64(True):
        return jax.make_jaxpr(fn)(*args)


def iter_eqns(jaxpr) -> Iterable[Any]:
    """All equations of a jaxpr, descending into sub-jaxprs (pjit bodies,
    while/cond/scan branches, pallas kernel bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    yield from iter_eqns(inner)
                elif hasattr(sub, "eqns"):
                    yield from iter_eqns(sub)


def _aval_of(var):
    aval = getattr(var, "aval", None)
    return aval if aval is not None and hasattr(aval, "dtype") else None


def _find_pallas_calls(jaxpr) -> List[Any]:
    return [e for e in iter_eqns(jaxpr) if e.primitive.name == "pallas_call"]


def _allowlisted(rule: str, subject: str) -> Optional[str]:
    for prefix, reason in ALLOWLIST.get(rule, {}).items():
        if subject.startswith(prefix):
            return reason
    return None


def _emit(report: Report, rule: str, severity: Severity, subject: str,
          message: str, fix_hint: str = "") -> None:
    """Add a finding, downgrading allowlisted subjects to NOTE."""
    reason = _allowlisted(rule, subject)
    if reason is not None:
        severity = Severity.NOTE
        message = f"{message} [allowlisted: {reason}]"
    report.add(Finding(rule, severity, subject, message, fix_hint))


# ------------------------------------------------------- rule: audit.dtype64
def check_dtype64(report: Report, target: AuditTarget, jaxpr) -> None:
    """No non-weak 64-bit avals in the traced jaxpr (TPU has no f64/i64).

    Weak-typed scalars (Python int/float literals) are exempt: they trace as
    64-bit under x64 but lower to the operand dtype — only *committed* 64-bit
    values (explicit ``astype``, default argmin index dtypes, promoting
    reductions) produce non-weak 64-bit outputs.
    """
    report.bump("audit.dtype64")
    hits: Dict[str, int] = {}
    tops = list(jaxpr.jaxpr.invars) + list(jaxpr.jaxpr.outvars)
    for var in tops:
        aval = _aval_of(var)
        if aval is None or getattr(aval, "weak_type", False):
            continue
        if str(aval.dtype) in _BAD64:
            hits[f"boundary:{aval.dtype}"] = (
                hits.get(f"boundary:{aval.dtype}", 0) + 1
            )
    for eqn in iter_eqns(jaxpr.jaxpr):
        for var in eqn.outvars:
            aval = _aval_of(var)
            if aval is None or getattr(aval, "weak_type", False):
                continue
            if str(aval.dtype) in _BAD64:
                k = f"{eqn.primitive.name}:{aval.dtype}"
                hits[k] = hits.get(k, 0) + 1
    if hits:
        detail = ", ".join(f"{k} x{n}" for k, n in sorted(hits.items()))
        _emit(
            report, "audit.dtype64", Severity.ERROR, target.name,
            f"64-bit values in traced jaxpr: {detail}",
            "use explicit 32-bit dtypes (lax.argmin(..., jnp.int32), "
            "jnp.sum(..., dtype=jnp.int32), .astype(jnp.int32)); TPUs have "
            "no 64-bit lanes and x64-off mode silently downcasts",
        )


# ------------------------------------------------ rule: audit.dtype64-source
#: modules scanned for 64-bit dtype tokens (AST attributes, so docstrings
#: and comments do not count)
DTYPE_SOURCE_MODULES = (
    "repro.kernels.segment_ops",
    "repro.kernels.ops",
    "repro.kernels.chain_apply",
    "repro.kernels.sparse_apply",
    "repro.kernels.block_diff",
    "repro.kernels.xor_delta",
    "repro.store.delta",
    "repro.store.materializer",
    "repro.core.solvers.jax_backend",
)


def check_dtype64_source(report: Report, module_name: str) -> None:
    """No ``jnp.int64`` / ``np.float64`` / … attribute tokens in the module.

    Complements the jaxpr rule: an ``astype(jnp.int64)`` in untraced host
    code (or a path the example args miss) still shows up here.
    """
    import importlib

    report.bump("audit.dtype64-source")
    mod = importlib.import_module(module_name)
    tree = ast.parse(inspect.getsource(mod))
    lines: List[int] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr in _BAD64:
            lines.append(node.lineno)
    if lines:
        _emit(
            report, "audit.dtype64-source", Severity.ERROR, module_name,
            f"{len(lines)} 64-bit dtype reference(s) at line(s) "
            f"{sorted(set(lines))}",
            "replace with 32-bit dtypes or guard capacities host-side "
            "(see kernels/segment_ops.py MAX_INT32_ELEMS)",
        )


# ---------------------------------------------------- rule: audit.host-sync
#: {module: function qualnames} forming the materializer decode hot path;
#: a device→host call *inside a loop* there serializes one sync per leaf
HOT_PATH_FUNCTIONS: Dict[str, Tuple[str, ...]] = {
    "repro.store.delta": ("apply_delta_chains",),
    "repro.store.materializer": (
        "Materializer._execute_fused",
        "Materializer._materialize_chain",
    ),
}

_SYNC_ATTRS = ("item", "block_until_ready", "device_get")
_NP_SYNC_FUNCS = ("asarray", "array")


def _qualnames(tree: ast.Module):
    """Yield (qualname, FunctionDef) for every function, class-aware."""
    def walk(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                q = f"{prefix}{child.name}"
                yield q, child
                yield from walk(child, f"{q}.")
            elif isinstance(child, ast.ClassDef):
                yield from walk(child, f"{prefix}{child.name}.")
    yield from walk(tree, "")


def _sync_calls_in_loops(fn: ast.AST) -> List[Tuple[int, str]]:
    """(line, callname) for device→host sync calls inside for/while loops."""
    hits: List[Tuple[int, str]] = []

    def scan(node, in_loop):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue  # nested function: separate scope, lint separately
            entering = in_loop or isinstance(child, (ast.For, ast.While))
            if isinstance(child, ast.Call) and in_loop:
                f = child.func
                if isinstance(f, ast.Attribute):
                    base = f.value
                    if (f.attr in _NP_SYNC_FUNCS
                            and isinstance(base, ast.Name)
                            and base.id in ("np", "numpy")):
                        hits.append((child.lineno, f"np.{f.attr}"))
                    elif f.attr in _SYNC_ATTRS:
                        hits.append((child.lineno, f".{f.attr}"))
            scan(child, entering)

    scan(fn, False)
    return hits


def check_host_sync(report: Report, module_name: str,
                    functions: Sequence[str]) -> None:
    """No per-leaf device→host syncs in the decode hot path.

    Syntactic by design: ``np.asarray`` on a host array is free, but the
    listed functions handle device-resident blocked leaves, where it blocks
    on the device stream once per call.  Batch the transfers instead —
    collect device results and fetch them with one ``jax.device_get`` after
    the loop (one transfer per request group).
    """
    import importlib

    mod = importlib.import_module(module_name)
    tree = ast.parse(inspect.getsource(mod))
    found = dict(_qualnames(tree))
    for qual in functions:
        report.bump("audit.host-sync")
        fn = found.get(qual)
        subject = f"{module_name}.{qual}"
        if fn is None:
            _emit(report, "audit.host-sync", Severity.WARNING, subject,
                  "hot-path function missing from module (rule config is "
                  "stale)",
                  "update HOT_PATH_FUNCTIONS in repro/analysis/kernel_audit.py")
            continue
        hits = _sync_calls_in_loops(fn)
        if hits:
            detail = ", ".join(f"{name}@L{line}" for line, name in hits)
            _emit(
                report, "audit.host-sync", Severity.ERROR, subject,
                f"device→host sync inside per-leaf loop: {detail}",
                "accumulate device results and fetch once with "
                "jax.device_get after the loop (one batched transfer per "
                "request group)",
            )


# -------------------------------------------------- rule: audit.shape-bucket
@dataclasses.dataclass(frozen=True)
class BucketContract:
    """A size-bucketing function and the contract its callers rely on."""

    name: str
    fn: Callable[[int], int]
    kind: str                  # "pow2" | "mult8" | "eighth"
    max_check: int = 4096


def bucket_contracts() -> List[BucketContract]:
    from ..core.solvers import jax_backend as jb
    from ..kernels import ops
    from ..store import delta

    return [
        BucketContract("store.delta._slot_bucket", delta._slot_bucket, "pow2"),
        BucketContract("kernels.ops._round_capacity", ops._round_capacity,
                       "pow2"),
        BucketContract("kernels.ops.grown_blocks", ops.grown_blocks, "eighth"),
        BucketContract("core.solvers.jax_backend._bucket_rows",
                       jb._bucket_rows, "pow2"),
        BucketContract("core.solvers.jax_backend._bucket_width",
                       jb._bucket_width, "mult8"),
    ]


def check_bucket_contract(report: Report, c: BucketContract) -> None:
    """Bucket functions must cover (f(k) >= k), quantize (pow2 / mult-of-8 /
    under an eighth of padding), be idempotent (f(f(k)) == f(k)) and
    monotone — the conditions under which jit caches are shared and
    recompiles stay O(log max_size)."""
    report.bump("audit.shape-bucket")
    problems: List[str] = []
    prev = 0
    for k in range(1, c.max_check + 1):
        b = c.fn(k)
        if b < k:
            problems.append(f"f({k})={b} < k (dropped data)")
        if c.kind == "pow2" and b & (b - 1):
            problems.append(f"f({k})={b} not a power of two")
        if c.kind == "mult8" and b % 8:
            problems.append(f"f({k})={b} not a multiple of 8")
        if c.kind == "eighth" and 8 * (b - k) >= k:
            problems.append(f"f({k})={b} pads by an eighth or more")
        if c.fn(b) != b:
            problems.append(f"f(f({k}))={c.fn(b)} != f({k})={b} (not "
                            f"idempotent)")
        if b < prev:
            problems.append(f"f({k})={b} < f({k-1})={prev} (not monotone)")
        prev = b
        if len(problems) >= 4:
            break
    if problems:
        _emit(
            report, "audit.shape-bucket", Severity.ERROR, c.name,
            f"bucket contract violated: {'; '.join(problems[:4])}",
            "jit signatures derived from this bucket will fragment the "
            "compile cache (or drop data); restore pow2/mult8 rounding",
        )


@dataclasses.dataclass(frozen=True)
class BucketProbe:
    """Sizes in the same bucket must trace to identical jit signatures."""

    name: str
    trace: Callable[[int], str]    # size -> canonical signature string
    sizes: Tuple[int, ...]         # all mapping to one bucket


def _signature(jaxpr) -> str:
    """Canonical kernel-shape signature: the in/out avals of every pallas
    call in the trace.  Top-level invars are deliberately excluded — the
    *outer* jit is always keyed on the raw input shape; the bucket contract
    is that the padded shapes reaching the kernels (and hence the Pallas
    compile cache) coincide for same-bucket sizes."""
    pcs = ";".join(
        ",".join(str(_aval_of(v)) for v in e.invars)
        + "->" + ",".join(str(_aval_of(v)) for v in e.outvars)
        for e in _find_pallas_calls(jaxpr.jaxpr)
    )
    return f"pallas[{pcs}]"


def bucket_probes() -> List[BucketProbe]:
    from ..kernels import ops, segment_ops
    from ..store import delta

    def probe_min_argmin(n: int) -> str:
        return _signature(jax.make_jaxpr(
            lambda x: segment_ops.min_argmin_1d(x)
        )(S((n,), jnp.float32)))

    def probe_compact(n_changed: int) -> str:
        cap = ops._round_capacity(n_changed)
        return _signature(jax.make_jaxpr(
            functools.partial(ops._compact, capacity=cap)
        )(S((64, 1), jnp.int32), S((64, 8, 128), jnp.int32)))

    def probe_chain(total_slots: int) -> str:
        cap = delta._slot_bucket(total_slots)
        return _signature(jax.make_jaxpr(
            lambda b, p, i: ops.chain_apply(b, p, i)
        )(S((8, 8, 128), jnp.int32), S((cap, 8, 128), jnp.int32),
          S((cap,), jnp.int32)))

    return [
        BucketProbe("kernels.segment_ops.min_argmin_1d/pad_to_rows",
                    probe_min_argmin, (27, 100, 128)),
        BucketProbe("kernels.ops.compact/_round_capacity",
                    probe_compact, (5, 6, 8)),
        BucketProbe("store.delta.apply_delta_chains/_slot_bucket",
                    probe_chain, (3, 5, 8)),
    ]


def check_bucket_probe(report: Report, p: BucketProbe) -> None:
    report.bump("audit.shape-bucket")
    sigs = {}
    with jax.enable_x64(True):
        for n in p.sizes:
            sigs.setdefault(p.trace(n), []).append(n)
    if len(sigs) > 1:
        detail = "; ".join(f"sizes {v} -> {k[:80]}" for k, v in sigs.items())
        _emit(
            report, "audit.shape-bucket", Severity.ERROR, p.name,
            f"same-bucket sizes trace to different jit signatures: {detail}",
            "route the size through the bucket function before shaping "
            "device arrays so the jit cache is shared",
        )


# ------------------------------------------------------ rule: audit.io-alias
def check_io_alias(report: Report, target: AuditTarget, jaxpr) -> None:
    """Pallas calls writing a large output that matches an input's
    shape+dtype must alias it (``input_output_aliases``): without donation
    the dispatch allocates a second full-size HBM buffer and pays an extra
    copy — on the checkout hot path that is pure waste."""
    report.bump("audit.io-alias")
    for ei, eqn in enumerate(_find_pallas_calls(jaxpr.jaxpr)):
        aliases = tuple(eqn.params.get("input_output_aliases") or ())
        aliased_outs = {pair[1] for pair in aliases}
        in_avals = [_aval_of(v) for v in eqn.invars]
        for oi, var in enumerate(eqn.outvars):
            aval = _aval_of(var)
            if aval is None:
                continue
            nbytes = int(np.prod(aval.shape)) * aval.dtype.itemsize
            if nbytes < ALIAS_BYTES_THRESHOLD:
                continue
            match = any(
                ia is not None
                and ia.shape == aval.shape and ia.dtype == aval.dtype
                for ia in in_avals
            )
            if match and oi not in aliased_outs:
                _emit(
                    report, "audit.io-alias", Severity.WARNING, target.name,
                    f"pallas_call #{ei} output {oi} "
                    f"({aval.dtype}{list(aval.shape)}, {nbytes >> 20} MiB) "
                    f"matches an input but is not aliased",
                    "pass input_output_aliases={in_idx: out_idx} to "
                    "pl.pallas_call so the buffer is updated in place",
                )


# ------------------------------------------------------------------- driver
def run_audit() -> Report:
    """Run the full rule catalog; returns a :class:`Report`.

    Trace failures are findings too (``audit.trace``): a kernel that stops
    tracing abstractly would silently drop out of every jaxpr rule.
    """
    report = Report(tool="audit")
    for target in audit_targets():
        report.bump("audit.trace")
        try:
            jaxpr = trace_target(target)
        except Exception as e:  # pragma: no cover - defensive
            report.add(Finding(
                "audit.trace", Severity.ERROR, target.name,
                f"abstract trace failed: {type(e).__name__}: {e}",
                "fix the target or its registry entry in "
                "repro/analysis/kernel_audit.py",
            ))
            continue
        check_dtype64(report, target, jaxpr)
        check_io_alias(report, target, jaxpr)
    for module in DTYPE_SOURCE_MODULES:
        check_dtype64_source(report, module)
    for module, functions in HOT_PATH_FUNCTIONS.items():
        check_host_sync(report, module, functions)
    for contract in bucket_contracts():
        check_bucket_contract(report, contract)
    for probe in bucket_probes():
        check_bucket_probe(report, probe)
    return report
