"""System-layer benchmarks: delta kernels, store throughput, restore latency.

Kernel numbers on this container run under the Pallas *interpreter* (CPU) —
they validate plumbing and give relative shape behaviour; absolute GB/s on
TPU comes from the BlockSpec analysis in EXPERIMENTS.md §Roofline.
"""

from __future__ import annotations

import tempfile
import time
from typing import List

import jax.numpy as jnp
import numpy as np

from repro.core import OptimizeSpec, optimize
from repro.kernels import ops
from repro.store import VersionStore

from .common import Row, timed


def kernel_throughput() -> List[Row]:
    rows: List[Row] = []
    rng = np.random.RandomState(0)
    for nb in (256, 1024, 4096):
        nbytes = nb * 4096
        a = jnp.asarray(
            rng.randint(-(2**31), 2**31, size=(nb, 8, 128), dtype=np.int64
                        ).astype(np.int32))
        b = a.at[jnp.arange(0, nb, 7)].add(3)

        out, us = timed(lambda: ops.xor_encode(a, b).block_until_ready(), repeats=3)
        rows.append(Row(f"kernel/xor/{nbytes>>20}MiB", us,
                        f"GBps_interpret={3*nbytes/us/1e3:.3f}"))
        out, us = timed(
            lambda: __import__("repro.kernels.block_diff", fromlist=["x"]).changed_block_mask(a, b).block_until_ready(),
            repeats=3)
        rows.append(Row(f"kernel/mask/{nbytes>>20}MiB", us,
                        f"GBps_interpret={2*nbytes/us/1e3:.3f}"))
        mask, n = ops.count_changed(a, b)
        idx, blocks = ops.compact(mask, b, n)
        out, us = timed(lambda: ops.sparse_apply(a, blocks, idx).block_until_ready(),
                        repeats=3)
        rows.append(Row(f"kernel/sparse_apply/{nbytes>>20}MiB", us,
                        f"changed={n};GBps_interpret={2*n*4096/us/1e3:.3f}"))
    return rows


def store_roundtrip() -> List[Row]:
    rows: List[Row] = []
    rng = np.random.RandomState(1)
    payload = {"w": rng.randn(512, 512).astype(np.float32),
               "b": rng.randn(4096).astype(np.float32)}
    with tempfile.TemporaryDirectory() as d:
        store = VersionStore(d)
        _, us0 = timed(lambda: store.commit(payload, message="base"))
        vids = [1]
        def one_commit():
            payload["w"][rng.randint(0, 480):][:16] += 1.0
            vids.append(store.commit(payload, parents=[vids[-1]]))
        _, us_delta = timed(one_commit, repeats=5)
        # cold: fresh store handle with the FlatTree cache disabled
        cold = VersionStore(d, cache_budget_bytes=0)
        _, us_co = timed(lambda: cold.checkout(vids[-1]), repeats=3)
        # warm: the shared materialization cache serves the hot version
        store.checkout(vids[-1])  # populate
        _, us_warm = timed(lambda: store.checkout(vids[-1]), repeats=3)
        _, us_batch = timed(lambda: cold.checkout_many(vids), repeats=3)
        mb = sum(a.nbytes for a in payload.values()) / 1e6
        rows.append(Row("store/commit_full", us0, f"payload_mb={mb:.1f}"))
        rows.append(Row("store/commit_delta", us_delta,
                        f"stored_kb={store.log()[-1].stored_bytes/1e3:.1f}"))
        rows.append(Row("store/checkout_chain6_cold", us_co,
                        f"modelled_phi_ms={store.recreation_cost(vids[-1])*1e3:.2f}"))
        rows.append(Row("store/checkout_chain6_warm", us_warm,
                        f"speedup={us_co/max(us_warm,1e-9):.0f}x"))
        rows.append(Row("store/checkout_many_all6", us_batch,
                        "shared-prefix plan, uncached"))
    return rows


def restore_latency_vs_theta() -> List[Row]:
    """Problem 6 in vivo: tighter θ buys faster worst-case restore with more
    storage — measured on real checkpoint chains, wall-clock + modelled."""
    rows: List[Row] = []
    rng = np.random.RandomState(2)
    payload = {"w": rng.randn(384, 384).astype(np.float32)}
    with tempfile.TemporaryDirectory() as d:
        store = VersionStore(d)
        vid = store.commit(payload, message="v1")
        for i in range(11):
            payload = {"w": payload["w"].copy()}
            payload["w"][(i * 31) % 350:][:8] += 0.5
            vid = store.commit(payload, parents=[vid])
        g, _ = store.build_cost_graph()
        spt = optimize(g, OptimizeSpec.problem(2)).solution
        base = spt.max_recreation()
        for mult in (1.05, 2.0, 8.0):
            store.repack(OptimizeSpec.problem(6, theta=base * mult))
            worst_vid = max(store.versions, key=store.recreation_cost)
            t0 = time.monotonic()
            store.checkout(worst_vid)
            wall = (time.monotonic() - t0) * 1e6
            rows.append(Row(
                f"restore/theta{mult:g}x", wall,
                f"storage_mb={store.storage_bytes()/1e6:.2f};"
                f"modelled_worst_ms={store.recreation_cost(worst_vid)*1e3:.2f};"
                f"chain_len={max(_chain_len(store, v) for v in store.versions)}",
            ))
    return rows


def _chain_len(store: VersionStore, vid: int) -> int:
    n, v = 0, vid
    while v is not None:
        v = store.versions[v].stored_base
        n += 1
    return n
