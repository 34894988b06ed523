"""EP (shard_map all_to_all) MoE vs the pjit dispatch baseline.

Numerical equivalence needs a real multi-device mesh, so the check runs in a
subprocess with forced host devices (the main pytest process has already
locked jax to 1 device).
"""

import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import ARCHS
    from repro.distributed.sharding import logical_sharding
    from repro.models import moe as moe_lib
    from repro.models import moe_ep

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)

    cfg = dataclasses.replace(
        ARCHS["phi3.5-moe-42b-a6.6b"].reduced(),
        n_experts=8, top_k=2, expert_d_ff=32, d_model=64,
        capacity_factor=4.0,  # drop-free so both impls agree exactly
        n_shared_experts=1,
    )
    rng = np.random.RandomState(0)
    E, D, F = cfg.n_experts, cfg.d_model, cfg.expert_d_ff
    p = {
        "router": jnp.asarray(rng.randn(D, E), jnp.float32) * 0.1,
        "w_gate": jnp.asarray(rng.randn(E, D, F), jnp.bfloat16) * 0.1,
        "w_up": jnp.asarray(rng.randn(E, D, F), jnp.bfloat16) * 0.1,
        "w_down": jnp.asarray(rng.randn(E, F, D), jnp.bfloat16) * 0.1,
        "shared_w_gate": jnp.asarray(rng.randn(D, F), jnp.bfloat16) * 0.1,
        "shared_w_up": jnp.asarray(rng.randn(D, F), jnp.bfloat16) * 0.1,
        "shared_w_down": jnp.asarray(rng.randn(F, D), jnp.bfloat16) * 0.1,
    }
    B, S = 4, 16
    x = jnp.asarray(rng.randn(B, S, D), jnp.bfloat16) * 0.5

    base = jax.jit(lambda p, x: moe_lib.moe_block(cfg, p, x))(p, x)

    with logical_sharding(mesh):
        ep_fn = jax.jit(lambda p, x: moe_ep.moe_block_ep(cfg, p, x))
        got = ep_fn(p, x)

    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(base, np.float32),
        rtol=2e-2, atol=2e-2,
    )

    # gradients must also agree (the shard_map AD path)
    def loss_ep(p, x):
        with logical_sharding(mesh):
            return jnp.sum(moe_ep.moe_block_ep(cfg, p, x).astype(jnp.float32) ** 2)

    def loss_base(p, x):
        return jnp.sum(moe_lib.moe_block(cfg, p, x).astype(jnp.float32) ** 2)

    g_ep = jax.grad(loss_ep)(p, x)
    g_b = jax.grad(loss_base)(p, x)
    for k in ("w_gate", "w_down", "router", "shared_w_down"):
        np.testing.assert_allclose(
            np.asarray(g_ep[k], np.float32), np.asarray(g_b[k], np.float32),
            rtol=5e-2, atol=5e-2,
        )
    print("EP==dispatch OK")
""")


def test_ep_matches_dispatch_on_8dev_mesh():
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=600,
        # the child runs on host devices like the tests themselves: keep the
        # parent's environment, JAX_PLATFORMS=cpu included, so it never
        # reaches for an accelerator
        env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-3000:]}"
    assert "EP==dispatch OK" in out.stdout
