"""Main-path kernels compiled for a described TPU v5e chip at real sizes.

Nothing runs: the TPU compiler, which is installed here, compiles for a chip
that is described and not attached, and refuses what the chip would refuse
(tiling, VMEM, layouts).  The sizes are those of ``chip_smoke.py``'s
MiniCPM-2B-width checkpoint leaves.  ``interpret=False`` is passed
explicitly: the process's default backend is still the CPU.
"""

import functools
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.block_diff import changed_block_mask
from repro.kernels.chain_apply import (
    chain_delta_apply,
    chain_delta_apply_batched,
)
from repro.kernels.ref import BLOCK_BYTES

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import chip_smoke  # noqa: E402

SPEC = chip_smoke.leaf_specs(
    chip_smoke.ARCHS[chip_smoke.ARCH], chip_smoke.SMOKE_LAYERS,
    chip_smoke.DATA_ROWS,
)


def _blocks(key):
    shape, dtype = SPEC[key]
    n = dtype.itemsize
    for d in shape:
        n *= d
    return -(-n // BLOCK_BYTES)


EMBED = _blocks("params/embed")      # 138098 blocks, 566 MB
WQ = _blocks("params/layers/0/attn/wq")
# slots of one fine-tune step on the embedding (~2% of its blocks), bucketed
EMBED_SLOTS = 4096
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES
    return compiled.as_text()


@pytest.mark.parametrize("nb", [EMBED, WQ, 1])
def test_changed_block_mask(one_chip, nb):
    hlo = _compile(
        functools.partial(changed_block_mask, interpret=False),
        ((nb, 8, 128), jnp.int32), ((nb, 8, 128), jnp.int32),
        sharding=one_chip,
    )
    assert "tpu_custom_call" in hlo


def test_changed_block_mask_names(one_chip):
    """The trace reduction keys on these names (``block_diff_roofline``):
    the program ``jit_changed_block_mask`` and its ``%changed_block_mask``
    custom call, whose first operand's shape gives the block count."""
    x = jax.ShapeDtypeStruct((WQ, 8, 128), jnp.int32, sharding=one_chip)
    hlo = changed_block_mask.lower(x, x, interpret=False).compile().as_text()
    assert hlo.startswith("HloModule jit_changed_block_mask,")
    call = re.search(r"%changed_block_mask[.\d]* = \S+ custom-call\((.*)$", hlo, re.M)
    assert call and 'custom_call_target="tpu_custom_call"' in call.group(1)
    assert f"s32[{WQ},8,128]" in call.group(1)


def test_compact_name(one_chip):
    """``compact_roofline`` reads the device time of ``jit__compact``."""
    hlo = ops._compact.lower(
        jax.ShapeDtypeStruct((EMBED, 1), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((EMBED, 8, 128), jnp.int32, sharding=one_chip),
        capacity=EMBED_SLOTS,
    ).compile().as_text()
    assert hlo.startswith("HloModule jit__compact,")


def test_compact(one_chip):
    _compile(
        functools.partial(ops._compact, capacity=EMBED_SLOTS),
        ((EMBED, 1), jnp.int32), ((EMBED, 8, 128), jnp.int32),
        sharding=one_chip,
    )


def test_chain_delta_apply(one_chip):
    hlo = _compile(
        functools.partial(chain_delta_apply, interpret=False),
        ((EMBED, 8, 128), jnp.int32), ((EMBED_SLOTS, 8, 128), jnp.int32),
        ((EMBED_SLOTS,), jnp.int32),
        sharding=one_chip,
    )
    assert "tpu_custom_call" in hlo


def test_chain_delta_apply_batched(one_chip):
    leaves, slots = chip_smoke.SMOKE_LAYERS, 64
    hlo = _compile(
        functools.partial(chain_delta_apply_batched, interpret=False),
        ((leaves, WQ, 8, 128), jnp.int32),
        ((leaves, slots, 8, 128), jnp.int32), ((leaves, slots), jnp.int32),
        sharding=one_chip,
    )
    assert "tpu_custom_call" in hlo
