"""Main-path kernels compiled for a described TPU v5e chip at real sizes.

Nothing runs: the TPU compiler, which is installed here, compiles for a chip
that is described and not attached, and refuses what the chip would refuse
(tiling, VMEM, layouts).  The sizes are those of ``chip_smoke.py``'s
MiniCPM-2B-width checkpoint leaves, and the padded block counts of the
benchmark's YCSB-shaped table columns.  ``interpret=False`` is passed
explicitly: the process's default backend is still the CPU.  The names the
benchmark's trace reduction and span readers key on are pinned here too.
"""

import functools
import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import obs
from repro.kernels import ops
from repro.kernels.block_diff import changed_block_mask
from repro.kernels.chain_apply import (
    chain_delta_apply,
    chain_delta_apply_batched,
)
from repro.kernels.ref import BLOCK_BYTES
from repro.store.delta import apply_delta_chain, encode_delta
from repro.store.objectstore import Codec

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
# a 200,000-record YCSB table growing by 50 rows a save: a 100 B field
# column (4,883 blocks) and the int64 key column (391), padded as grown
# leaves, with the compacted capacity of one 1,000-op batch's changed blocks
# (~90 a field column, ~2 the key column: under each capacity's floor)
TABLE_FIELD = ops.grown_blocks(ops.num_blocks_of(200_000 * 100))
TABLE_KEY = ops.grown_blocks(ops.num_blocks_of(200_000 * 8))
TABLE_SLOTS = {nb: ops.compact_capacity(1, nb) for nb in (TABLE_FIELD, TABLE_KEY)}
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


@pytest.mark.parametrize("nb", [TABLE_FIELD, TABLE_KEY])
def test_grown_table_column_kernels(one_chip, nb):
    assert (TABLE_FIELD, TABLE_KEY) == (5120, 416)
    assert TABLE_SLOTS == {5120: 256, 416: 16}
    hlo = _compile(
        functools.partial(changed_block_mask, interpret=False),
        ((nb, 8, 128), jnp.int32), ((nb, 8, 128), jnp.int32),
        sharding=one_chip,
    )
    assert "tpu_custom_call" in hlo
    _compile(
        functools.partial(ops._compact, capacity=TABLE_SLOTS[nb]),
        ((nb, 1), jnp.int32), ((nb, 8, 128), jnp.int32),
        sharding=one_chip,
    )


def test_diff_counter_names():
    """The span readers key on these counters: ``changed_blocks``,
    ``total_blocks``, ``grown_leaves`` and ``full_leaves`` on
    ``delta.encode_delta`` (``commit_changed_blocks_pct``,
    ``commit_grown_leaves``, ``commit_full_leaves``, ``compact_roofline``),
    and ``grown_leaves`` on ``delta.apply_chains``."""
    rng = np.random.default_rng(9)
    v0 = {"a": rng.integers(0, 256, (60, 100), dtype=np.uint8),
          "b": np.arange(9, dtype=np.int64), "c": np.zeros(4, np.float32)}
    v1 = {"a": np.concatenate([v0["a"], v0["a"][:5] ^ 1]),
          "b": np.arange(11, dtype=np.int64), "c": np.zeros(5, np.int32)}
    tracer = obs.Tracer(enabled=True)
    old = obs.set_tracer(tracer)
    try:
        payload, stats = encode_delta(v0, v1)
        apply_delta_chain(v0, [payload])
    finally:
        obs.set_tracer(old)
    (enc,) = [s for s in tracer.spans() if s.name == "delta.encode_delta"]
    (app,) = [s for s in tracer.spans() if s.name == "delta.apply_chains"]
    assert (enc.attrs["grown_leaves"], enc.attrs["full_leaves"]) == (2, 1)
    assert enc.attrs["total_blocks"] == ops.num_blocks_of(6500) + 1
    assert enc.attrs["changed_blocks"] == stats["changed_blocks"] > 0
    assert app.attrs["grown_leaves"] == 2


def test_compress_counter_names(monkeypatch):
    """``commit_compress_mt_pct`` keys on ``workers`` (0 on the one-threaded
    path) and ``in_bytes`` on ``objects.compress``."""
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: {0, 1})
    codec = Codec()
    small, large = bytes(1000), bytes((8 << 20) + 1)  # one zstd job, two
    tracer = obs.Tracer(enabled=True)
    old = obs.set_tracer(tracer)
    try:
        codec.compress(small)
        codec.compress(large)
    finally:
        obs.set_tracer(old)
    got = [s.attrs for s in tracer.spans() if s.name == "objects.compress"]
    mt = 2 if codec.backend == "zstd" else 0
    assert got == [{"workers": 0, "in_bytes": 1000},
                   {"workers": mt, "in_bytes": len(large)}]
