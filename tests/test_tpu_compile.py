"""The served path's kernels compiled for a described TPU v5e, no chip.

The TPU compiler ships with JAX and compiles for a chip that is described
but not attached.  It refuses what interpret mode cannot see: a block not
aligned to the (8, 128) tiling, a kernel over its fast-memory budget, a
program too large for the chip, a Mosaic kernel left to the automatic
partitioner.  Every test here compiles at deployment size (5 replicas,
2^20 keys) and asserts the kernel is in the program (``tpu_custom_call``).

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and every pytest
worker imports every test file.  Keep these tests in this one file.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (
    Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding,
)

from repro.core import proposer_vector, vector
from repro.kernels.paxos_apply.kernel import paxos_apply
from repro.kernels.paxos_propose.ops import issuer_step
from repro.serve.paxos import cluster_engine

N_MACHINES = 5
N_KEYS = 1 << 20
SESSIONS = 8
CHIP_HBM = 16e9                      # one v5e chip's HBM, bytes


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        # else libtpu writes its logs under /tmp
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:          # no libtpu, or it is held elsewhere
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one: keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_paxos_apply_compiles_at_deployment_lanes(one_chip):
    n = N_MACHINES * N_KEYS
    lane = _i32((n,), one_chip)
    compiled = paxos_apply.lower(
        vector.KVTable(*[lane] * len(vector.KVTable._fields)),
        vector.MsgBatch(*[lane] * len(vector.MsgBatch._fields)),
        lane, block_rows=32, interpret=False).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("lanes", [N_MACHINES * SESSIONS, 1024])
def test_paxos_propose_compiles_at_default_block_rows(one_chip, lanes):
    """The served issuer shape, and more than 128 lanes: a (1, 128) block
    is refused there, so the default block must be tile-legal."""
    lane = _i32((lanes,), one_chip)
    step = jax.jit(lambda t, rep: issuer_step(
        t, rep, n_machines=N_MACHINES, majority=3, commit_need=2,
        log_too_high_threshold=4, interpret=False))
    compiled = step.lower(
        proposer_vector.ProposerTable(
            *[lane] * len(proposer_vector.ProposerTable._fields)),
        proposer_vector.IssuerReplyBatch(
            *[lane] * len(proposer_vector.IssuerReplyBatch._fields)),
    ).compile()
    assert _has_kernel(compiled)


def test_fused_receiver_step_compiles_and_fits_one_chip(one_chip):
    m, k = N_MACHINES, N_KEYS
    compiled = cluster_engine._fused_receiver_step.lower(
        _i32((cluster_engine.N_KV, m, k), one_chip),
        _i32((cluster_engine.N_MSGREG, m, k), one_chip),
        use_kernel=True, block_rows=32, interpret=False).compile()
    assert _has_kernel(compiled)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < CHIP_HBM, total


def test_compact_receiver_wave_compiles_and_fits_one_chip(one_chip):
    """The compact wire at deployment size: the step takes a 640-entry
    staged batch and a patch array, expands them on the chip and keeps the
    kernel; the gather of the touched lanes compiles apart."""
    m, k, w = N_MACHINES, N_KEYS, N_MACHINES * 128
    kv = _i32((cluster_engine.N_KV, m, k), one_chip)
    entries = _i32((1 + cluster_engine.N_MSGREG, w), one_chip)
    compiled = cluster_engine._fused_receiver_step.lower(
        kv, entries, _i32((1 + cluster_engine.N_KV, w), one_chip),
        use_kernel=True, block_rows=32, interpret=False).compile()
    assert _has_kernel(compiled)
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < CHIP_HBM
    gather = cluster_engine._touched_lanes.lower(
        kv, _i32((cluster_engine.N_REP, m, k), one_chip),
        jax.ShapeDtypeStruct((m, k), jnp.bool_, sharding=one_chip),
        entries).compile()
    assert gather.out_info.shape == (
        cluster_engine.N_KV + cluster_engine.N_REP + 1, w)


def test_sharded_fused_receiver_step_compiles_on_four_chips(topo):
    """The KV plane split over a 2x2 mesh: each chip steps its own lane
    block with no collective, holding a quarter of the state."""
    mesh = Mesh(np.array(topo.devices), ("shard",))
    lanes = NamedSharding(mesh, P(None, None, "shard"))
    m, k = N_MACHINES, N_KEYS
    compiled = cluster_engine._fused_receiver_step.lower(
        _i32((cluster_engine.N_KV, m, k), lanes),
        _i32((cluster_engine.N_MSGREG, m, k), lanes),
        use_kernel=True, block_rows=32, shard_lanes=k // 4,
        out_sharding=lanes, interpret=False).compile()
    assert _has_kernel(compiled)
    text = compiled.as_text()
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute", "reduce-scatter"):
        assert collective not in text, collective
    whole = (cluster_engine.N_KV + cluster_engine.N_MSGREG) * m * k * 4
    assert compiled.memory_analysis().argument_size_in_bytes < whole / 2
