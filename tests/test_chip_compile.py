"""Ahead-of-time compiles of the chip path for a described TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a topology that is described, not present, and refuses what the chip
would refuse (unaligned slices, primitives Pallas cannot lower, programs
that do not fit). Each test compiles one kernel of the main path at the
engine's per-device geometry: 16,384 slots x 1,024 voter lanes.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and the test workers all
import this file (see the on-chip-measurement guide, section 2).
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

SLOTS, LANES = 16_384, 1_024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs in /tmp
    try:
        described = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    # A compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep the cache out of it.
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield described
    jax.config.update("jax_enable_compilation_cache", cache)


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory placed on one described chip."""
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)


@pytest.fixture(scope="module")
def pool(spec):
    """The ten pool arrays in ProposalPool._init_device_arrays order."""
    i32, b = jnp.int32, jnp.bool_
    return [
        spec((SLOTS,), i32), spec((SLOTS,), i32), spec((SLOTS,), i32),
        spec((SLOTS, LANES), b), spec((SLOTS, LANES), b),
        spec((SLOTS,), i32), spec((SLOTS,), i32), spec((SLOTS,), i32),
        spec((SLOTS,), b), spec((SLOTS,), b),
    ]


def _compile(fn, *args):
    return fn.lower(*args).compile()


def test_scan_ingest_compiles(spec, pool):
    from hashgraph_tpu.ops.ingest import grid_dtype, ingest_kernel

    rows, depth = 1_024, 8
    _compile(
        ingest_kernel, *pool, spec((rows,), jnp.int32),
        spec((rows, depth), grid_dtype(LANES)),
    )


@pytest.mark.parametrize("laneless", [False, True])
def test_fresh_ingest_compiles(spec, pool, laneless):
    from hashgraph_tpu.ops.ingest import (
        fresh_ingest_kernel,
        fresh_ingest_laneless_kernel,
        grid_dtype,
    )

    kernel = fresh_ingest_laneless_kernel if laneless else fresh_ingest_kernel
    dtype = jnp.uint8 if laneless else grid_dtype(LANES)
    _compile(
        kernel, *pool, spec((1_024,), jnp.int32), spec((1_024, 1_024), dtype)
    )


def test_pallas_ingest_rows_compiles_to_a_kernel(spec):
    """The Pallas vote scan at a full 683-vote P2P quorum depth: compiled,
    not interpreted, so the program carries the TPU custom call."""
    from hashgraph_tpu.ops.pallas_ingest import SCALAR_COLS, pallas_ingest_rows

    rows, depth = 1_024, 683
    i32 = jnp.int32
    compiled = _compile(
        pallas_ingest_rows,
        spec((rows, SCALAR_COLS), i32), spec((rows, LANES), i32),
        spec((rows, LANES), i32), spec((rows, depth), i32),
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_timeout_sweep_compiles(spec, pool):
    from hashgraph_tpu.ops.decide import timeout_kernel

    state, yes, tot, _m, _v, n, req, _c, _g, live = pool
    _compile(
        timeout_kernel, state, yes, tot, n, req, live,
        spec((SLOTS,), jnp.int32),
    )


def test_decide_kernel_compiles(spec):
    from hashgraph_tpu.ops.decide import decide_kernel

    i32 = spec((SLOTS,), jnp.int32)
    b = spec((SLOTS,), jnp.bool_)
    _compile(jax.jit(decide_kernel), i32, i32, i32, i32, b, b)


@pytest.mark.parametrize("batched", [False, True])
def test_chain_kernel_compiles(spec, batched):
    from hashgraph_tpu.ops.chain import HASH_WORDS, chain_kernel, chain_kernel_batch

    votes = 1_024  # one proposal's padded chain at 1,024 voters
    lead = (16,) if batched else ()
    i32 = jnp.int32
    h = spec(lead + (votes, HASH_WORDS + 1), i32)
    _compile(
        chain_kernel_batch if batched else chain_kernel,
        h, h, h, spec(lead + (votes,), i32), spec(lead + (votes, 2), i32),
        spec(lead + (votes,), jnp.bool_),
    )


def test_sharded_pool_compiles_on_four_chips(topo):
    """ShardedPool's kernels on a 4-chip mesh: the per-shard ingest
    partitions, and the global state histogram is a cross-chip all-reduce."""
    from jax.sharding import Mesh

    from hashgraph_tpu.parallel.mesh import PROPOSAL_AXIS
    from hashgraph_tpu.parallel.sharded import ShardedPool

    kernels = object.__new__(ShardedPool)  # kernels only: no device arrays
    kernels.mesh = Mesh(np.asarray(topo.devices[:4]), (PROPOSAL_AXIS,))
    kernels.axis = PROPOSAL_AXIS
    kernels._build_sharded_kernels()
    total = 4 * SLOTS
    v1 = NamedSharding(kernels.mesh, PartitionSpec(PROPOSAL_AXIS))
    v2 = NamedSharding(kernels.mesh, PartitionSpec(PROPOSAL_AXIS, None))

    def s(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    i32, b = jnp.int32, jnp.bool_
    state = s((total,), i32, v1)
    pool = [
        state, s((total,), i32, v1), s((total,), i32, v1),
        s((total, LANES), b, v2), s((total, LANES), b, v2),
        s((total,), i32, v1), s((total,), i32, v1), s((total,), i32, v1),
        s((total,), b, v1), s((total,), b, v1),
    ]
    rows = 4 * 1_024  # routed batch: 1,024 rows per chip
    ingest = _compile(
        kernels._sharded_ingest, *pool, s((rows,), i32, v1),
        s((rows, 8), jnp.uint16, v2),
    )
    assert "all-reduce" not in ingest.as_text()  # shards apply alone
    counts = _compile(kernels._sharded_counts, state).as_text()
    assert "all-reduce" in counts


def test_ed25519_decompress_compiles(spec):
    """Device signature verification's first stage, at the lane bucket a
    512-vote batch uses (identities + R points)."""
    from hashgraph_tpu.crypto_device import curve

    _compile(jax.jit(curve.decompress), spec((1_024, 32), jnp.uint8))
