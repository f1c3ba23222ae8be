"""Compile the fused apply kernel for a described TPU v5e (no chip needed).

Interpret mode cannot see what Mosaic refuses — block shapes off the
(8, 128) tiling, primitives it does not lower, reshapes it cannot relayout,
more VMEM than the kernel may use.  These tests hand the kernel's
``pallas_call`` (``kernels.flix_apply.apply_call``) the shapes of real
deployments and compile it with the TPU compiler for one chip of a v5e
topology: single- and double-buffered, at the ``KVPageIndex`` geometry
(node_size 16, 8 nodes per bucket) and the default build geometry (32, 16),
on a grid of several windows and bucket blocks, with the default tile and
with each corner of the autotuner's tile grid (smallest and largest
``block_q`` × ``block_b``, the largest being the biggest VMEM footprint).
One more compiles the whole fused executor, wrapper and kernel, at the
benchmark's one-chip store (2^23 keys, 4 096-op batches) and holds its
memory to what a v5e can load.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler library.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.autotune import (
    CANDIDATE_BLOCK_B,
    CANDIDATE_BLOCK_Q,
    VMEM_BUDGET_BYTES,
    vmem_bytes,
)
from repro.core.config import ExecConfig
from repro.core.state import FliXState
from repro.kernels.flix_apply import N_FENCE_ROWS, apply_call, flix_apply_pallas

GEOMETRIES = pytest.mark.parametrize(
    "ns,npb", [(16, 8), (32, 16)], ids=["kv_index_16x8", "default_32x16"]
)
PIPELINES = pytest.mark.parametrize("pipeline", [False, True], ids=["single", "double"])
CORNERS = [
    (bq, bb)
    for bq in (min(CANDIDATE_BLOCK_Q), max(CANDIDATE_BLOCK_Q))
    for bb in (min(CANDIDATE_BLOCK_B), max(CANDIDATE_BLOCK_B))
]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _operands(one_chip, *, nb_blocks, block_b, npb, ns, n_windows, block_q, mr=128):
    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    S = npb * ns
    return (
        sds(n_windows),
        sds(n_windows),
        sds(n_windows, 1, block_q),
        sds(n_windows, 1, block_q),
        sds(nb_blocks, block_b, S),
        sds(nb_blocks, block_b, S),
        sds(nb_blocks, block_b, npb),
        sds(nb_blocks, block_b, S),
        sds(nb_blocks, block_b, S),
        sds(nb_blocks, block_b, S),
        sds(nb_blocks, block_b, N_FENCE_ROWS),
        sds(1, mr),
    )


def _compile(one_chip, *, ns, npb, pipeline, block_q, block_b):
    ops = _operands(
        one_chip, nb_blocks=8, block_b=block_b, npb=npb, ns=ns, n_windows=4,
        block_q=block_q,
    )
    fn = functools.partial(apply_call, ns=ns, interpret=False, pipeline=pipeline)
    compiled = jax.jit(fn).lower(*ops).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2**30


@PIPELINES
@GEOMETRIES
def test_fused_kernel_compiles_for_v5e(one_chip, ns, npb, pipeline):
    _compile(one_chip, ns=ns, npb=npb, pipeline=pipeline, block_q=128, block_b=2)


@PIPELINES
@GEOMETRIES
@pytest.mark.parametrize(
    "block_q,block_b", CORNERS, ids=[f"q{q}_b{b}" for q, b in CORNERS]
)
def test_tile_grid_corners_compile_for_v5e(
    one_chip, block_q, block_b, ns, npb, pipeline
):
    """The autotuner only picks tiles its VMEM model calls feasible; the
    corners of its grid must then also compile under the kernel's limit."""
    assert vmem_bytes(block_q, block_b, node_size=ns, nodes_per_bucket=npb) <= (
        VMEM_BUDGET_BYTES
    )
    _compile(
        one_chip, ns=ns, npb=npb, pipeline=pipeline, block_q=block_q, block_b=block_b
    )


def test_fused_executor_memory_fits_v5e(one_chip):
    """The fused executor at 2^23 keys (2^19 buckets of 16 × 32 slots) and
    4 096 ops: its arguments, outputs and temporaries fit one v5e's HBM,
    and the temporaries stay near the 6.26 GiB this compile gives (a whole
    1 GiB plane kept alive, or the stripes' layout copies left to fuse into
    each reader, adds 1 GiB or more and the chip refuses to load it)."""
    nb, npb, ns, n = 2**19, 16, 32, 4096

    def sds(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = FliXState(
        keys=sds(nb, npb, ns),
        vals=sds(nb, npb, ns),
        node_count=sds(nb, npb),
        node_max=sds(nb, npb),
        num_nodes=sds(nb),
        mkba=sds(nb),
        needs_restructure=sds(dtype=jnp.bool_),
    )
    compiled = flix_apply_pallas.lower(
        state, sds(n), sds(n), sds(n),
        max_results=ExecConfig().max_results, interpret=False, pipeline=True,
    ).compile()
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert total < 15.75 * 2**30
    assert mem.temp_size_in_bytes < 7 * 2**30
