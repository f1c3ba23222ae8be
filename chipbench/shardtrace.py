"""The sharded executor's program in a profiler trace.

``core/distributed`` names its jitted ``shard_map`` programs
``jit_flix_shard_replicated`` and ``jit_flix_shard_a2a`` on each device's
``XLA Modules`` line, and its phases ``flix.shard.mask``, ``.apply``,
``.combine``, ``.range_counts`` and ``.exchange`` (``jax.named_scope``),
with the inner executor's ``flix.fused.*`` or ``flix.reference.*`` scopes
inside ``.apply``.  Every chip runs the same program; a batch waits for the
slowest, so the readers take the chip where a quantity sums largest.

``program_text`` compiles the cell's sharded program again from its shapes
alone, afresh, for ``progtrace.op_scopes`` (the reason it is fresh is
``progtrace._compiled_text``'s).
"""

from __future__ import annotations

import functools
import math

from chipbench import devtrace, progtrace

PROGRAM = r"^jit_flix_shard_(?:replicated|a2a)\("
KERNEL = r"^%flix_apply_pallas[.0-9]* = .*tpu_custom_call"  # as the trace names it


def largest_chip_ns(run, line: str, pattern: str) -> int:
    """The largest, over the chips, of the summed durations of the events of
    ``line`` (``"ops"`` or ``"modules"``) that match ``pattern`` in the
    window; 0 without a device trace."""
    if run.trace is None or not run.trace.devices:
        return 0
    lo, hi = run.trace.window
    events = getattr(run.trace, line)
    return max(devtrace.matching_ns(events[d], pattern, lo, hi) for d in run.trace.devices)


def largest_chip_scope_ns(run, scope: str) -> int:
    """The largest, over the chips, of the device ns of the outermost ops of
    the sharded program that carry ``scope`` (``progtrace.scope_ns``); 0
    without a device trace or where the program has no such scope."""
    if run.trace is None or not run.trace.devices:
        return 0
    scopes = progtrace.op_scopes(program_text(run.cell))
    if scope not in scopes.values():
        return 0
    lo, hi = run.trace.window
    return max(
        progtrace.scope_ns(progtrace.label_ops(run.trace, d, {PROGRAM: scopes}), lo, hi).get(
            scope, 0
        )
        for d in run.trace.devices
    )


def program_shapes(cell, n_shards: int) -> tuple[int, int, int, int]:
    """``(num_buckets, nodes_per_bucket, node_size, batch_ops)`` of the
    table ``shard_build`` makes for the cell over ``n_shards`` and of each
    batch the cell sends."""
    g = cell.config["geometry"]
    p = max(1, int(g["node_size"] * g["fill"]))
    per_shard = max(1, math.ceil(math.ceil((1 << int(cell.config["log2_keys"])) / p) / n_shards))
    return (per_shard * n_shards, g["nodes_per_bucket"], g["node_size"],
            sum(cell.traffic["ops"].values()))


def program_text(cell) -> str:
    """The compiled text of the sharded program ``shard_apply_ops_safe`` runs
    for the cell's batches on its first table, over the cell's chips."""
    import jax

    n_shards = len(jax.devices()[: cell.chips])  # the mesh the system builds
    ops = cell.traffic["ops"]
    has_updates = any(ops.get(k, 0) for k in ("insert", "delete", "update"))
    return _compiled_text(
        *program_shapes(cell, n_shards), n_shards, cell.config["routing"], has_updates,
        bool(ops.get("range", 0)),
    )


@functools.lru_cache(maxsize=4)
def _compiled_text(nb, npb, ns, n, n_shards, routing, has_updates, has_ranges) -> str:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.config import ExecConfig
    from repro.core.distributed import ShardedFliX, shard_executor
    from repro.core.ops import OpBatch, resolve_impl
    from repro.core.state import FliXState

    axis = "shards"
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:n_shards]), (axis,))

    def i32(shape, spec=None):
        sharding = None if spec is None else NamedSharding(mesh, spec)
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)

    state = FliXState(
        keys=i32((nb, npb, ns), P(axis, None, None)), vals=i32((nb, npb, ns), P(axis, None, None)),
        node_count=i32((nb, npb), P(axis, None)), node_max=i32((nb, npb), P(axis, None)),
        num_nodes=i32((nb,), P(axis)), mkba=i32((nb,), P(axis)),
        needs_restructure=jax.ShapeDtypeStruct((), jnp.bool_, sharding=NamedSharding(mesh, P())),
    )
    idx = ShardedFliX(state=state, lower_fence=i32((n_shards,), P(axis)),
                      part_fences=i32((n_shards,), P()), axis=axis)
    # the batch as make_ops leaves it: uncommitted, placed by the program
    ops = OpBatch(tag=i32((n,)), key=i32((n,)), val=i32((n,)))
    impl = resolve_impl("auto", ops, has_updates)
    cfg = ExecConfig(routing=routing, impl=impl, donate=False)
    fn, args, kwargs = shard_executor(idx, ops, mesh, cfg=cfg, has_ranges=has_ranges)
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        lowered = fn.lower(*args, **kwargs)
        return lowered.compile({"xla_dump_hlo_as_text": False}).as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
