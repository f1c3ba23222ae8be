"""Restructuring (paper §3.5, Figure 3d, Table 4).

Flattens every bucket's chain into single-node buckets, merges underfull
nodes, and re-emits a uniform half-full structure aligned to the *current*
key distribution — bounding both query latency (chain length → 1) and memory
(node recovery).  Entirely device-resident: one global sort + the standard
build; the host only chooses the new static geometry (the analogue of the
paper's kernel relaunch with a new bucket count).

:func:`replay_on_overflow` is the one host driver around it: every engine
(single-device, sharded, tiered, durable) runs a batch, and on bucket
overflow regrows the pre-batch handle and replays the batch through it.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.build import build_from_sorted, plan_geometry
from repro.core.ops import OP_EXPIRE, OP_INSERT
from repro.core.state import EMPTY, FliXState
from repro.core.trace import span


@partial(
    jax.jit,
    static_argnames=("num_buckets", "nodes_per_bucket", "node_size", "fill"),
)
def restructure(
    state: FliXState,
    *,
    num_buckets: int,
    nodes_per_bucket: int | None = None,
    node_size: int | None = None,
    fill: float = 0.5,
) -> FliXState:
    """Rebuild into the given geometry from the current live contents."""
    npb = nodes_per_bucket or state.nodes_per_bucket
    ns = node_size or state.node_size
    flat_k = state.keys.reshape(-1)
    flat_v = state.vals.reshape(-1)
    order = jnp.argsort(flat_k, stable=True)     # EMPTY sentinels sort last
    built = build_from_sorted(
        flat_k[order],
        flat_v[order],
        num_buckets=num_buckets,
        nodes_per_bucket=npb,
        node_size=ns,
        fill=fill,
    )
    if state.exps is None:
        return built
    # expiry plane: the identical build with the expiry column in the value
    # slot lands the identical layout (build positions depend on keys only).
    from repro.core.expiry import NO_EXPIRY

    flat_e = state.exps.reshape(-1)
    built_e = build_from_sorted(
        flat_k[order],
        flat_e[order],
        num_buckets=num_buckets,
        nodes_per_bucket=npb,
        node_size=ns,
        fill=fill,
    )
    exps = jnp.where(built.keys == EMPTY, NO_EXPIRY, built_e.vals)
    return dataclasses.replace(built, exps=exps)


def plan(state: FliXState, *, extra_keys: int = 0, fill: float = 0.5):
    """Host-side geometry planning from the current live count."""
    live = int(state.live_keys()) + extra_keys
    return plan_geometry(
        live,
        node_size=state.node_size,
        nodes_per_bucket=state.nodes_per_bucket,
        fill=fill,
    )


def restructure_auto(state: FliXState, *, fill: float = 0.5) -> FliXState:
    """Restructure to the geometry the initial build would choose now."""
    nb, npb, ns = plan(state, fill=fill)
    return restructure(
        state, num_buckets=nb, nodes_per_bucket=npb, node_size=ns, fill=fill
    )


def restructure_shrink(
    state: FliXState,
    *,
    fill: float = 0.5,
    nodes_per_bucket: int | None = None,
) -> tuple[FliXState, int]:
    """Compact to the smallest geometry for the current live set, reclaiming
    pages (paper §3.5 "memory reclamation").

    ``restructure_auto`` re-plans the bucket count but keeps the old
    ``nodes_per_bucket``, so a structure that once grew wide never gives
    chain capacity back.  Shrink narrows both axes: the bucket count is
    sized for the live keys at ``fill`` and the chain depth drops to the
    smallest count whose capacity is still ≥ 2× the per-bucket fill (the
    same headroom ``restructure_grow`` relies on, so a shrink never makes
    the very next insert batch overflow-prone).

    Returns ``(new_state, reclaimed_bytes)`` where ``reclaimed_bytes`` is
    the drop in allocated footprint (0 if the structure could not shrink).
    """
    live = int(state.live_keys())
    p = max(1, int(state.node_size * fill))
    nb = max(1, math.ceil(live / p))
    if nodes_per_bucket is None:
        # capacity npb*ns ≥ 2p: content can double before overflow.
        npb = max(2, math.ceil(2 * p / state.node_size))
    else:
        npb = nodes_per_bucket
    new = restructure(
        state,
        num_buckets=nb,
        nodes_per_bucket=npb,
        node_size=state.node_size,
        fill=fill,
    )
    reclaimed = max(0, state.memory_bytes() - new.memory_bytes())
    return new, reclaimed


def restructure_grow(
    state: FliXState, *, extra_keys: int, fill: float = 0.5
) -> FliXState:
    """Restructure sized for ``extra_keys`` more keys (overflow recovery).

    Geometry guarantee used by ``insert_safe``: with ``fill`` ≤ 1/2 the new
    buckets are half full, so a subsequent insert of ``extra_keys`` keys can
    at most double any bucket's content — which fits, since capacity is
    ``nodes_per_bucket/fill ≥ 2×`` the initial fill.  Worst-case skew (every
    new key in one bucket) is additionally covered by sizing the bucket count
    for ``live + extra`` and capping the per-bucket sublist at capacity.
    """
    live = int(state.live_keys())
    p = max(1, int(state.node_size * fill))
    # enough buckets that even if all extra keys land between two adjacent
    # fences, that bucket's merged content (p + extra ≤ capacity) fits.
    nb = max(1, math.ceil((live + extra_keys) / p))
    cap = state.nodes_per_bucket * state.node_size
    if p + extra_keys > cap:
        # pathological skew: widen nodes_per_bucket so one bucket can absorb
        # the whole batch (host-side realloc, mirrors the paper's adaptive
        # compute-to-bucket discussion in §3.4).
        npb = math.ceil((p + extra_keys) / state.node_size)
    else:
        npb = state.nodes_per_bucket
    return restructure(
        state,
        num_buckets=nb,
        nodes_per_bucket=npb,
        node_size=state.node_size,
        fill=fill,
    )


def replay_on_overflow(run, grow, handle, ops):
    """Run one batch; on bucket overflow, regrow and replay it once.

    ``run(h)`` executes ``ops`` against the handle ``h`` and returns
    ``(new, results, stats)``; it must not mutate ``h`` (the replay needs
    the pre-batch contents).  ``grow(h, extra_keys=n)`` returns a handle
    with the same contents, restructured to absorb ``n`` more keys.  Any
    handle works that answers ``needs_restructure``: a ``FliXState`` or a
    ``ShardedFliX``.

    The overflowed attempt's result is untrustworthy (``insert``'s
    contract), so the whole batch is replayed on the grown pre-batch
    handle, sized for the batch's INSERT and EXPIRE rows; one replay
    always fits (``restructure_grow``'s geometry guarantee).  A handle
    that was already flagged before the batch is not retried.

    Returns ``(new, results, stats)`` from the attempt that counts, with
    ``stats["restructure_retries"]`` (host int, 0 or 1) set: callers that
    must know the geometry changed (the durable layer's fence epoch, the
    tiered engine's full install) read it there.
    """
    new, results, stats = run(handle)
    # waits for the executor: the device's tail of the batch ends here
    with span("sync.needs_restructure"):
        overflowed = bool(new.needs_restructure) and not bool(handle.needs_restructure)
    if overflowed:
        with span("restructure"):
            n_inserting = int(jnp.sum((ops.tag == OP_INSERT) | (ops.tag == OP_EXPIRE)))
            grown = grow(handle, extra_keys=max(n_inserting, 1))
            new, results, stats = run(grown)
            assert not bool(new.needs_restructure), "post-restructure overflow"
    stats = dict(stats)
    stats["restructure_retries"] = int(overflowed)
    return new, results, stats
