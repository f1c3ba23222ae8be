"""Bulk insertion (paper §4.2/4.3, Figure 3b, Table 2 — TL-Bulk semantics).

Per bucket, in one shot (vmapped over buckets; the Pallas kernel form keeps
the bucket stripe in VMEM):

  1. *pull* the bucket's sublist from the sorted update batch (flipped
     indexing: boundaries from ``batch.bucket_slices``),
  2. merge it with the bucket's chain content (upsert: an incoming duplicate
     key overwrites the stored value — the paper's "if not present, insert"
     plus rowID update),
  3. re-chunk each *original node region* into ``ceil(m_j / node_size)``
     balanced pieces.  A region that still fits keeps its node untouched
     (same keys, same boundary); an overflowing region splits into
     even pieces — the batched fixed point of the paper's split-in-half rule.
     Regions are never merged by insertion (merging is restructuring's job),
     so underfull-node accounting matches the paper's.

TPU adaptation note (DESIGN.md §3): the whole bucket stripe is one VMEM
block, so rewriting the stripe costs the same DMA as editing one node — the
paper's node-local shift-right optimization targets GPU cache lines, which do
not exist here.  What we keep is the *work assignment* (compute→bucket) and
the *node-level structure* (bounded nodes, splits, chain order).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.batch import bucket_slices, gather_kv_sublists
from repro.core.state import (
    EMPTY,
    KEY_DTYPE,
    VAL_DTYPE,
    FliXState,
    flatten_bucket_sorted,
)


def _merge_one_bucket(
    ck, cv, ik, iv, onm, onn, *, node_size: int, nodes_per_bucket: int
):
    """Merge one bucket's content (ck/cv) with its incoming sublist (ik/iv).

    Returns new (keys [npb, ns], vals, overflow flag).  All shapes static.
    """
    ns, npb = node_size, nodes_per_bucket
    # upsert-dedup *before* the sort: both sides are sorted with EMPTY tails
    # and unique valid keys, so a stripe key that reappears in the incoming
    # sublist is found by one binary search.  Masking those to EMPTY up front
    # collapses the old two-pass form (lexsort by (key, source) followed by a
    # full argsort of the masked keys) into a single stable sort.
    pos = jnp.searchsorted(ik, ck, side="left")
    pos_c = jnp.minimum(pos, ik.shape[0] - 1)
    dup = (ik[pos_c] == ck) & (ck != EMPTY)    # incoming value wins
    allk = jnp.concatenate([jnp.where(dup, EMPTY, ck), ik])
    allv = jnp.concatenate([cv, iv])
    order = jnp.argsort(allk, stable=True)     # the single sort pass
    mk = allk[order]                           # merged keys, EMPTY tail
    mv = allv[order]
    L = mk.shape[0]
    valid = mk != EMPTY
    m_total = jnp.sum(valid).astype(jnp.int32)

    # --- original node regions -------------------------------------------
    # region j covers (onm[j-1], onm[j]]; keys above the last active node's
    # max fall into the last region (the paper: last node's maxKey grows).
    r = jnp.searchsorted(onm, mk, side="left").astype(jnp.int32)
    r = jnp.minimum(r, jnp.maximum(onn - 1, 0))
    r = jnp.where(valid, r, npb - 1)

    m_j = jnp.zeros((npb,), jnp.int32).at[r].add(valid.astype(jnp.int32))
    s_j = (m_j + ns - 1) // ns                # pieces per region
    f_j = jnp.cumsum(m_j) - m_j               # first rank of region
    base_j = jnp.cumsum(s_j) - s_j            # first output slot of region
    total_new = jnp.sum(s_j).astype(jnp.int32)

    rank = jnp.arange(L, dtype=jnp.int32) - f_j[r]
    m_r = jnp.maximum(m_j[r], 1)
    s_r = jnp.maximum(s_j[r], 1)
    piece = (rank * s_r) // m_r
    piece_start = (piece * m_r + s_r - 1) // s_r
    pos = rank - piece_start
    slot = base_j[r] + piece

    dump = npb * ns
    dest = jnp.where(valid & (slot < npb), slot * ns + pos, dump)
    nk = jnp.full((npb * ns + 1,), EMPTY, KEY_DTYPE).at[dest].set(mk)
    nv = jnp.zeros((npb * ns + 1,), VAL_DTYPE).at[dest].set(mv)
    overflow = total_new > npb
    return (
        nk[:-1].reshape(npb, ns),
        nv[:-1].reshape(npb, ns),
        overflow,
        total_new,
        m_total,
    )


def insert_with_slices(
    state: FliXState,
    sorted_keys: jax.Array,
    sorted_vals: jax.Array,
    starts: jax.Array,
    ends: jax.Array,
):
    """Bulk-insert with precomputed per-bucket slice boundaries.

    The routing (``starts``/``ends`` into the sorted batch) is supplied by
    the caller: :func:`insert` computes it with ``bucket_slices``; the mixed
    batch engine (``core.ops.apply_ops``) derives it from its *single*
    routing of the whole mixed batch via prefix counts.  Both paths hit this
    identical merge code, which is what makes mixed execution byte-identical
    to per-type execution.
    """
    nb, npb, ns = state.num_buckets, state.nodes_per_bucket, state.node_size
    cap = state.bucket_capacity
    keys_in = sorted_keys.astype(KEY_DTYPE)
    vals_in = sorted_vals.astype(VAL_DTYPE)

    ik, iv, counts, true_counts = gather_kv_sublists(
        keys_in, vals_in, starts, ends, cap
    )

    ck, cv = flatten_bucket_sorted(state)

    nk, nv, overflow, total_new, m_total = jax.vmap(
        partial(_merge_one_bucket, node_size=ns, nodes_per_bucket=npb)
    )(ck, cv, ik, iv, state.node_max, state.num_nodes)

    slice_overflow = true_counts > cap
    any_overflow = jnp.any(overflow) | jnp.any(slice_overflow)

    node_count = jnp.sum(nk != EMPTY, axis=2).astype(jnp.int32)
    node_max = jnp.where(
        node_count > 0,
        jnp.take_along_axis(
            nk, jnp.maximum(node_count - 1, 0)[..., None], axis=2
        )[..., 0],
        EMPTY,
    ).astype(KEY_DTYPE)
    num_nodes = jnp.sum(node_count > 0, axis=1).astype(jnp.int32)

    new_state = FliXState(
        keys=nk,
        vals=nv,
        node_count=node_count,
        node_max=node_max,
        num_nodes=num_nodes,
        mkba=state.mkba,  # fences fixed until restructuring (paper §3.2)
        needs_restructure=state.needs_restructure | any_overflow,
    )
    stats = {
        "inserted": jnp.sum(jnp.minimum(true_counts, cap)),
        "nodes_after": jnp.sum(num_nodes),
        "splits": jnp.sum(jnp.maximum(num_nodes - state.num_nodes, 0)),
        "overflowed_buckets": jnp.sum(overflow | slice_overflow),
    }
    return new_state, stats


@jax.jit
def insert(state: FliXState, sorted_keys: jax.Array, sorted_vals: jax.Array):
    """Bulk-insert a sorted, deduplicated batch. Returns (state', stats).

    If any bucket overflows its capacity, the returned state's
    ``needs_restructure`` flag is set and *that bucket's contents are not
    trustworthy* — callers use :func:`insert_safe` (or check the flag and
    retry on the original state after restructuring).  ``insert`` itself
    never mutates its input (functional), so retry is always clean.
    """
    starts, ends = bucket_slices(state, sorted_keys.astype(KEY_DTYPE))
    return insert_with_slices(state, sorted_keys, sorted_vals, starts, ends)


def insert_safe(state: FliXState, sorted_keys, sorted_vals):
    """Host-level driver: insert, restructure-and-retry on overflow.

    This is the paper's contract — restructuring is the capacity-management
    mechanism (§3.5); overflow pressure triggers it.  Host-driven because the
    new geometry changes static shapes (like a GPU-side realloc + rebuild).
    The retry is ``core.restructure.replay_on_overflow``, the engines' one
    driver, which sizes the regrowth by the batch's non-EMPTY keys; its
    ``restructure_retries`` joins the returned stats.
    """
    from repro.core.ops import OP_INSERT, OP_NOP, OpBatch
    from repro.core.restructure import replay_on_overflow, restructure_grow

    keys = jnp.asarray(sorted_keys)
    ops = OpBatch(
        tag=jnp.where(keys != EMPTY, OP_INSERT, OP_NOP), key=keys, val=sorted_vals
    )

    def run(s):
        new_state, stats = insert(s, sorted_keys, sorted_vals)
        return new_state, None, stats

    new_state, _, stats = replay_on_overflow(run, restructure_grow, state, ops)
    return new_state, stats
