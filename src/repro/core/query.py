"""Flipped query execution (paper §3.3, Figure 4).

The sorted query batch replaces the index layer: bucket slice boundaries come
from one vectorized searchsorted (``batch.bucket_slices``); inside a bucket,
node location and in-node position are *compare-and-count* reductions — the
TPU analogue of the paper's tile threads each owning one key and voting.

Two execution forms with identical semantics:
  * ``point_query`` / ``successor_query``: fully vectorized jnp (the oracle
    form; also what the CPU benchmarks run).
  * ``kernels/flix_query.py``: the Pallas compute-to-bucket kernel (grid maps
    to bucket blocks, each pulls its query slice).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.state import EMPTY, KEY_DTYPE, NOT_FOUND, FliXState


def _locate(state: FliXState, queries: jax.Array):
    """For each query: (bucket, node-slot, in-node position, key-at-position).

    node-slot is the first active node whose maxKey ≥ q (compare-count over
    the node_max row; inactive slots hold EMPTY so they never match first).
    """
    b = jnp.searchsorted(state.mkba, queries, side="left").astype(jnp.int32)
    b = jnp.minimum(b, state.num_buckets - 1)
    nmax_rows = state.node_max[b]                       # [Q, npb]
    nidx = jnp.sum(nmax_rows < queries[:, None], axis=1).astype(jnp.int32)
    in_bucket = nidx < state.num_nodes[b]
    nidx_c = jnp.minimum(nidx, state.nodes_per_bucket - 1)
    rows = state.keys[b, nidx_c]                        # [Q, ns]
    pos = jnp.sum(rows < queries[:, None], axis=1).astype(jnp.int32)
    pos_c = jnp.minimum(pos, state.node_size - 1)
    key_at = rows[jnp.arange(queries.shape[0]), pos_c]
    return b, nidx_c, pos_c, key_at, in_bucket, pos


def locate_stored(state: FliXState, queries: jax.Array):
    """For each query: (stored?, bucket, node-slot, in-node position).

    The slot is the query's own where ``stored`` holds; elsewhere it is
    clamped in range and names no key of the query."""
    b, nidx, pos, key_at, in_bucket, raw_pos = _locate(state, queries)
    hit = in_bucket & (raw_pos < state.node_size) & (key_at == queries)
    return hit, b, nidx, pos


@jax.jit
def point_query(state: FliXState, sorted_queries: jax.Array) -> jax.Array:
    """Point lookups for a sorted query batch. Misses return NOT_FOUND."""
    q = sorted_queries.astype(KEY_DTYPE)
    hit, b, nidx, pos = locate_stored(state, q)
    return jnp.where(hit, state.vals[b, nidx, pos], NOT_FOUND)


def _suffix_min_with_index(g: jax.Array):
    """suffix_min[i] = min(g[i:]), plus the index attaining it."""
    n = g.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)

    def combine(a, b):
        av, ai = a
        bv, bi = b
        take_a = av <= bv
        return jnp.where(take_a, av, bv), jnp.where(take_a, ai, bi)

    rv, ri = jax.lax.associative_scan(combine, (g[::-1], idx[::-1]))
    return rv[::-1], ri[::-1]


def _successor_fence_rows(state: FliXState):
    """Padded suffix-min rows over per-bucket minimum present keys.

    ``smin_pad[b+1]`` is the smallest key stored in any bucket after ``b``
    (EMPTY if none) and ``sidx_pad[b+1]`` the bucket attaining it — the
    successor fallback for queries past their bucket's largest present key.
    """
    bucket_min = jnp.where(state.num_nodes > 0, state.keys[:, 0, 0], EMPTY)  # [nb]
    smin, sidx = _suffix_min_with_index(bucket_min)
    smin_pad = jnp.concatenate([smin, jnp.array([EMPTY], KEY_DTYPE)])
    sidx_pad = jnp.concatenate([sidx, jnp.array([0], jnp.int32)])
    return smin_pad, sidx_pad


_successor_fence_rows_jit = jax.jit(_successor_fence_rows)


def with_successor_cache(state: FliXState) -> FliXState:
    """Return ``state`` carrying the successor suffix-scan cache.

    Read-only query streams call this once and reuse the returned state, so
    every subsequent :func:`successor_query` skips the O(nb) ``bucket_min``
    rebuild + suffix scan.  Mutating operations construct their result state
    without the cache fields, which is the invalidation rule — no flags to
    maintain.  Idempotent.
    """
    if state.succ_smin is not None:
        return state
    smin_pad, sidx_pad = _successor_fence_rows_jit(state)
    return dataclasses.replace(state, succ_smin=smin_pad, succ_sidx=sidx_pad)


@jax.jit
def successor_query(state: FliXState, sorted_queries: jax.Array):
    """Smallest stored key ≥ q (and its value); (EMPTY, NOT_FOUND) if none.

    In-bucket path: compare-count as in point queries.  Out-of-bucket path
    (bucket's largest *present* key < q): suffix-min over per-bucket minimum
    present keys gives the next non-empty bucket in O(1) per query.  A state
    carrying the :func:`with_successor_cache` rows skips that O(nb) scan
    (the branch is structural, so each form jits separately).
    """
    q = sorted_queries.astype(KEY_DTYPE)
    nb, npb = state.num_buckets, state.nodes_per_bucket
    b = jnp.searchsorted(state.mkba, q, side="left").astype(jnp.int32)
    b = jnp.minimum(b, nb - 1)

    # in-bucket candidate
    nmax_rows = state.node_max[b]
    nidx = jnp.sum(nmax_rows < q[:, None], axis=1).astype(jnp.int32)
    in_bucket = nidx < state.num_nodes[b]
    nidx_c = jnp.minimum(nidx, npb - 1)
    rows = state.keys[b, nidx_c]
    pos = jnp.sum(rows < q[:, None], axis=1).astype(jnp.int32)
    pos_c = jnp.minimum(pos, state.node_size - 1)
    in_key = rows[jnp.arange(q.shape[0]), pos_c]
    in_val = state.vals[b, nidx_c, pos_c]

    # out-of-bucket candidate: first non-empty bucket after b
    if state.succ_smin is not None:
        smin_pad, sidx_pad = state.succ_smin, state.succ_sidx
    else:
        smin_pad, sidx_pad = _successor_fence_rows(state)
    out_key = smin_pad[b + 1]
    out_bucket = sidx_pad[b + 1]
    out_val = state.vals[out_bucket, 0, 0]

    use_in = in_bucket & (pos < state.node_size)
    succ_key = jnp.where(use_in, in_key, out_key)
    succ_val = jnp.where(use_in, in_val, out_val)
    found = succ_key != EMPTY
    return succ_key, jnp.where(found, succ_val, NOT_FOUND)


# ---------------------------------------------------------------------------
# Dense half-open range machinery (the RANGE batch op, DESIGN.md §10)
# ---------------------------------------------------------------------------
#
# A RANGE op carries ``[lo, hi)`` and the batch carries one static
# ``max_results`` output budget.  All three executors — the jnp reference
# phase, the standalone two-pass kernel (``kernels/flix_range``), and the
# fused apply kernel (``kernels/flix_apply``) — share the formulas below so
# the output contract cannot diverge: per-op *full* in-range counts are
# exclusive-scanned into densely packed output offsets (earlier sorted ops
# win the budget, each op emits a prefix of its smallest in-range keys), and
# every output slot resolves to one global key rank.


def flat_rank(flat_k: jax.Array, pref: jax.Array, mkba: jax.Array, q: jax.Array):
    """Global rank (count of stored keys < q) per query, from per-bucket
    sorted rows ``flat_k`` [nb, cap] and live-count prefix sums ``pref``
    [nb+1].  One searchsorted to the owning bucket + one compare-count row."""
    nb = flat_k.shape[0]
    b = jnp.minimum(
        jnp.searchsorted(mkba, q.astype(KEY_DTYPE), side="left"), nb - 1
    ).astype(jnp.int32)
    p = jnp.sum(flat_k[b] < q[:, None], axis=1).astype(jnp.int32)
    return pref[b] + p


def range_offsets(full: jax.Array, is_range: jax.Array, max_results: int):
    """Deterministic budget split: exclusive-scan the full counts (sorted
    batch order), clamp to the budget.  Returns ``(start, emit, total_emit,
    truncated)`` — op i's results land at ``[start[i], start[i]+emit[i])``,
    segments tile ``[0, total_emit)`` consecutively, and ``truncated`` counts
    the range ops whose full result set did not fit."""
    full = jnp.where(is_range, full, 0).astype(jnp.int32)
    # guard the int32 scan: any count > budget behaves identically to
    # budget+1 (start/emit are budget-clamped and emit < budget+1 still
    # flags truncation), and the clamp bounds the running sum by
    # N·(budget+1) so whole-keyspace range floods cannot wrap the cumsum
    full = jnp.minimum(full, max_results + 1)
    start_full = jnp.cumsum(full) - full
    start = jnp.minimum(start_full, max_results).astype(jnp.int32)
    emit = jnp.minimum(full, max_results - start).astype(jnp.int32)
    total_emit = jnp.minimum(jnp.sum(full), max_results).astype(jnp.int32)
    truncated = jnp.sum((emit < full) & is_range).astype(jnp.int32)
    return start, emit, total_emit, truncated


def range_slot_ranks(
    rank_lo: jax.Array, start: jax.Array, total_emit: jax.Array, max_results: int
):
    """Per-output-slot global key rank.  Slot p belongs to the last op whose
    (clamped) start ≤ p — zero-width segments share their start with the
    following op, so ``side="right"`` lands on the true owner.  Invalid
    slots (≥ total_emit) get rank -1."""
    p = jnp.arange(max_results, dtype=jnp.int32)
    owner = jnp.clip(
        jnp.searchsorted(start, p, side="right").astype(jnp.int32) - 1,
        0,
        start.shape[0] - 1,
    )
    g = rank_lo[owner] + (p - start[owner])
    return jnp.where(p < total_emit, g, -1)


def dense_range_scan(
    state: FliXState,
    is_range: jax.Array,
    lo: jax.Array,
    hi: jax.Array,
    *,
    max_results: int,
):
    """The RANGE oracle: answer every active ``[lo, hi)`` op against
    ``state``, packing results densely at exclusive-scan offsets.

    Returns ``(keys[max_results], vals[max_results], start[N], count[N],
    truncated)``.  Output is globally key-ordered within each op's segment
    (and across segments when the ranges are disjoint); slots beyond the
    emitted total hold EMPTY / NOT_FOUND.
    """
    from repro.core.state import flatten_bucket_sorted

    flat_k, flat_v = flatten_bucket_sorted(state)
    nb = state.num_buckets
    live = jnp.sum(flat_k != EMPTY, axis=1).astype(jnp.int32)
    pref = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(live).astype(jnp.int32)]
    )
    rank_lo = flat_rank(flat_k, pref, state.mkba, lo)
    rank_hi = flat_rank(flat_k, pref, state.mkba, hi)
    full = jnp.maximum(rank_hi - rank_lo, 0)
    start, emit, total_emit, truncated = range_offsets(full, is_range, max_results)
    g = range_slot_ranks(rank_lo, start, total_emit, max_results)
    valid = g >= 0
    g_c = jnp.where(valid, g, 0)
    src_b = jnp.clip(
        jnp.searchsorted(pref, g_c, side="right").astype(jnp.int32) - 1, 0, nb - 1
    )
    src_p = g_c - pref[src_b]
    rk = jnp.where(valid, flat_k[src_b, src_p], EMPTY)
    rv = jnp.where(valid, flat_v[src_b, src_p], NOT_FOUND)
    return (
        rk,
        rv,
        jnp.where(is_range, start, 0),
        jnp.where(is_range, emit, 0),
        truncated,
    )


@partial(jax.jit, static_argnames=("max_results",))
def range_query(
    state: FliXState, lo: jax.Array, hi: jax.Array, *, max_results: int = 128
):
    """Keys/vals in [lo, hi] per query pair, padded to max_results.

    Bucket-local walk from the successor position of ``lo`` — no global
    argsort.  Bucket order *is* key order (I2/I3), so each bucket only needs
    its own row sorted (``flatten_bucket_sorted``, a parallel per-row sort
    over the short capacity axis); per-bucket live-count prefix sums then
    turn (bucket, in-bucket position) into a global rank, and the walk is a
    pure rank→(bucket, position) gather across chain/bucket boundaries.
    Bonus operation (the paper discusses but does not benchmark range
    queries); used by the serving KV index.
    """
    from repro.core.state import flatten_bucket_sorted

    flat_k, flat_v = flatten_bucket_sorted(state)        # [nb, cap]
    nb = state.num_buckets
    loq = lo.astype(KEY_DTYPE)

    live = jnp.sum(flat_k != EMPTY, axis=1).astype(jnp.int32)            # [nb]
    pref = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(live).astype(jnp.int32)]
    )                                                                    # [nb+1]
    total = pref[-1]

    # successor position of lo: owning bucket + compare-count inside it
    b0 = jnp.minimum(
        jnp.searchsorted(state.mkba, loq, side="left"), nb - 1
    ).astype(jnp.int32)
    p0 = jnp.sum(flat_k[b0] < loq[:, None], axis=1).astype(jnp.int32)
    rank0 = pref[b0] + p0            # global rank of the first key ≥ lo

    ranks = rank0[:, None] + jnp.arange(max_results, dtype=jnp.int32)[None, :]
    in_range = ranks < total
    ranks_c = jnp.clip(ranks, 0, jnp.maximum(total - 1, 0))
    rb = jnp.clip(
        jnp.searchsorted(pref, ranks_c, side="right").astype(jnp.int32) - 1,
        0,
        nb - 1,
    )
    rpos = ranks_c - pref[rb]
    rk = flat_k[rb, rpos]
    rv = flat_v[rb, rpos]
    valid = in_range & (rk <= hi[:, None]) & (rk != EMPTY)
    return jnp.where(valid, rk, EMPTY), jnp.where(valid, rv, NOT_FOUND), jnp.sum(
        valid, axis=1
    )
