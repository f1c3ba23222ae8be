"""Pallas TPU kernel for FliX flipped range queries (the RANGE batch op,
DESIGN.md §10): two compute-to-bucket passes over the bucket stripes.

A RANGE op is ``[lo, hi)``; the batch carries one static ``max_results``
output budget and the results are packed densely at exclusive-scan offsets
(the shared ``core.query`` offset formulas — the same contract the jnp
oracle and the fused apply kernel implement).  The flipped structure:

  * **Pass 1 — count.**  Grid = (op windows, bucket blocks), the
    established ``flix_query`` layout with scalar-prefetched per-window
    block bounds.  Each bucket stripe is the warp analogue: while resident
    it "binary-searches the sorted batch" in compare-count form — every op
    window that intersects the stripe votes, per op, how many of the
    stripe's keys fall in that op's ``[lo, hi)``.  Counts accumulate across
    the stripe blocks a window touches, yielding each op's *full* in-range
    count with no global gather.

  * **Host seam.**  The shared ``range_offsets`` / ``range_slot_ranks``
    formulas turn full counts into clamped segment offsets and one global
    key rank per output slot (rank of ``lo`` itself is one searchsorted +
    compare-count row against the per-bucket sorted rows, as every FliX
    read does).

  * **Pass 2 — scatter.**  Grid = (bucket blocks,).  Each resident stripe
    block claims the output slots whose rank falls inside its live-count
    prefix span (``pref`` fence rows stream through the fence BlockSpec)
    and writes ``(key, val)`` with exact one-hot MXU gathers — a dense,
    globally key-ordered output with no atomics and no second sort.

Wrapper-side preprocessing (per-bucket row sort, live-count prefix sums)
mirrors how ``flix_successor`` precomputes its fence rows: O(nb·cap) jnp
work outside the kernel, none of it per-op.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flix_query import DEFAULT_BLOCK_Q, _exact_gather_i32
from repro.core.state import EMPTY, KEY_DTYPE

DEFAULT_BLOCK_B = 4     # bucket stripes per block (count mask is O(QB·BB·S))
_EMPTY = int(jnp.iinfo(jnp.int32).max)
_MISS = -1


def _range_count_kernel(
    lo_ref,      # scalar prefetch: [n_windows] first bucket block of window
    hi_ref,      # scalar prefetch: [n_windows] last  bucket block of window
    l_ref,       # [1, QB] sorted range lows for window j
    h_ref,       # [1, QB] their (unsorted) exclusive highs
    keys_ref,    # [BB, cap] per-bucket sorted key rows (EMPTY-padded)
    cnt_ref,     # [1, QB] accumulated full in-range counts
    *,
    block_b: int,
    cap: int,
):
    j = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    active = (i >= lo_ref[j]) & (i <= hi_ref[j])

    @pl.when(active)
    def _process():
        k = keys_ref[...].reshape(1, block_b * cap)       # [1, BB*cap]
        lo = l_ref[0, :][:, None]                         # [QB, 1]
        h = h_ref[0, :][:, None]
        hit = (k >= lo) & (k < h) & (k != _EMPTY)         # [QB, BB*cap]
        cnt_ref[0, :] = cnt_ref[0, :] + jnp.sum(hit.astype(jnp.int32), axis=1)


def _range_scatter_kernel(
    lo_ref,      # scalar prefetch: [1] first bucket block holding output
    hi_ref,      # scalar prefetch: [1] last  bucket block holding output
    g_ref,       # [1, MR] per-slot global key rank (-1 = unused slot)
    keys_ref,    # [BB, cap] per-bucket sorted key rows
    vals_ref,    # [BB, cap] aligned vals
    ps_ref,      # [1, BB] pref[b]   (rank of the bucket's first key)
    pe_ref,      # [1, BB] pref[b+1] (rank just past its last key)
    outk_ref,    # [1, MR] dense range keys / EMPTY
    outv_ref,    # [1, MR] dense range vals / NOT_FOUND
    *,
    block_b: int,
    cap: int,
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        outk_ref[...] = jnp.full_like(outk_ref, _EMPTY)
        outv_ref[...] = jnp.full_like(outv_ref, _MISS)

    active = (i >= lo_ref[0]) & (i <= hi_ref[0])

    @pl.when(active)
    def _process():
        g = g_ref[0, :]                                   # [MR]
        gcol = g[:, None]
        ps = ps_ref[0, :][None, :]                        # [1, BB]
        pe = pe_ref[0, :][None, :]

        # which local bucket's rank span holds each slot (compare-count over
        # the prefix fences; empty buckets have ps == pe and never own)
        bloc = jnp.sum((pe <= gcol).astype(jnp.int32), axis=1)     # [MR]
        bloc_c = jnp.minimum(bloc, block_b - 1)
        oh_b = (
            jax.lax.broadcasted_iota(jnp.int32, (g.shape[0], block_b), 1)
            == bloc_c[:, None]
        )
        ps_g = jnp.sum(jnp.where(oh_b, ps, 0), axis=1)
        mine = (g >= 0) & (bloc < block_b) & (g >= ps_g)

        # in-bucket position: rows are bucket-sorted, so rank maps directly
        pos = jnp.clip(g - ps_g, 0, cap - 1)
        krow = _exact_gather_i32(oh_b.astype(jnp.float32), keys_ref[...])
        vrow = _exact_gather_i32(oh_b.astype(jnp.float32), vals_ref[...])
        oh_p = (
            jax.lax.broadcasted_iota(jnp.int32, (g.shape[0], cap), 1)
            == pos[:, None]
        )
        kk = jnp.sum(jnp.where(oh_p, krow, 0), axis=1)
        vv = jnp.sum(jnp.where(oh_p, vrow, 0), axis=1)

        outk_ref[0, :] = jnp.where(mine, kk, outk_ref[0, :])
        outv_ref[0, :] = jnp.where(mine, vv, outv_ref[0, :])


@functools.partial(
    jax.jit,
    static_argnames=("max_results", "block_q", "block_b", "interpret"),
)
def flix_range_pallas(
    keys3d: jax.Array,      # [nb, npb, ns] int32
    vals3d: jax.Array,      # [nb, npb, ns] int32
    mkba: jax.Array,        # [nb] int32
    sorted_lo: jax.Array,   # [Q] int32, ascending (the batch's one sort)
    hi: jax.Array,          # [Q] int32, aligned exclusive upper bounds
    *,
    max_results: int = 128,
    block_q: int = DEFAULT_BLOCK_Q,
    block_b: int = DEFAULT_BLOCK_B,
    interpret: bool = False,
):
    """Dense ``[lo, hi)`` scans.  Returns ``(keys[max_results],
    vals[max_results], start[Q], count[Q], truncated)`` — byte-identical to
    ``core.query.dense_range_scan`` on the same state."""
    from repro.core.query import flat_rank, range_offsets, range_slot_ranks
    from repro.core.state import sort_bucket_rows

    nb, npb, ns = keys3d.shape
    cap = npb * ns
    qn = sorted_lo.shape[0]

    # per-bucket sorted rows (chain order has interior EMPTY padding)
    flat_k, flat_v = sort_bucket_rows(
        keys3d.reshape(nb, cap), vals3d.reshape(nb, cap)
    )
    live = jnp.sum(flat_k != EMPTY, axis=1).astype(jnp.int32)
    pref = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(live).astype(jnp.int32)]
    )

    # pad buckets to a block multiple (EMPTY stripes never count or own)
    nb_p = pl.cdiv(nb, block_b) * block_b
    flat_kp, flat_vp, mkba_p = flat_k, flat_v, mkba
    ps_row = pref[:-1]
    pe_row = pref[1:]
    if nb_p != nb:
        pad = nb_p - nb
        flat_kp = jnp.pad(flat_kp, ((0, pad), (0, 0)), constant_values=EMPTY)
        flat_vp = jnp.pad(flat_vp, ((0, pad), (0, 0)))
        mkba_p = jnp.pad(mkba_p, (0, pad), constant_values=EMPTY - 1)
        total = pref[-1]
        ps_row = jnp.concatenate([ps_row, jnp.full((pad,), total, jnp.int32)])
        pe_row = jnp.concatenate([pe_row, jnp.full((pad,), total, jnp.int32)])
    nb_blocks = nb_p // block_b

    # --- pass 1: full in-range counts ------------------------------------
    qp = pl.cdiv(max(qn, 1), block_q) * block_q
    l_pad = jnp.pad(sorted_lo.astype(KEY_DTYPE), (0, qp - qn), constant_values=EMPTY)
    # pad hi with 0, not EMPTY: padded ops are already dead (lo = EMPTY
    # matches no key), and an EMPTY hi would drag a partial last window's
    # max(h2) — and with it the window's block span — to the end of the
    # bucket axis
    h_pad = jnp.pad(hi.astype(KEY_DTYPE), (0, qp - qn), constant_values=0)
    n_windows = qp // block_q
    l2 = l_pad.reshape(n_windows, block_q)
    h2 = h_pad.reshape(n_windows, block_q)

    first_b = jnp.searchsorted(mkba_p, l2[:, 0], side="left")
    last_b = jnp.searchsorted(mkba_p, jnp.max(h2, axis=1) - 1, side="left")
    lo_blk = jnp.minimum(first_b, nb_p - 1).astype(jnp.int32) // block_b
    hi_blk = jnp.minimum(last_b, nb_p - 1).astype(jnp.int32) // block_b
    hi_blk = jnp.maximum(hi_blk, lo_blk)

    def bucket_map(j, i, lo_ref, hi_ref):
        return (jnp.clip(i, lo_ref[j], hi_ref[j]), 0)

    count_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_windows, nb_blocks),
        in_specs=[
            pl.BlockSpec((1, block_q), lambda j, i, lo, hi: (j, 0)),
            pl.BlockSpec((1, block_q), lambda j, i, lo, hi: (j, 0)),
            pl.BlockSpec((block_b, cap), bucket_map),
        ],
        out_specs=pl.BlockSpec((1, block_q), lambda j, i, lo, hi: (j, 0)),
    )
    counts = pl.pallas_call(
        functools.partial(_range_count_kernel, block_b=block_b, cap=cap),
        grid_spec=count_spec,
        out_shape=jax.ShapeDtypeStruct((n_windows, block_q), jnp.int32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
    )(lo_blk, hi_blk, l2, h2, flat_kp)
    full = counts.reshape(qp)[:qn]

    # --- host seam: shared offset/rank formulas --------------------------
    is_range = jnp.ones((qn,), bool)
    start, emit, total_emit, truncated = range_offsets(full, is_range, max_results)
    rank_lo = flat_rank(flat_k, pref, mkba, sorted_lo)
    g = range_slot_ranks(rank_lo, start, total_emit, max_results)

    # --- pass 2: scatter to exclusive-scan offsets -----------------------
    mrp = pl.cdiv(max_results, 128) * 128
    g_row = jnp.pad(g, (0, mrp - max_results), constant_values=-1).reshape(1, mrp)
    # overlapping ranges make per-slot ranks non-monotone — bound the block
    # sweep by the min/max rank over the *valid* slots
    g0 = jnp.min(jnp.where(g_row >= 0, g_row, jnp.iinfo(jnp.int32).max))
    g0 = jnp.clip(g0, 0, pref[-1])
    g_last = jnp.maximum(jnp.max(g_row), 0)
    b_first = jnp.clip(
        jnp.searchsorted(pref, g0, side="right").astype(jnp.int32) - 1, 0, nb - 1
    )
    b_last = jnp.clip(
        jnp.searchsorted(pref, g_last, side="right").astype(jnp.int32) - 1,
        0,
        nb - 1,
    )
    lo2 = (b_first // block_b).reshape(1)
    hi2 = (b_last // block_b).reshape(1)

    def bucket_map1(i, lo_ref, hi_ref):
        return (jnp.clip(i, lo_ref[0], hi_ref[0]), 0)

    def fence_map1(i, lo_ref, hi_ref):
        return (0, jnp.clip(i, lo_ref[0], hi_ref[0]))

    scatter_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb_blocks,),
        in_specs=[
            pl.BlockSpec((1, mrp), lambda i, lo, hi: (0, 0)),
            pl.BlockSpec((block_b, cap), bucket_map1),
            pl.BlockSpec((block_b, cap), bucket_map1),
            pl.BlockSpec((1, block_b), fence_map1),
            pl.BlockSpec((1, block_b), fence_map1),
        ],
        out_specs=[
            pl.BlockSpec((1, mrp), lambda i, lo, hi: (0, 0)),
            pl.BlockSpec((1, mrp), lambda i, lo, hi: (0, 0)),
        ],
    )
    outk, outv = pl.pallas_call(
        functools.partial(_range_scatter_kernel, block_b=block_b, cap=cap),
        grid_spec=scatter_spec,
        out_shape=[
            jax.ShapeDtypeStruct((1, mrp), jnp.int32),
            jax.ShapeDtypeStruct((1, mrp), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
    )(
        lo2,
        hi2,
        g_row,
        flat_kp,
        flat_vp,
        ps_row.reshape(1, nb_p),
        pe_row.reshape(1, nb_p),
    )
    return outk[0, :max_results], outv[0, :max_results], start, emit, truncated
