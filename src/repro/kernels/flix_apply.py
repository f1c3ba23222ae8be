"""Fused compute-to-bucket apply kernel: one VMEM-resident pass per bucket
for the whole mixed batch (the paper's "a bucket does all of its work in one
visit", §4.1, applied across the full operation mix).

``core.ops.apply_ops`` in reference form executes a mixed batch as four
separate device passes — insert merge, delete, point reads, successor reads —
so every bucket stripe crosses HBM four-plus times per step.  This kernel
collapses them: while a bucket stripe is VMEM-resident it

  1. upsert-merges its INSERT slice with original-node-region re-chunking
     (identical formulas to ``flix_insert`` / ``core.insert``),
  2. physically DELETEs its DELETE slice with in-node and chain compaction
     (identical formulas to ``flix_delete`` / ``core.delete``),
  3. answers the batch's POINT and SUCCESSOR ops that fall in the bucket
     against the *post-update* stripe (compare-count votes + one-hot
     masked gathers, the formulas of ``flix_query`` / ``flix_successor``),
  4. fills the output slots of the batch's RANGE ops whose global key rank
     lands in the bucket — the dense count/scatter contract of
     ``kernels/flix_range`` (DESIGN.md §10), read straight from the
     post-update stripe in the same VMEM residency,

writing the new stripe, the per-bucket metadata, and the per-op results in
one pass.

Grid layout — the established window/bucket-block scheme from
``flix_query`` with one twist: **window 0 sweeps every bucket block** (its
scalar-prefetched bounds are widened to [0, nb_blocks)), which is where the
single full update pass happens; windows ≥ 1 only re-visit the blocks their
own op range touches and *recompute* the update for those stripes.  The
recompute is idempotent — the merge/delete depend only on per-bucket tiles
gathered from the whole batch, not on the window — so revisited stripe
blocks are rewritten with byte-identical data and every flush of an output
block happens after a full in-window rewrite.  Total state traffic is one
full sweep plus boundary revisits, versus ≥ 4 full sweeps for the reference
engine.  Window 0 still visits every block, but only a bucket whose INSERT
or DELETE tile holds a key pays the merge/delete compute; every other
bucket writes its stripe through (on a state that keeps I1–I5 the merge and
delete of empty tiles are the identity), so a batch's update compute scales
with the buckets it updates, not with the table.

The successor out-of-bucket fallback cannot be resolved block-locally, so
the wrapper feeds the same fence-row trick as ``flix_successor``: it derives
the *post-update* per-bucket minimum (min of surviving stripe keys and the
bucket's insert slice — exact because one batch never inserts and deletes
the same key) and suffix-scans it into ``next_key``/``next_val`` rows that
stream through the fence BlockSpec.

RANGE uses the same predict-without-running-the-update trick, extended from
the per-bucket minimum to the whole per-bucket key multiset: the wrapper
sorts (surviving stripe keys minus upsert duplicates) ∪ (insert slice) per
bucket, prefix-sums the live counts into post-update rank fences
``pref[b]``/``pref[b+1]``, resolves every op's ``[lo, hi)`` to full counts
→ clamped segment offsets → one global rank per output slot (the shared
``core.query`` formulas), and streams the rank fences through the fence
BlockSpec.  The kernel then only has to map "rank within my bucket" to a
(node, position) of the stripe it just rebuilt — values come from VMEM, not
from a second state pass.

Tiered residency (DESIGN.md §15): the kernel is *residency-oblivious*.  A
``TieredFliX`` working set arrives here as an ordinary packed ``FliXState``
whose buckets are the promoted subset, re-fenced so ``mkba[-1] ==
MAX_VALID``; because every bucket an op can touch is promoted by the
prefetch pre-pass (``core.ops.touched_buckets``), the searchsorted routing
and the successor/range fence rows are self-contained in the packed view and
nothing below this line knows tiers exist.  The only contract this file owes
the residency plane is the one it already keeps: it never reads or writes a
bucket outside the state it was handed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flix_query import DEFAULT_BLOCK_Q
from repro.core.batch import bucket_slices, gather_kv_sublists, gather_sublists
from repro.core.state import EMPTY, KEY_DTYPE, FliXState

DEFAULT_BLOCK_B = 2     # bucket stripes per block (walked one bucket at a time)
# Scoped VMEM the kernel is compiled with, set explicitly rather than left
# to the compiler's default; kernels.autotune holds its tile candidates to
# the same number.  The kernel needs a few MiB at S = 512 (column scratch +
# (CHUNK, S) tiles + double-buffered blocks) — a v5e core has 128 MiB.
VMEM_LIMIT_BYTES = 32 * 2**20
_EMPTY = int(jnp.iinfo(jnp.int32).max)
_MIN = int(jnp.iinfo(jnp.int32).min)
_MISS = -1
_OP_POINT = 2           # mirror core.ops tags as Python literals (kernels
_OP_SUCCESSOR = 3       # must not capture traced constants)
_OP_RANGE = 5

# rows of the per-bucket fence array streamed with every stripe block
_FENCE_MKBA, _FENCE_LF, _FENCE_NXK, _FENCE_NXV, _FENCE_PS, _FENCE_PE = range(6)
N_FENCE_ROWS = 6


# column scratch slots: per-bucket vectors turned on their side, so a loop
# over (CHUNK, S) tiles can stream them by sublane offset
(_C_A, _C_AV, _C_B, _C_BV, _C_D, _C_RANK_A, _C_REG_A, _C_KEEP_A, _C_RANK_B,
 _C_REG_B, _C_MK, _C_MV, _C_FK, _C_FV) = range(14)
N_COLS = 14


def _chunk_rows(S: int) -> int:
    """Rows per (CHUNK, S) tile in the in-kernel loops.  128 keeps each tile
    64 vregs at S = 512, so the unrolled loop body — and the compile — stay
    small; smaller stripes (interpret-mode tests) run as one chunk."""
    return 128 if S % 128 == 0 else S


def _col(row):
    """[1, W] row → [W, 1] column."""
    return row[0, :][:, None]


def _row(col):
    """[W, 1] column → [1, W] row."""
    return col[:, 0][None, :]


def _sum_rows(x):
    return jnp.sum(x, axis=0, keepdims=True)


def _sum_lanes(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _bucket_body(
    b,           # bucket index within the block (traced loop index)
    A_ref,       # [BB, S] stripe keys (VMEM-resident, chain order)
    Av_ref,      # [BB, S] stripe vals
    t_ref,       # [1, QB] op tags for window j
    q_ref,       # [1, QB] sorted op keys for window j
    nmax_ref,    # [BB, npb] per-node max keys (EMPTY when inactive)
    ik_ref,      # [BB, cap] sorted per-bucket INSERT keys (EMPTY-padded)
    iv_ref,      # [BB, cap]
    dk_ref,      # [BB, cap] sorted per-bucket DELETE keys (present only)
    fence_ref,   # [BB, N_FENCE_ROWS] per-bucket fences (see _FENCE_*)
    g_ref,       # [1, MR] per-RANGE-slot post-update global rank (-1 unused)
    okeys_ref,   # [BB, S] post-update stripes
    ovals_ref,   # [BB, S]
    ocnt_ref,    # [BB, npb]
    omax_ref,    # [BB, npb]
    bmeta_ref,   # [BB, 3]: node count, overflow flag, keys deleted
    resv_ref,    # [1, QB] POINT/SUCCESSOR values / NOT_FOUND
    resk_ref,    # [1, QB] SUCCESSOR keys / EMPTY
    rngk_ref,    # [1, MR] dense RANGE keys / EMPTY (shared across windows)
    rngv_ref,    # [1, MR] dense RANGE vals / NOT_FOUND
    cols,        # [N_COLS, S, 1] column scratch
    *,
    npb: int,
    ns: int,
):
    """Merge + delete + reads + range gather for one bucket stripe.

    Vectors live as [1, S] rows.  Every all-pairs compare-count runs as a
    loop over (CHUNK, S) tiles whose sublane axis streams one vector from
    the column scratch, so no [S, S] temporary exists and no reshape splits
    the lane axis; a lane's node is ``lane // ns``.  The formulas are those
    of ``core.insert`` / ``core.delete`` / ``core.query``, term for term.
    A bucket with no INSERT and no DELETE keys writes its stripe through
    instead of running phases 1-2, which would give it back unchanged.
    """
    S = npb * ns
    C = _chunk_rows(S)
    nch = S // C
    row = pl.ds(b, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
    node_of_lane = lane // ns                                  # [1, S]
    sub = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    node_col = jax.lax.broadcasted_iota(jnp.int32, (npb, 1), 0)
    node_row = jax.lax.broadcasted_iota(jnp.int32, (1, npb), 1)

    def chunk(c):
        return pl.ds(pl.multiple_of(c * C, C), C)

    def loop(body, init):
        return jax.lax.fori_loop(0, nch, body, init)

    A = A_ref[row, :]                          # [1, S]
    Av = Av_ref[row, :]
    B = ik_ref[row, :]                         # [1, cap] incoming
    D = dk_ref[row, :]
    nmax = nmax_ref[row, :]                    # [1, npb]
    meta_lane = jax.lax.broadcasted_iota(jnp.int32, (1, 3), 1)

    def merge_delete():
        Bv = iv_ref[row, :]
        for slot, vec in (
            (_C_A, A), (_C_AV, Av), (_C_B, B), (_C_BV, Bv), (_C_D, D)
        ):
            cols[slot] = _col(vec)
        zero_row = jnp.zeros((1, S), jnp.int32)
        # ---- phase 1: upsert merge of the INSERT slice ----------------
        validA = A != _EMPTY
        validB_i = (B != _EMPTY).astype(jnp.int32)

        def dup_tile(c, acc):                      # is A[l] re-inserted by B?
            return acc + _sum_rows(jnp.where(cols[_C_B, chunk(c), :] == A, 1, 0))

        dupA = loop(dup_tile, jnp.zeros((1, S), jnp.int32)) > 0
        keepA_i = (validA & ~dupA).astype(jnp.int32)   # incoming value wins
        cols[_C_KEEP_A] = _col(keepA_i)

        # original node regions (fixed boundaries; last region open-ended)
        onn0 = _sum_lanes((nmax != _EMPTY).astype(jnp.int32))     # [1, 1]
        onn_c = jnp.maximum(onn0 - 1, 0)

        def rank_pass(z_slot, keep_slot, rank_slot, reg_slot):
            # merged ranks by compare-count (both sides sorted & unique), the
            # region of each key, and the live-key count of every region
            def tile(c, m):
                z = cols[z_slot, chunk(c), :]      # [C, 1]
                keep = (
                    cols[keep_slot, chunk(c), :]
                    if keep_slot is not None
                    else (z != _EMPTY).astype(jnp.int32)
                )
                rank = _sum_lanes(jnp.where(A < z, keepA_i, 0)) + _sum_lanes(
                    jnp.where(B < z, validB_i, 0)
                )
                reg = jnp.minimum(
                    _sum_lanes((nmax < z).astype(jnp.int32)), onn_c
                )
                cols[rank_slot, chunk(c), :] = rank
                cols[reg_slot, chunk(c), :] = reg
                return m + _sum_rows(jnp.where(reg == node_row, keep, 0))

            return tile

        m_j = loop(
            rank_pass(_C_A, _C_KEEP_A, _C_RANK_A, _C_REG_A),
            jnp.zeros((1, npb), jnp.int32),
        )
        m_j = loop(rank_pass(_C_B, None, _C_RANK_B, _C_REG_B), m_j)   # [1, npb]

        def excl_prefix(x_row):
            # exclusive prefix sum of a [1, npb] row (Mosaic has no cumsum)
            return _sum_rows(jnp.where(node_col < node_row, _col(x_row), 0))

        s_j = (m_j + ns - 1) // ns                 # pieces per region
        f_j = excl_prefix(m_j)                     # first rank of region
        base_j = excl_prefix(s_j)                  # first output slot
        total_new = _sum_lanes(s_j)                # [1, 1]

        def scatter_pass(z_slot, v_slot, keep_slot, rank_slot, reg_slot):
            # balanced split within each region (same formulas as core/insert),
            # then a one-hot scatter of each key to its merged lane
            def tile(c, acc):
                mk, mv, filled = acc
                z = cols[z_slot, chunk(c), :]
                keep = (
                    cols[keep_slot, chunk(c), :] > 0
                    if keep_slot is not None
                    else z != _EMPTY
                )
                oh = cols[reg_slot, chunk(c), :] == node_row      # [C, npb]
                m_r = jnp.maximum(_sum_lanes(jnp.where(oh, m_j, 0)), 1)
                s_r = jnp.maximum(_sum_lanes(jnp.where(oh, s_j, 0)), 1)
                f_r = _sum_lanes(jnp.where(oh, f_j, 0))
                b_r = _sum_lanes(jnp.where(oh, base_j, 0))
                rr = cols[rank_slot, chunk(c), :] - f_r
                piece = (rr * s_r) // m_r
                start = (piece * m_r + s_r - 1) // s_r
                slot = b_r + piece
                dest = jnp.where(keep & (slot < npb), slot * ns + rr - start, S)
                hit = dest == lane                                # [C, S]
                return (
                    mk + _sum_rows(jnp.where(hit, z, 0)),
                    mv + _sum_rows(jnp.where(hit, cols[v_slot, chunk(c), :], 0)),
                    filled + _sum_rows(hit.astype(jnp.int32)),
                )

            return tile

        acc = loop(
            scatter_pass(_C_A, _C_AV, _C_KEEP_A, _C_RANK_A, _C_REG_A),
            (zero_row, zero_row, zero_row),
        )
        mk, mv, filled = loop(
            scatter_pass(_C_B, _C_BV, None, _C_RANK_B, _C_REG_B), acc
        )
        mk = jnp.where(filled > 0, mk, _EMPTY)     # [1, S] merged stripe
        mv = jnp.where(filled > 0, mv, 0)

        # ---- phase 2: physical delete on the merged stripe -----------
        def hit_tile(c, acc):
            return acc + _sum_rows(jnp.where(cols[_C_D, chunk(c), :] == mk, 1, 0))

        hit = (loop(hit_tile, zero_row) > 0) & (mk != _EMPTY)
        del_cnt = _sum_lanes(hit.astype(jnp.int32))               # [1, 1]
        keep_i = ((~hit) & (mk != _EMPTY)).astype(jnp.int32)      # [1, S]
        cols[_C_MK] = _col(mk)
        cols[_C_MV] = _col(mv)

        # chain compaction: surviving nodes shift into the lowest slots
        cnt = _sum_lanes(jnp.where(node_of_lane == node_col, keep_i, 0))  # [npb, 1]
        nonempty = (cnt > 0).astype(jnp.int32)
        slot_dest = _sum_rows(jnp.where(node_col < node_row, nonempty, 0))  # [1, npb]

        def compact_tile(c, acc):
            # a kept lane moves to (its node's new slot, the count of kept
            # lanes before it in its own node)
            fk, fv, filled = acc
            z = cols[_C_MK, chunk(c), :]
            l = c * C + sub                                       # [C, 1]
            kept = (z != _EMPTY) & (_sum_lanes(jnp.where(z == D, 1, 0)) == 0)
            before = (lane < l) & (node_of_lane == l // ns)
            dest = _sum_lanes(jnp.where(before, keep_i, 0))
            slot = _sum_lanes(jnp.where(node_row == l // ns, slot_dest, 0))
            final = jnp.where(kept, slot * ns + dest, S)
            hit = final == lane
            return (
                fk + _sum_rows(jnp.where(hit, z, 0)),
                fv + _sum_rows(jnp.where(hit, cols[_C_MV, chunk(c), :], 0)),
                filled + _sum_rows(hit.astype(jnp.int32)),
            )

        fk, fv, filled = loop(compact_tile, (zero_row, zero_row, zero_row))
        fk = jnp.where(filled > 0, fk, _EMPTY)                    # [1, S]
        fv = jnp.where(filled > 0, fv, 0)

        # metadata (keys ascend within a node, so its max is its last key)
        live = fk != _EMPTY
        mine_lane = (node_of_lane == node_col) & live            # [npb, S]
        ocnt = _sum_lanes(mine_lane.astype(jnp.int32))            # [npb, 1]
        omax = jnp.max(jnp.where(mine_lane, fk, _MIN), axis=1, keepdims=True)
        omax = jnp.where(ocnt > 0, omax, _EMPTY)
        onn = _sum_rows((ocnt > 0).astype(jnp.int32))             # [1, 1]

        okeys_ref[row, :] = fk
        ovals_ref[row, :] = fv
        ocnt_ref[row, :] = _row(ocnt)
        omax_ref[row, :] = _row(omax)
        oflow = (total_new > npb).astype(jnp.int32)
        bmeta_ref[row, :] = jnp.where(
            meta_lane == 0, onn, jnp.where(meta_lane == 1, oflow, del_cnt)
        )

    def write_through():
        # no INSERT: region j is node j and holds c_j <= ns keys, so the
        # merge puts every key back in its own lane; no DELETE: compaction
        # moves nothing.  On a stripe that keeps I1-I5 phases 1-2 are the
        # identity, but for the values of EMPTY lanes, which they zero.
        live_i = (A != _EMPTY).astype(jnp.int32)
        okeys_ref[row, :] = A
        ovals_ref[row, :] = jnp.where(live_i > 0, Av, 0)
        ocnt_ref[row, :] = _row(
            _sum_lanes(jnp.where(node_of_lane == node_col, live_i, 0))
        )
        omax_ref[row, :] = nmax
        onn = _sum_lanes((nmax != _EMPTY).astype(jnp.int32))  # [1, 1]
        bmeta_ref[row, :] = jnp.where(meta_lane == 0, onn, 0)

    # only a bucket whose INSERT or DELETE tile holds a key pays phases
    # 1-2; a row reduction, so the tiles' order does not matter
    has_update = jnp.sum(jnp.where((B != _EMPTY) | (D != _EMPTY), 1, 0)) > 0
    jax.lax.cond(has_update, merge_delete, write_through)

    fences = fence_ref[row, :]                 # [1, N_FENCE_ROWS]

    def fence(k):
        return fences[:, k : k + 1]            # [1, 1]

    # ---- phase 3: reads against the post-update stripe ---------------
    t = t_ref[...]                             # [1, QB] op tags
    q = q_ref[...]                             # [1, QB] op keys
    is_p = t == _OP_POINT
    is_s = t == _OP_SUCCESSOR
    # the bucket owns the ops in (lower fence, mkba]
    mine = (is_p | is_s) & (q > fence(_FENCE_LF)) & (q <= fence(_FENCE_MKBA))
    has_reads = jnp.sum(mine.astype(jnp.int32)) > 0
    # valid RANGE slots are a prefix, so g[0] < 0 <=> nothing to emit
    has_ranges = g_ref[0, 0] >= 0

    # phases 3-4 read the post-update stripe from whichever branch wrote it
    @pl.when(has_reads | has_ranges)
    def _stage_stripe():
        cols[_C_FK] = _col(okeys_ref[row, :])
        cols[_C_FV] = _col(ovals_ref[row, :])

    @pl.when(has_reads)
    def _reads():
        # node by post-update node-max votes, position by key votes
        omax = _col(omax_ref[row, :])                         # [npb, 1]
        onn = bmeta_ref[row, :][:, 0:1]                       # [1, 1]
        nidx = _sum_rows((omax < q).astype(jnp.int32))        # [1, QB]
        in_bucket = nidx < onn
        nidx_c = jnp.minimum(nidx, npb - 1)

        def pos_tile(c, pos):
            k = cols[_C_FK, chunk(c), :]
            l = c * C + sub
            return pos + _sum_rows(
                jnp.where((l // ns == nidx_c) & (k < q), 1, 0)
            )

        pos = loop(pos_tile, jnp.zeros_like(q))
        target = nidx_c * ns + jnp.minimum(pos, ns - 1)

        def at_tile(c, acc):
            hit = (c * C + sub) == target                     # [C, QB]
            return (
                acc[0] + _sum_rows(jnp.where(hit, cols[_C_FK, chunk(c), :], 0)),
                acc[1] + _sum_rows(jnp.where(hit, cols[_C_FV, chunk(c), :], 0)),
            )

        key_at, val_at = loop(at_tile, (jnp.zeros_like(q), jnp.zeros_like(q)))

        # POINT: hit iff the key is stored post-update
        use_in = in_bucket & (pos < ns)
        point_res = jnp.where(use_in & (key_at == q), val_at, _MISS)

        # SUCCESSOR: in-bucket candidate, else the post-update fence rows
        succ_key = jnp.where(use_in, key_at, fence(_FENCE_NXK))
        succ_val = jnp.where(use_in, val_at, fence(_FENCE_NXV))
        succ_val = jnp.where(succ_key != _EMPTY, succ_val, _MISS)

        resv_ref[...] = jnp.where(
            mine & is_p,
            point_res,
            jnp.where(mine & is_s, succ_val, resv_ref[...]),
        )
        resk_ref[...] = jnp.where(mine & is_s, succ_key, resk_ref[...])

    # ---- phase 4: dense RANGE slots owned by this bucket --------------
    # slot p carries the post-update global rank of its key; the bucket
    # claims p iff the rank falls in its [pref[b], pref[b+1]) span, then
    # maps the in-bucket rank to a (node, pos) of the post-update stripe
    # (ocnt prefix sums = node boundaries).  Batches with no RANGE output
    # skip the gather compute entirely.
    @pl.when(has_ranges)
    def _range_gather():
        g = g_ref[...]                         # [1, MR]
        ocnt = _col(ocnt_ref[row, :])          # [npb, 1]
        ps = fence(_FENCE_PS)
        mine_r = (g >= ps) & (g < fence(_FENCE_PE))
        r = g - ps                             # rank within the bucket

        cum = _sum_lanes(jnp.where(node_row <= node_col, _row(ocnt), 0))  # [npb, 1]
        node_r = jnp.minimum(_sum_rows((cum <= r).astype(jnp.int32)), npb - 1)
        base = _sum_rows(jnp.where(node_col == node_r, cum - ocnt, 0))
        target = node_r * ns + jnp.clip(r - base, 0, ns - 1)  # [1, MR]

        def gather_tile(c, acc):
            hit = (c * C + sub) == target                     # [C, MR]
            return (
                acc[0] + _sum_rows(jnp.where(hit, cols[_C_FK, chunk(c), :], 0)),
                acc[1] + _sum_rows(jnp.where(hit, cols[_C_FV, chunk(c), :], 0)),
            )

        kk, vv = loop(gather_tile, (jnp.zeros_like(g), jnp.zeros_like(g)))
        rngk_ref[...] = jnp.where(mine_r, kk, rngk_ref[...])
        rngv_ref[...] = jnp.where(mine_r, vv, rngv_ref[...])


def _stripe_body(A_ref, Av_ref, *refs, block_b: int, npb: int, ns: int):
    """One active stripe block: every bucket of it, one at a time.

    Shared verbatim by the single-buffer kernel (stripes arrive through the
    automatic BlockSpec pipeline) and the double-buffered kernel (stripes
    arrive via explicit DMA into two-slot scratch) — only where ``A_ref``/
    ``Av_ref`` point differs, so the two variants cannot diverge.
    """

    def one(b, carry):
        _bucket_body(b, A_ref, Av_ref, *refs, npb=npb, ns=ns)
        return carry

    jax.lax.fori_loop(0, block_b, one, 0)


def _init_outputs(j, i, resv_ref, resk_ref, rngk_ref, rngv_ref):
    @pl.when(i == 0)
    def _init():
        resv_ref[...] = jnp.full_like(resv_ref, _MISS)
        resk_ref[...] = jnp.full_like(resk_ref, _EMPTY)

    # the RANGE output block is shared by every window (its slots belong to
    # buckets, not windows), so it is initialised exactly once — window 0's
    # full sweep then fills every owned slot, later windows rewrite
    # idempotently
    @pl.when((j == 0) & (i == 0))
    def _init_range():
        rngk_ref[...] = jnp.full_like(rngk_ref, _EMPTY)
        rngv_ref[...] = jnp.full_like(rngv_ref, _MISS)


def _apply_kernel(
    lo_ref,      # scalar prefetch: [n_windows] first bucket block of window
    hi_ref,      # scalar prefetch: [n_windows] last  bucket block of window
    t_ref,
    q_ref,
    keys_ref,    # [BB, S] bucket-block key stripes (auto-pipelined)
    vals_ref,    # [BB, S]
    *rest,       # the remaining blocked inputs/outputs, the column scratch
    block_b: int,
    npb: int,
    ns: int,
):
    """Single-buffer variant: stripes stream through the BlockSpec pipeline."""
    j = pl.program_id(0)
    i = pl.program_id(1)
    _init_outputs(j, i, *rest[-5:-1])
    active = (i >= lo_ref[j]) & (i <= hi_ref[j])

    @pl.when(active)
    def _process():
        _stripe_body(
            keys_ref, vals_ref, t_ref, q_ref, *rest,
            block_b=block_b, npb=npb, ns=ns,
        )


def _apply_kernel_pipelined(
    lo_ref,      # scalar prefetch: [n_windows] first bucket block of window
    hi_ref,      # scalar prefetch: [n_windows] last  bucket block of window
    t_ref,
    q_ref,
    keys_hbm,    # [nb_blocks, BB, S] FULL key stripes, HBM-resident (ANY)
    vals_hbm,    # [nb_blocks, BB, S]
    *rest,       # the remaining blocked inputs/outputs, then the scratch:
    #              kscr/vscr [2, BB, S] two-slot VMEM stripes, ksem/vsem
    #              DMA semaphores [2], the column scratch
    block_b: int,
    npb: int,
    ns: int,
    nb_blocks: int,
    n_windows: int,
):
    """Double-buffered variant: explicit two-slot bucket-stripe staging.

    The grid is sequential (``dimension_semantics=("arbitrary",
    "arbitrary")``), so scratch persists across steps: at linear step ``s``
    the kernel *starts* the async HBM→VMEM copy of step ``s+1``'s stripe
    block into slot ``(s+1) % 2``, then *waits* on slot ``s % 2`` — whose
    copy was issued one step earlier — and computes from it.  The next
    stripe's DMA therefore overlaps this stripe's merge/delete/read
    compute, which is the PR-10 pipelining contract (DESIGN.md §16).  Block
    indices are clipped exactly as the single-buffer BlockSpec index map
    clips them, and the stripe maths is `_stripe_body`, shared verbatim —
    the two variants are byte-identical by construction.

    The wait is unconditional (inactive steps still staged their block):
    every started copy is consumed, so semaphore counts can never leak into
    a later step.
    """
    j = pl.program_id(0)
    i = pl.program_id(1)
    kscr, vscr, ksem, vsem, cols = rest[-5:]
    rest = (*rest[:-5], cols)
    step = j * nb_blocks + i
    slot = jax.lax.rem(step, 2)

    def block_of(jj, ii):
        return jnp.clip(ii, lo_ref[jj], hi_ref[jj])

    def copies(b, sl):
        return (
            pltpu.make_async_copy(keys_hbm.at[b], kscr.at[sl], ksem.at[sl]),
            pltpu.make_async_copy(vals_hbm.at[b], vscr.at[sl], vsem.at[sl]),
        )

    @pl.when(step == 0)
    def _warm_up():
        for c in copies(block_of(j, i), slot):
            c.start()

    @pl.when(step + 1 < n_windows * nb_blocks)
    def _prefetch_next():
        nj = jnp.where(i + 1 < nb_blocks, j, j + 1)
        ni = jnp.where(i + 1 < nb_blocks, i + 1, 0)
        for c in copies(block_of(nj, ni), jax.lax.rem(step + 1, 2)):
            c.start()

    for c in copies(block_of(j, i), slot):
        c.wait()

    _init_outputs(j, i, *rest[-5:-1])
    active = (i >= lo_ref[j]) & (i <= hi_ref[j])

    @pl.when(active)
    def _process():
        _stripe_body(
            kscr.at[slot], vscr.at[slot], t_ref, q_ref, *rest,
            block_b=block_b, npb=npb, ns=ns,
        )


def apply_call(
    lo,          # [n_windows] first bucket block of each window (scalar prefetch)
    hi,          # [n_windows] last bucket block of each window
    tags,        # [n_windows, 1, QB] op tags
    keys,        # [n_windows, 1, QB] sorted op keys
    stripe_k,    # [nb_blocks, BB, S] bucket key stripes
    stripe_v,    # [nb_blocks, BB, S]
    node_max,    # [nb_blocks, BB, npb]
    ik,          # [nb_blocks, BB, S] per-bucket INSERT keys
    iv,          # [nb_blocks, BB, S]
    dk,          # [nb_blocks, BB, S] per-bucket DELETE keys
    fences,      # [nb_blocks, BB, N_FENCE_ROWS]
    g_row,       # [1, MR] per-RANGE-slot global ranks
    *,
    ns: int,
    interpret: bool,
    pipeline: bool,
):
    """The fused kernel's ``pallas_call`` on pre-blocked operands.

    Returns ``(keys, vals, node_count, node_max, meta, value, succ_key,
    range_key, range_val)`` in the same blocked layouts.  Split out of the
    wrapper so the kernel can be compiled for a described chip from shapes
    alone (``tests/test_chip_compile.py``).
    """
    n_windows, _, block_q = tags.shape
    nb_blocks, block_b, S = stripe_k.shape
    npb = S // ns
    cap = ik.shape[2]
    mrp = g_row.shape[1]

    def bucket_map(j, i, lo_ref, hi_ref):
        return (jnp.clip(i, lo_ref[j], hi_ref[j]), 0, 0)

    def window_map(j, i, lo_ref, hi_ref):
        return (j, 0, 0)

    def per_bucket(width):
        return pl.BlockSpec((None, block_b, width), bucket_map)

    window_spec = pl.BlockSpec((None, 1, block_q), window_map)
    range_spec = pl.BlockSpec((1, mrp), lambda j, i, lo, hi: (0, 0))

    cols = pltpu.VMEM((N_COLS, S, 1), jnp.int32)

    # the pipelined variant stages the big stripe planes itself: keys/vals
    # stay HBM-resident (ANY memory space) and a two-slot VMEM scratch +
    # DMA semaphore pair per plane double-buffers them across grid steps;
    # everything else keeps the automatic BlockSpec pipeline either way
    if pipeline:
        stripe_specs = [
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ]
        scratch_shapes = [
            pltpu.VMEM((2, block_b, S), jnp.int32),
            pltpu.VMEM((2, block_b, S), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            cols,
        ]
        kernel = functools.partial(
            _apply_kernel_pipelined,
            block_b=block_b,
            npb=npb,
            ns=ns,
            nb_blocks=nb_blocks,
            n_windows=n_windows,
        )
    else:
        stripe_specs = [per_bucket(S), per_bucket(S)]
        scratch_shapes = [cols]
        kernel = functools.partial(_apply_kernel, block_b=block_b, npb=npb, ns=ns)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_windows, nb_blocks),
        in_specs=[
            window_spec,
            window_spec,
            *stripe_specs,
            per_bucket(npb),
            per_bucket(cap),
            per_bucket(cap),
            per_bucket(cap),
            per_bucket(N_FENCE_ROWS),
            range_spec,
        ],
        out_specs=[
            per_bucket(S),
            per_bucket(S),
            per_bucket(npb),
            per_bucket(npb),
            per_bucket(3),
            window_spec,
            window_spec,
            range_spec,
            range_spec,
        ],
        scratch_shapes=scratch_shapes,
    )

    i32 = jnp.int32
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nb_blocks, block_b, S), i32),
            jax.ShapeDtypeStruct((nb_blocks, block_b, S), i32),
            jax.ShapeDtypeStruct((nb_blocks, block_b, npb), i32),
            jax.ShapeDtypeStruct((nb_blocks, block_b, npb), i32),
            jax.ShapeDtypeStruct((nb_blocks, block_b, 3), i32),
            jax.ShapeDtypeStruct((n_windows, 1, block_q), i32),
            jax.ShapeDtypeStruct((n_windows, 1, block_q), i32),
            jax.ShapeDtypeStruct((1, mrp), i32),
            jax.ShapeDtypeStruct((1, mrp), i32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
    )(lo, hi, tags, keys, stripe_k, stripe_v, node_max, ik, iv, dk, fences, g_row)


def _surviving_min(keys2d, vals2d, hit, bkt, slot):
    """Each bucket's smallest stored key that the batch's deletes leave, and
    its value, from the delete lanes' own slots: ``(min [nb], val [nb],
    buckets recomputed)``.

    ``keys2d``/``vals2d`` are the stripes ``[nb, S]``; lane ``i`` of the
    sorted delete keys is stored where ``hit[i]``, at stripe slot
    ``slot[i]`` of bucket ``bkt[i]``.  A bucket without such a lane keeps
    its stored minimum, slot 0 (I1–I2; EMPTY when the bucket is empty).  A
    bucket with one is recomputed once, in the row of its first hit lane:
    the keys are sorted, so the buckets of the hit lanes never decrease
    and each bucket's deletes form one run.  Costs O(nb + n·S) for n lanes,
    where a search of every stored slot would cost O(nb·S·log n)."""
    nb, S = keys2d.shape
    n = hit.shape[0]
    lane = jnp.arange(n, dtype=jnp.int32)
    seen = jax.lax.cummax(jnp.where(hit, bkt, -1))
    prev = jnp.concatenate([jnp.full((min(n, 1),), -1, jnp.int32), seen[:-1]])
    first = hit & (bkt > prev)                 # first hit lane of its bucket
    run = jax.lax.cummax(jnp.where(first, lane, 0))
    drop = (
        jnp.zeros((n, S), jnp.bool_)
        .at[jnp.where(hit, run, n), slot]
        .set(True, mode="drop")
    )
    rows = jnp.where(drop, EMPTY, keys2d[bkt])  # [n, S]: lane i's bucket
    row_min = jnp.min(rows, axis=1)
    row_val = vals2d[bkt, jnp.argmin(rows, axis=1)]
    fix = jnp.where(first, bkt, nb)
    surv_min = keys2d[:, 0].at[fix].set(row_min, mode="drop")
    surv_val = vals2d[:, 0].at[fix].set(row_val, mode="drop")
    return surv_min, surv_val, jnp.sum(first).astype(jnp.int32)


def _fused_apply(
    state, tag, key, val, *, block_q, block_b, max_results, interpret, pipeline
):
    """Trace the fused apply: returns (new_state, results, stats)."""
    from repro.core.ops import derive_type_views
    from repro.core.query import (
        _suffix_min_with_index,
        flat_rank,
        locate_stored,
        range_offsets,
        range_slot_ranks,
    )

    nb, npb, ns = state.num_buckets, state.nodes_per_bucket, state.node_size
    cap = state.bucket_capacity
    S = npb * ns
    n = key.shape[0]

    # --- the single routing + derived per-type views (shared with the
    # reference engine, so the routing contract cannot diverge) ------------
    with jax.named_scope("flix.fused.route"):
        _, _, ins_keys, ins_vals, del_keys, ins_starts, ins_ends = (
            derive_type_views(state, tag, key, val)
        )
        true_counts = (ins_ends - ins_starts).astype(jnp.int32)

        # per-bucket INSERT tiles (keys + aligned vals)
        ik, iv, _, _ = gather_kv_sublists(ins_keys, ins_vals, ins_starts, ins_ends, cap)

    with jax.named_scope("flix.fused.delete_tiles"):
        # per-bucket DELETE tiles, pre-filtered to PRESENT keys so each bucket's
        # sublist fits its capacity tile (same trick as flix_delete; filtering
        # against the pre-insert state is exact because one batch never inserts
        # and deletes the same key).  The located slots of the present keys
        # also serve the fence rows and the RANGE plumbing below.
        present, dbkt, dnode, dpos = locate_stored(state, del_keys)
        dslot = dnode * ns + dpos
        dk_sorted = jnp.sort(jnp.where(present, del_keys, EMPTY))
        dstarts, dends = bucket_slices(state, dk_sorted)
        dk_tile, _, _ = gather_sublists(dk_sorted, dstarts, dends, cap)

    # --- post-update successor fence rows (one O(nb) suffix scan) ---------
    with jax.named_scope("flix.fused.fence_rows"):
        # the stripes in row-major [nb, S], made once for every reader below
        # and the kernel: left alone, XLA fuses the layout change into each
        # reader, and at 2^23 keys the v5e compile then counts 12.3 GiB of
        # temporaries against 6.3 GiB with the one copy
        flat_k, flat_v = jax.lax.optimization_barrier(
            (state.keys.reshape(nb, S), state.vals.reshape(nb, S))
        )
        surv_min, surv_val, min_fixups = _surviving_min(
            flat_k, flat_v, present, dbkt, dslot
        )
        ins_min = ik[:, 0]                       # tiles are sorted, EMPTY-padded
        ins_val = iv[:, 0]
        bucket_min = jnp.minimum(surv_min, ins_min)
        # tie (same key upserted) → the incoming value wins
        min_val = jnp.where(ins_min <= surv_min, ins_val, surv_val)
        smin, sidx = _suffix_min_with_index(bucket_min)
        next_key = jnp.concatenate([smin[1:], jnp.array([EMPTY], KEY_DTYPE)])
        next_idx = jnp.concatenate([sidx[1:], jnp.array([0], jnp.int32)])
        next_val = min_val[next_idx]

    # --- post-update RANGE rank fences + per-slot ranks -------------------
    # same predict-without-running-the-update argument as the fence rows,
    # extended to the whole multiset: post-update bucket contents are
    # (survivors minus upsert duplicates) ∪ (insert slice) — exact because
    # one batch never inserts and deletes the same key.  Sorting those rows
    # gives per-bucket rank fences and every op's [lo, hi) full count; the
    # shared core.query formulas then fix the dense output layout.
    is_range = tag == _OP_RANGE

    with jax.named_scope("flix.fused.range_plumbing"):
        def _range_plumbing():
            # the stripes less the batch's present deletes and its upserted
            # keys, each emptied at its own slot (the insert tiles bring the
            # upserts back with their new values)
            upserted, ubkt, unode, upos = locate_stored(state, ins_keys)
            gone = jnp.concatenate([present, upserted])
            rows = jnp.where(gone, jnp.concatenate([dbkt, ubkt]), nb)
            cols = jnp.concatenate([dslot, unode * ns + upos])
            post_rows = jnp.concatenate(
                [flat_k.at[rows, cols].set(EMPTY, mode="drop"), ik], axis=1
            )
            post_sorted = jnp.sort(post_rows, axis=1)
            live_post = jnp.sum(post_sorted != EMPTY, axis=1).astype(jnp.int32)
            pref_post = jnp.concatenate(
                [jnp.zeros((1,), jnp.int32), jnp.cumsum(live_post).astype(jnp.int32)]
            )
            rank_lo = flat_rank(post_sorted, pref_post, state.mkba, key)
            rank_hi = flat_rank(
                post_sorted, pref_post, state.mkba, val.astype(KEY_DTYPE)
            )
            full = jnp.maximum(rank_hi - rank_lo, 0)
            rstart, remit, total_emit, rtrunc = range_offsets(
                full, is_range, max_results
            )
            g = range_slot_ranks(rank_lo, rstart, total_emit, max_results)
            return g, pref_post[:-1], pref_post[1:], rstart, remit, rtrunc

        # a batch with no RANGE ops skips the per-bucket post-state sort and
        # rank scans entirely (lax.cond executes one branch — no host sync, and
        # update-only fused steps keep their PR-2 cost); all slots dead (-1)
        # makes the kernel's pl.when skip the phase-4 gather compute too
        g, ps_row_post, pe_row_post, rstart, remit, rtrunc = jax.lax.cond(
            jnp.any(is_range),
            _range_plumbing,
            lambda: (
                jnp.full((max_results,), -1, jnp.int32),
                jnp.zeros((nb,), jnp.int32),
                jnp.zeros((nb,), jnp.int32),
                jnp.zeros((n,), jnp.int32),
                jnp.zeros((n,), jnp.int32),
                jnp.int32(0),
            ),
        )

    # --- pad buckets to a block multiple (EMPTY stripes merge to EMPTY) ---
    with jax.named_scope("flix.fused.layout"):
        nb_p = pl.cdiv(nb, block_b) * block_b
        keys2d, vals2d, node_max, mkba = flat_k, flat_v, state.node_max, state.mkba
        if nb_p != nb:
            pad = nb_p - nb
            keys2d = jnp.pad(keys2d, ((0, pad), (0, 0)), constant_values=EMPTY)
            vals2d = jnp.pad(vals2d, ((0, pad), (0, 0)))
            node_max = jnp.pad(node_max, ((0, pad), (0, 0)), constant_values=EMPTY)
            mkba = jnp.pad(mkba, (0, pad), constant_values=EMPTY - 1)
            ik = jnp.pad(ik, ((0, pad), (0, 0)), constant_values=EMPTY)
            iv = jnp.pad(iv, ((0, pad), (0, 0)))
            dk_tile = jnp.pad(dk_tile, ((0, pad), (0, 0)), constant_values=EMPTY)
            next_key = jnp.pad(next_key, (0, pad), constant_values=EMPTY)
            next_val = jnp.pad(next_val, (0, pad))
            # padded buckets own no ranks: empty [total, total) spans
            total_post = pe_row_post[-1]
            ps_row_post = jnp.concatenate(
                [ps_row_post, jnp.full((pad,), total_post, jnp.int32)]
            )
            pe_row_post = jnp.concatenate(
                [pe_row_post, jnp.full((pad,), total_post, jnp.int32)]
            )
        lfence = jnp.concatenate(
            [jnp.array([jnp.iinfo(jnp.int32).min], KEY_DTYPE), mkba[:-1]]
        )
        mrp = pl.cdiv(max_results, 128) * 128
        g_row = jnp.pad(g, (0, mrp - max_results), constant_values=-1).reshape(1, mrp)

        # --- pad ops to a window multiple (NOP pads never match) --------------
        qp = pl.cdiv(max(n, 1), block_q) * block_q
        from repro.core.ops import OP_NOP

        tpad = jnp.pad(tag, (0, qp - n), constant_values=OP_NOP)
        qpad = jnp.pad(key.astype(KEY_DTYPE), (0, qp - n), constant_values=EMPTY)
        n_windows = qp // block_q

        # per-window bucket-block bounds; window 0 widens to the full sweep —
        # that is where every stripe's update pass is guaranteed to happen.
        q2 = qpad.reshape(n_windows, block_q)
        first_b = jnp.searchsorted(mkba, q2[:, 0], side="left")
        last_b = jnp.searchsorted(mkba, q2[:, -1], side="left")
        nb_blocks = nb_p // block_b
        lo = jnp.minimum(first_b, nb_p - 1).astype(jnp.int32) // block_b
        hi = jnp.minimum(last_b, nb_p - 1).astype(jnp.int32) // block_b
        lo = lo.at[0].set(0)
        hi = hi.at[0].set(nb_blocks - 1)

        # Mosaic takes a block whose last two dims are (8, 128)-aligned or span
        # the whole array, so every blocked array gets a leading grid axis and
        # full trailing dims: per-bucket planes [nb_blocks, BB, W] (fences and
        # the node-count/overflow/deleted triple included), op windows
        # [n_windows, 1, QB].
        def by_block(x):
            return x.reshape(nb_blocks, block_b, x.shape[1])

        fences = jnp.stack(
            [mkba, lfence, next_key, next_val, ps_row_post, pe_row_post], axis=1
        ).astype(jnp.int32)                        # [nb_p, N_FENCE_ROWS]
        operands = (
            lo,
            hi,
            tpad.reshape(n_windows, 1, block_q),
            qpad.reshape(n_windows, 1, block_q),
            by_block(keys2d),
            by_block(vals2d),
            by_block(node_max),
            by_block(ik),
            by_block(iv),
            by_block(dk_tile),
            by_block(fences),
            g_row,
        )

    okeys, ovals, ocnt, omax, bmeta, resv, resk, rngk, rngv = apply_call(
        *operands, ns=ns, interpret=interpret, pipeline=pipeline
    )
    with jax.named_scope("flix.fused.state_out"):
        bmeta = bmeta.reshape(nb_p, 3)[:nb]
        onn, oflow, odel = bmeta[:, 0], bmeta[:, 1], bmeta[:, 2]

        slice_overflow = true_counts > cap
        any_overflow = (jnp.sum(oflow) > 0) | jnp.any(slice_overflow)
        new_state = FliXState(
            keys=okeys.reshape(nb_p, npb, ns)[:nb],
            vals=ovals.reshape(nb_p, npb, ns)[:nb],
            node_count=ocnt.reshape(nb_p, npb)[:nb],
            node_max=omax.reshape(nb_p, npb)[:nb],
            num_nodes=onn,
            mkba=state.mkba,
            needs_restructure=state.needs_restructure | any_overflow,
        )
        results = {
            "value": resv.reshape(qp)[:n],
            "succ_key": resk.reshape(qp)[:n],
            "range_key": rngk[0, :max_results],
            "range_val": rngv[0, :max_results],
            "range_start": jnp.where(is_range, rstart, 0),
            "range_count": jnp.where(is_range, remit, 0),
        }
        stats = {
            "inserted": jnp.sum(jnp.minimum(true_counts, cap)),
            "deleted": jnp.sum(odel),
            "overflowed_buckets": jnp.sum((oflow > 0) | slice_overflow),
            "range_truncated": rtrunc,
            # buckets that ran the kernel's merge/delete phases: those whose
            # INSERT or DELETE tile holds a key, i.e. whose slice is not
            # empty.  Counted from the slice bounds, not from the tiles: a
            # read of a tile here would keep that 1 GiB plane (at 2^23 keys)
            # alive past the kernel, beyond what a v5e can load
            "updated_buckets": jnp.sum((true_counts > 0) | (dends > dstarts)),
            # buckets whose post-update minimum was recomputed: those holding
            # a present DELETE (counted from the delete lanes, O(n))
            "delete_min_fixups": min_fixups,
        }
    return new_state, results, stats


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_b", "max_results", "interpret", "pipeline"),
)
def flix_apply_pallas(
    state: FliXState,
    tag: jax.Array,
    key: jax.Array,
    val: jax.Array,
    *,
    block_q: int = DEFAULT_BLOCK_Q,
    block_b: int = DEFAULT_BLOCK_B,
    max_results: int = 128,
    interpret: bool = False,
    pipeline: bool = False,
):
    """Fused mixed-batch apply.  Same contract as ``core.ops.apply_ops``.

    ``pipeline=True`` selects the double-buffered bucket-stripe variant
    (`_apply_kernel_pipelined`): explicit two-slot scratch + async-copy
    staging so the next stripe's HBM→VMEM transfer overlaps the current
    stripe's compute.  Byte-identical to ``pipeline=False`` — the stripe
    maths is shared — and works in interpret mode, which is how the
    differential suite proves it off-TPU."""
    return _fused_apply(
        state,
        tag,
        key,
        val,
        block_q=block_q,
        block_b=block_b,
        max_results=max_results,
        interpret=interpret,
        pipeline=pipeline,
    )


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_b", "max_results", "interpret", "pipeline"),
    donate_argnums=(0,),
)
def flix_apply_pallas_donated(
    state: FliXState,
    tag: jax.Array,
    key: jax.Array,
    val: jax.Array,
    *,
    block_q: int = DEFAULT_BLOCK_Q,
    block_b: int = DEFAULT_BLOCK_B,
    max_results: int = 128,
    interpret: bool = False,
    pipeline: bool = False,
):
    """Donating variant: the input state's buffers are handed to XLA so step
    N+1's stripes reuse step N's allocation instead of copying.  The caller
    must not touch ``state`` afterwards — in particular the restructure-and-
    retry driver (``apply_ops_safe``) must use the non-donating entry, since
    a retry replays the batch on the *pre-batch* state."""
    return _fused_apply(
        state,
        tag,
        key,
        val,
        block_q=block_q,
        block_b=block_b,
        max_results=max_results,
        interpret=interpret,
        pipeline=pipeline,
    )
