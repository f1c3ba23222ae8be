"""Pallas TPU kernel for TL-Bulk insertion (paper §4.3.2, Table 2).

Per bucket, entirely in VMEM, matching ``core.insert`` bit-for-bit:

  1. upsert-dedup: stripe keys that reappear in the incoming sublist are
     dropped (the incoming value wins) — broadcast equality, the tile-ballot
     analogue of Table 2's per-thread ownership comparisons,
  2. merged ranks by compare-count (no sort needed in-kernel: both sides are
     sorted, so rank(z) = #kept-stripe< z + #incoming< z),
  3. original node regions keep their boundaries; a region that overflows
     splits into balanced pieces (the batched fixed point of the paper's
     split-in-half rule; identical formulas to core/insert.py),
  4. one-hot reposition into the new stripe + metadata recompute.

The wrapper pulls per-bucket sublists (flipped-indexing boundaries) and
reports per-bucket overflow; callers use the same restructure-and-retry
contract as ``core.insert_safe``.

VMEM per step (BB=1): stripe (npb·ns) + incoming tile (cap) + the [L, S]
reposition mask with L = 2·cap, S = npb·ns — ≈ 2.5 MB at cap 512.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.batch import bucket_slices, gather_kv_sublists
from repro.core.state import KEY_DTYPE, VAL_DTYPE, FliXState

_EMPTY = int(jnp.iinfo(jnp.int32).max)


def _insert_kernel(
    keys_ref,   # [1, npb*ns] stripe (chain order, per-node EMPTY padding)
    vals_ref,   # [1, npb*ns]
    nmax_ref,   # [1, npb] node maxes (EMPTY when inactive)
    ik_ref,     # [1, cap] sorted incoming keys (EMPTY-padded)
    iv_ref,     # [1, cap]
    okeys_ref,  # [1, npb*ns]
    ovals_ref,  # [1, npb*ns]
    ocnt_ref,   # [1, npb]
    omax_ref,   # [1, npb]
    onn_ref,    # [1, 1]
    oflow_ref,  # [1, 1]  bucket overflow flag
    *,
    npb: int,
    ns: int,
    cap: int,
):
    A = keys_ref[0, :]                    # stripe keys  [S]
    Av = vals_ref[0, :]
    B = ik_ref[0, :]                      # incoming     [cap]
    Bv = iv_ref[0, :]
    nmax = nmax_ref[0, :]                 # [npb]
    S = npb * ns

    validA = A != _EMPTY
    validB = B != _EMPTY
    dupA = jnp.any(A[:, None] == B[None, :], axis=1) & validA
    keepA = validA & ~dupA

    # merged ranks by compare-count (both sides sorted & unique)
    lessA_A = jnp.sum((A[None, :] < A[:, None]) & keepA[None, :], axis=1)
    lessB_A = jnp.sum((B[None, :] < A[:, None]) & validB[None, :], axis=1)
    rankA = lessA_A + lessB_A                                   # [S]
    lessA_B = jnp.sum((A[None, :] < B[:, None]) & keepA[None, :], axis=1)
    lessB_B = jnp.sum((B[None, :] < B[:, None]) & validB[None, :], axis=1)
    rankB = lessA_B + lessB_B                                   # [cap]

    # original node regions (fixed boundaries; last region open-ended)
    onn = jnp.sum((nmax != _EMPTY).astype(jnp.int32))
    onn_c = jnp.maximum(onn - 1, 0)

    def region_of(z):
        return jnp.minimum(
            jnp.sum((nmax[None, :] < z[:, None]).astype(jnp.int32), axis=1),
            onn_c,
        )

    regA = region_of(A)
    regB = region_of(B)

    # per-region sizes over kept elements
    iota_r = jax.lax.broadcasted_iota(jnp.int32, (1, npb), 1)[0]
    mA = jnp.sum((regA[:, None] == iota_r[None, :]) & keepA[:, None], axis=0)
    mB = jnp.sum((regB[:, None] == iota_r[None, :]) & validB[:, None], axis=0)
    m_j = (mA + mB).astype(jnp.int32)                            # [npb]
    s_j = (m_j + ns - 1) // ns
    f_j = jnp.cumsum(m_j) - m_j
    base_j = jnp.cumsum(s_j) - s_j
    total_new = jnp.sum(s_j)

    def dest_of(rank, reg, keep):
        # balanced split within each region (same formulas as core/insert)
        oh = reg[:, None] == iota_r[None, :]
        m_r = jnp.maximum(jnp.sum(jnp.where(oh, m_j[None, :], 0), axis=1), 1)
        s_r = jnp.maximum(jnp.sum(jnp.where(oh, s_j[None, :], 0), axis=1), 1)
        f_r = jnp.sum(jnp.where(oh, f_j[None, :], 0), axis=1)
        b_r = jnp.sum(jnp.where(oh, base_j[None, :], 0), axis=1)
        rr = rank - f_r
        piece = (rr * s_r) // m_r
        start = (piece * m_r + s_r - 1) // s_r
        pos = rr - start
        slot = b_r + piece
        return jnp.where(keep & (slot < npb), slot * ns + pos, S)

    destA = dest_of(rankA, regA, keepA)
    destB = dest_of(rankB, regB, validB)

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)[0]
    ohA = destA[:, None] == lane[None, :]                        # [S, S]
    ohB = destB[:, None] == lane[None, :]                        # [cap, S]
    nk = jnp.sum(jnp.where(ohA, A[:, None], 0), axis=0) + jnp.sum(
        jnp.where(ohB, B[:, None], 0), axis=0
    )
    nv = jnp.sum(jnp.where(ohA, Av[:, None], 0), axis=0) + jnp.sum(
        jnp.where(ohB, Bv[:, None], 0), axis=0
    )
    filled = jnp.any(ohA, axis=0) | jnp.any(ohB, axis=0)
    nk = jnp.where(filled, nk, _EMPTY)
    nv = jnp.where(filled, nv, 0)

    okeys_ref[0, :] = nk
    ovals_ref[0, :] = nv

    rows = nk.reshape(npb, ns)
    cnt = jnp.sum((rows != _EMPTY).astype(jnp.int32), axis=1)
    last = jnp.maximum(cnt - 1, 0)
    lane2 = jax.lax.broadcasted_iota(jnp.int32, (npb, ns), 1)
    nmax_new = jnp.sum(jnp.where(lane2 == last[:, None], rows, 0), axis=1)
    ocnt_ref[0, :] = cnt
    omax_ref[0, :] = jnp.where(cnt > 0, nmax_new, _EMPTY)
    onn_ref[0, 0] = jnp.sum((cnt > 0).astype(jnp.int32))
    oflow_ref[0, 0] = (total_new > npb).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def flix_insert_pallas(
    state: FliXState,
    sorted_keys: jax.Array,
    sorted_vals: jax.Array,
    *,
    interpret: bool = False,
):
    """TL-Bulk insertion via the Pallas kernel.

    Returns (new_state, per-bucket overflow counts).  Same contract as
    ``core.insert``: on overflow the caller retries after restructuring.
    """
    nb, npb, ns = state.num_buckets, state.nodes_per_bucket, state.node_size
    cap = state.bucket_capacity
    keys_in = sorted_keys.astype(KEY_DTYPE)
    vals_in = sorted_vals.astype(VAL_DTYPE)

    starts, ends = bucket_slices(state, keys_in)
    ik, iv, _, true_counts = gather_kv_sublists(keys_in, vals_in, starts, ends, cap)

    grid = (nb,)

    def row(i):
        return (i, 0)

    okeys, ovals, ocnt, omax, onn, oflow = pl.pallas_call(
        functools.partial(_insert_kernel, npb=npb, ns=ns, cap=cap),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, npb * ns), row),
            pl.BlockSpec((1, npb * ns), row),
            pl.BlockSpec((1, npb), row),
            pl.BlockSpec((1, cap), row),
            pl.BlockSpec((1, cap), row),
        ],
        out_specs=[
            pl.BlockSpec((1, npb * ns), row),
            pl.BlockSpec((1, npb * ns), row),
            pl.BlockSpec((1, npb), row),
            pl.BlockSpec((1, npb), row),
            pl.BlockSpec((1, 1), row),
            pl.BlockSpec((1, 1), row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, npb * ns), jnp.int32),
            jax.ShapeDtypeStruct((nb, npb * ns), jnp.int32),
            jax.ShapeDtypeStruct((nb, npb), jnp.int32),
            jax.ShapeDtypeStruct((nb, npb), jnp.int32),
            jax.ShapeDtypeStruct((nb, 1), jnp.int32),
            jax.ShapeDtypeStruct((nb, 1), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
    )(
        state.keys.reshape(nb, npb * ns),
        state.vals.reshape(nb, npb * ns),
        state.node_max,
        ik,
        iv,
    )

    slice_overflow = true_counts > cap
    any_overflow = (jnp.sum(oflow) > 0) | jnp.any(slice_overflow)
    new_state = FliXState(
        keys=okeys.reshape(nb, npb, ns),
        vals=ovals.reshape(nb, npb, ns),
        node_count=ocnt,
        node_max=omax,
        num_nodes=onn[:, 0],
        mkba=state.mkba,
        needs_restructure=state.needs_restructure | any_overflow,
    )
    return new_state, oflow[:, 0] + slice_overflow.astype(jnp.int32)
