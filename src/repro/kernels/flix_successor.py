"""Pallas TPU kernel for FliX flipped successor queries (paper §3.3 applied
to the ordered-CDS capability hash-table competitors lack).

Same compute-to-bucket mapping as ``flix_query``:

  * grid = (query windows, bucket blocks); scalar-prefetched per-window
    bucket-block bounds clamp out-of-range steps so they issue no DMA and
    skip compute,
  * inside the kernel the in-bucket candidate is the standard compare-count
    pair (node by node-max votes, position by key votes) plus exact one-hot
    gathers,
  * the out-of-bucket candidate (bucket's largest present key < q) cannot be
    resolved block-locally — the next non-empty bucket may live in a later
    block — so the wrapper precomputes two per-bucket fence-like rows with
    one O(nb) suffix scan: ``next_key[b]`` / ``next_val[b]`` = the smallest
    key (and its value) stored in any bucket after ``b``.  They stream
    through the same fence BlockSpec as the MKBA row, and the kernel picks
    in-bucket vs next-bucket per query.

Semantics are identical to ``core.query.successor_query``:
returns (succ_key | EMPTY, succ_val | NOT_FOUND) per query.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flix_query import (
    DEFAULT_BLOCK_B,
    DEFAULT_BLOCK_Q,
    _exact_gather_i32,
)
from repro.core.state import EMPTY, KEY_DTYPE

_EMPTY = int(jnp.iinfo(jnp.int32).max)
_MISS = -1


def _successor_kernel(
    lo_ref,      # scalar prefetch: [n_windows] first bucket block of window
    hi_ref,      # scalar prefetch: [n_windows] last  bucket block of window
    q_ref,       # [1, QB] sorted queries for window j
    keys_ref,    # [BB, npb*ns] bucket-block key stripes (chain order)
    vals_ref,    # [BB, npb*ns]
    nmax_ref,    # [BB, npb] per-node max keys (EMPTY when inactive)
    mkba_ref,    # [1, BB] bucket fences for the block
    lf_ref,      # [1, BB] lower fences
    nxk_ref,     # [1, BB] smallest key stored after bucket b (EMPTY if none)
    nxv_ref,     # [1, BB] its value
    outk_ref,    # [1, QB] successor keys / EMPTY
    outv_ref,    # [1, QB] successor values / NOT_FOUND
    *,
    block_b: int,
    npb: int,
    ns: int,
):
    j = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        outk_ref[...] = jnp.full_like(outk_ref, _EMPTY)
        outv_ref[...] = jnp.full_like(outv_ref, _MISS)

    active = (i >= lo_ref[j]) & (i <= hi_ref[j])

    @pl.when(active)
    def _process():
        q = q_ref[0, :]                                   # [QB]
        qcol = q[:, None]                                 # [QB, 1]

        # which local bucket owns each query (compare-count over fences)
        mkba = mkba_ref[0, :][None, :]                    # [1, BB]
        b_local = jnp.sum(mkba < qcol, axis=1)            # [QB]
        lf = lf_ref[0, :][None, :]
        b_sel = jnp.minimum(b_local, block_b - 1)
        oh_b = (
            jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], block_b), 1)
            == b_sel[:, None]
        )
        lf_q = jnp.sum(jnp.where(oh_b, lf, 0), axis=1)
        mine = (b_local < block_b) & (qcol[:, 0] > lf_q)

        # in-bucket candidate: node by node-max votes, position by key votes
        nmax_rows = _exact_gather_i32(
            oh_b.astype(jnp.float32), nmax_ref[...]
        )                                                  # [QB, npb]
        nidx = jnp.sum(nmax_rows < qcol, axis=1)           # [QB]
        n_active = jnp.sum((nmax_rows != _EMPTY).astype(jnp.int32), axis=1)
        in_bucket = nidx < n_active
        nidx_c = jnp.minimum(nidx, npb - 1)

        flat = b_sel * npb + nidx_c                        # [QB]
        oh_n = (
            jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], block_b * npb), 1)
            == flat[:, None]
        ).astype(jnp.float32)
        krow = _exact_gather_i32(oh_n, keys_ref[...].reshape(block_b * npb, ns))
        vrow = _exact_gather_i32(oh_n, vals_ref[...].reshape(block_b * npb, ns))

        pos = jnp.sum(krow < qcol, axis=1)
        pos_c = jnp.minimum(pos, ns - 1)
        oh_p = (
            jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], ns), 1)
            == pos_c[:, None]
        )
        in_key = jnp.sum(jnp.where(oh_p, krow, 0), axis=1)
        in_val = jnp.sum(jnp.where(oh_p, vrow, 0), axis=1)

        # out-of-bucket candidate: first key after the owning bucket
        nxk = nxk_ref[0, :][None, :]
        nxv = nxv_ref[0, :][None, :]
        out_key = jnp.sum(jnp.where(oh_b, nxk, 0), axis=1)
        out_val = jnp.sum(jnp.where(oh_b, nxv, 0), axis=1)

        use_in = in_bucket & (pos < ns)
        succ_key = jnp.where(use_in, in_key, out_key)
        succ_val = jnp.where(use_in, in_val, out_val)
        found = succ_key != _EMPTY
        succ_val = jnp.where(found, succ_val, _MISS)

        outk_ref[0, :] = jnp.where(mine, succ_key, outk_ref[0, :])
        outv_ref[0, :] = jnp.where(mine, succ_val, outv_ref[0, :])


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_b", "interpret"),
)
def flix_successor_pallas(
    keys3d: jax.Array,      # [nb, npb, ns] int32
    vals3d: jax.Array,      # [nb, npb, ns] int32
    node_max: jax.Array,    # [nb, npb] int32
    mkba: jax.Array,        # [nb] int32
    sorted_queries: jax.Array,  # [Q] int32, ascending
    *,
    block_q: int = DEFAULT_BLOCK_Q,
    block_b: int = DEFAULT_BLOCK_B,
    interpret: bool = False,
):
    nb, npb, ns = keys3d.shape
    qn = sorted_queries.shape[0]

    # per-bucket "first key after b" rows: one O(nb) suffix scan on the host
    # side of the kernel (the same associative scan successor_query uses).
    from repro.core.query import _suffix_min_with_index

    bucket_min = jnp.where(node_max[:, 0] != EMPTY, keys3d[:, 0, 0], EMPTY)
    head_val = vals3d[:, 0, 0]
    smin, sidx = _suffix_min_with_index(bucket_min)
    next_key = jnp.concatenate([smin[1:], jnp.array([EMPTY], KEY_DTYPE)])
    next_idx = jnp.concatenate([sidx[1:], jnp.array([0], jnp.int32)])
    next_val = head_val[next_idx]

    # pad buckets to a block multiple (EMPTY stripes never match)
    nb_p = pl.cdiv(nb, block_b) * block_b
    if nb_p != nb:
        pad = nb_p - nb
        keys3d = jnp.pad(keys3d, ((0, pad), (0, 0), (0, 0)), constant_values=EMPTY)
        vals3d = jnp.pad(vals3d, ((0, pad), (0, 0), (0, 0)))
        node_max = jnp.pad(node_max, ((0, pad), (0, 0)), constant_values=EMPTY)
        mkba = jnp.pad(mkba, (0, pad), constant_values=EMPTY - 1)
        next_key = jnp.pad(next_key, (0, pad), constant_values=EMPTY)
        next_val = jnp.pad(next_val, (0, pad))
    lfence = jnp.concatenate(
        [jnp.array([jnp.iinfo(jnp.int32).min], KEY_DTYPE), mkba[:-1]]
    )

    # pad queries to a window multiple (MAX_VALID pads are sliced off)
    qp = pl.cdiv(max(qn, 1), block_q) * block_q
    q = jnp.pad(
        sorted_queries.astype(KEY_DTYPE), (0, qp - qn), constant_values=EMPTY - 1
    )
    n_windows = qp // block_q
    q2 = q.reshape(n_windows, block_q)

    first_b = jnp.searchsorted(mkba, q2[:, 0], side="left")
    last_b = jnp.searchsorted(mkba, q2[:, -1], side="left")
    lo = jnp.minimum(first_b, nb_p - 1).astype(jnp.int32) // block_b
    hi = jnp.minimum(last_b, nb_p - 1).astype(jnp.int32) // block_b

    nb_blocks = nb_p // block_b
    keys2d = keys3d.reshape(nb_p, npb * ns)
    vals2d = vals3d.reshape(nb_p, npb * ns)
    mkba_row = mkba.reshape(1, nb_p)
    lf_row = lfence.reshape(1, nb_p)
    nxk_row = next_key.reshape(1, nb_p)
    nxv_row = next_val.reshape(1, nb_p)

    def bucket_map(j, i, lo_ref, hi_ref):
        return (jnp.clip(i, lo_ref[j], hi_ref[j]), 0)

    def fence_map(j, i, lo_ref, hi_ref):
        return (0, jnp.clip(i, lo_ref[j], hi_ref[j]))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_windows, nb_blocks),
        in_specs=[
            pl.BlockSpec((1, block_q), lambda j, i, lo, hi: (j, 0)),
            pl.BlockSpec((block_b, npb * ns), bucket_map),
            pl.BlockSpec((block_b, npb * ns), bucket_map),
            pl.BlockSpec((block_b, npb), bucket_map),
            pl.BlockSpec((1, block_b), fence_map),
            pl.BlockSpec((1, block_b), fence_map),
            pl.BlockSpec((1, block_b), fence_map),
            pl.BlockSpec((1, block_b), fence_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q), lambda j, i, lo, hi: (j, 0)),
            pl.BlockSpec((1, block_q), lambda j, i, lo, hi: (j, 0)),
        ],
    )

    outk, outv = pl.pallas_call(
        functools.partial(_successor_kernel, block_b=block_b, npb=npb, ns=ns),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_windows, block_q), jnp.int32),
            jax.ShapeDtypeStruct((n_windows, block_q), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
    )(lo, hi, q2, keys2d, vals2d, node_max, mkba_row, lf_row, nxk_row, nxv_row)
    return outk.reshape(qp)[:qn], outv.reshape(qp)[:qn]
