"""Pallas TPU kernel for FliX flipped point queries (paper §3.3, Figure 4).

Compute-to-bucket mapping on a TPU:

  * grid = (query windows, bucket blocks).  The window dimension is outer,
    so each (1, QB) query block and its output stay VMEM-resident while the
    bucket blocks that window needs stream through.
  * scalar-prefetched per-window bucket-block bounds ``lo[j]``/``hi[j]``
    drive the bucket BlockSpec index_map: steps outside a window's range
    *clamp to the boundary block index*, so Pallas issues **no DMA** for
    them (same-index blocks are not refetched) and ``pl.when`` skips the
    compute — the TPU analogue of the paper's "bucket with no queries
    terminates immediately".
  * inside the kernel every lookup is a compare-count (the tile-ballot
    analogue) plus a one-hot MXU matmul gather: int32 rows are split into
    two exact f16-range halves so the gather is exact in f32 arithmetic —
    this is the TPU-idiomatic replacement for the warp's per-thread gather.

VMEM working set per step: QB queries + one (BB, npb, ns) bucket stripe
(keys+vals) + (BB, npb) node maxes + fences — all shaped by the BlockSpecs
below; defaults (QB=128, BB=8, npb≤32, ns≤64) stay well under 1 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.state import EMPTY, KEY_DTYPE

DEFAULT_BLOCK_Q = 128   # queries per window
DEFAULT_BLOCK_B = 8     # buckets per bucket block
_MISS = -1              # NOT_FOUND as a Python literal (kernels must not
                        # capture traced constants)


def _exact_gather_i32(onehot_f32: jax.Array, table_i32: jax.Array) -> jax.Array:
    """Exact int32 row gather as two f32 MXU matmuls (hi/lo 16-bit split).

    The halves are split with an int32 mask and a logical shift — Mosaic
    has no uint32 ↔ f32 cast — and each is < 2^16, so the one-hot products
    are exact in f32 at full matmul precision."""
    lo = (table_i32 & 0xFFFF).astype(jnp.float32)
    hi = jax.lax.shift_right_logical(table_i32, 16).astype(jnp.float32)
    dot = functools.partial(
        jax.lax.dot,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    glo = dot(onehot_f32, lo).astype(jnp.int32)
    ghi = dot(onehot_f32, hi).astype(jnp.int32)
    return jax.lax.shift_left(ghi, 16) | glo


def _query_kernel(
    lo_ref,      # scalar prefetch: [n_windows] first bucket block of window
    hi_ref,      # scalar prefetch: [n_windows] last  bucket block of window
    q_ref,       # [1, QB] sorted queries for window j
    keys_ref,    # [BB, npb*ns] bucket-block key stripes (chain order)
    vals_ref,    # [BB, npb*ns]
    nmax_ref,    # [BB, npb] per-node max keys (EMPTY when inactive)
    mkba_ref,    # [1, BB] bucket fences for the block
    lf_ref,      # [1, BB] lower fences (previous bucket's mkba)
    out_ref,     # [1, QB] values / NOT_FOUND
    *,
    block_b: int,
    npb: int,
    ns: int,
):
    j = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, _MISS)

    active = (i >= lo_ref[j]) & (i <= hi_ref[j])

    @pl.when(active)
    def _process():
        blk = jnp.clip(i, lo_ref[j], hi_ref[j])
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
        # ownership: q must exceed its bucket's lower fence and fall in block
        lf_q = jnp.sum(jnp.where(oh_b, lf, 0), axis=1)
        mine = (b_local < block_b) & (qcol[:, 0] > lf_q)

        # locate node: compare-count over the bucket's node maxes
        nmax_rows = _exact_gather_i32(
            oh_b.astype(jnp.float32), nmax_ref[...]
        )                                                  # [QB, npb]
        nidx = jnp.sum(nmax_rows < qcol, axis=1)           # [QB]
        nidx_c = jnp.minimum(nidx, npb - 1)

        # gather the node row (keys+vals) with a flat one-hot over BB*npb
        flat = b_sel * npb + nidx_c                        # [QB]
        oh_n = (
            jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], block_b * npb), 1)
            == flat[:, None]
        ).astype(jnp.float32)
        krow = _exact_gather_i32(oh_n, keys_ref[...].reshape(block_b * npb, ns))
        vrow = _exact_gather_i32(oh_n, vals_ref[...].reshape(block_b * npb, ns))

        # in-node position by compare-count; hit iff the key matches
        pos = jnp.sum(krow < qcol, axis=1)
        pos_c = jnp.minimum(pos, ns - 1)
        oh_p = (
            jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], ns), 1)
            == pos_c[:, None]
        )
        key_at = jnp.sum(jnp.where(oh_p, krow, 0), axis=1)
        val_at = jnp.sum(jnp.where(oh_p, vrow, 0), axis=1)
        hit = mine & (pos < ns) & (key_at == qcol[:, 0])

        out_ref[0, :] = jnp.where(hit, val_at, out_ref[0, :])


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_b", "interpret"),
)
def flix_point_query_pallas(
    keys3d: jax.Array,      # [nb, npb, ns] int32
    vals3d: jax.Array,      # [nb, npb, ns] int32
    node_max: jax.Array,    # [nb, npb] int32
    mkba: jax.Array,        # [nb] int32
    sorted_queries: jax.Array,  # [Q] int32, ascending
    *,
    block_q: int = DEFAULT_BLOCK_Q,
    block_b: int = DEFAULT_BLOCK_B,
    interpret: bool = False,
) -> jax.Array:
    nb, npb, ns = keys3d.shape
    qn = sorted_queries.shape[0]

    # pad buckets to a block multiple (EMPTY stripes never match)
    nb_p = pl.cdiv(nb, block_b) * block_b
    if nb_p != nb:
        pad = nb_p - nb
        keys3d = jnp.pad(keys3d, ((0, pad), (0, 0), (0, 0)), constant_values=EMPTY)
        vals3d = jnp.pad(vals3d, ((0, pad), (0, 0), (0, 0)))
        node_max = jnp.pad(node_max, ((0, pad), (0, 0)), constant_values=EMPTY)
        mkba = jnp.pad(mkba, (0, pad), constant_values=EMPTY - 1)
    lfence = jnp.concatenate(
        [jnp.array([jnp.iinfo(jnp.int32).min], KEY_DTYPE), mkba[:-1]]
    )

    # pad queries to a window multiple (EMPTY-1 pads resolve to NOT_FOUND)
    qp = pl.cdiv(max(qn, 1), block_q) * block_q
    q = jnp.pad(
        sorted_queries.astype(KEY_DTYPE), (0, qp - qn), constant_values=EMPTY - 1
    )
    n_windows = qp // block_q
    q2 = q.reshape(n_windows, block_q)

    # per-window bucket-block bounds (the flipped-index pre-pass)
    first_b = jnp.searchsorted(mkba, q2[:, 0], side="left")
    last_b = jnp.searchsorted(mkba, q2[:, -1], side="left")
    lo = jnp.minimum(first_b, nb_p - 1).astype(jnp.int32) // block_b
    hi = jnp.minimum(last_b, nb_p - 1).astype(jnp.int32) // block_b

    nb_blocks = nb_p // block_b
    keys2d = keys3d.reshape(nb_p, npb * ns)
    vals2d = vals3d.reshape(nb_p, npb * ns)
    mkba_row = mkba.reshape(1, nb_p)
    lf_row = lfence.reshape(1, nb_p)

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
        ],
        out_specs=pl.BlockSpec((1, block_q), lambda j, i, lo, hi: (j, 0)),
    )

    out = pl.pallas_call(
        functools.partial(_query_kernel, block_b=block_b, npb=npb, ns=ns),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_windows, block_q), jnp.int32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
    )(lo, hi, q2, keys2d, vals2d, node_max, mkba_row, lf_row)
    return out.reshape(qp)[:qn]
