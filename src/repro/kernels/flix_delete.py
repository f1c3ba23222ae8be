"""Pallas TPU kernel for TL-Bulk deletion (paper §4.4, Table 3).

Per bucket block, entirely in VMEM:
  1. membership mark: every stored key is compared against the bucket's
     delete sublist (the tile-ballot analogue is a broadcast equality
     reduce),
  2. in-node compaction: survivors shift left by the number of preceding
     deletions (lane cumsum → one-hot reposition),
  3. chain compaction: emptied nodes drop out of the slot order and their
     slots are reclaimed,
  4. metadata (node_count / node_max / num_nodes) recomputed on the fly.

The wrapper materializes per-bucket delete sublists as a padded [nb, L]
tile (the flipped-indexing pull, same boundaries as the jnp path); the
kernel is then a pure bucket-block map with no cross-block traffic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.batch import bucket_slices, gather_sublists
from repro.core.state import EMPTY, KEY_DTYPE, FliXState

DEFAULT_BLOCK_B = 4
_EMPTY = int(jnp.iinfo(jnp.int32).max)


def _reposition(rows: jax.Array, dest: jax.Array, keep: jax.Array, width: int):
    """new[i] = rows[j] where dest[j] == i and keep[j]; EMPTY elsewhere.

    rows/dest/keep: [..., width].  One-hot masked-sum (gather-free scatter).
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, rows.shape + (width,), rows.ndim)
    oh = (dest[..., None] == lane) & keep[..., None]
    vals = jnp.where(oh, rows[..., None], 0)
    out = jnp.sum(vals, axis=-2)
    filled = jnp.any(oh, axis=-2)
    return jnp.where(filled, out, _EMPTY)


def _delete_kernel(
    keys_ref,   # [BB, npb, ns]
    vals_ref,   # [BB, npb, ns]
    del_ref,    # [BB, L] sorted per-bucket delete sublists (EMPTY-padded)
    okeys_ref,  # [BB, npb, ns]
    ovals_ref,  # [BB, npb, ns]
    ocnt_ref,   # [BB, npb] int32
    omax_ref,   # [BB, npb] int32
    onn_ref,    # [BB, 1] int32
    *,
    npb: int,
    ns: int,
):
    keys = keys_ref[...]
    vals = vals_ref[...]
    dels = del_ref[...]
    bb = keys.shape[0]

    # 1. membership mark: [BB, npb*ns] vs [BB, L] broadcast equality
    flat = keys.reshape(bb, npb * ns)
    hit = jnp.any(flat[:, :, None] == dels[:, None, :], axis=-1)
    hit &= flat != _EMPTY
    deleted = hit.reshape(bb, npb, ns)

    # 2. in-node compaction: dest = #kept before me (cumsum over the lane)
    keep = (~deleted) & (keys != _EMPTY)
    dest = jnp.cumsum(keep.astype(jnp.int32), axis=-1) - 1
    new_keys = _reposition(keys, dest, keep, ns)
    new_vals = jnp.where(new_keys == _EMPTY, 0, _reposition(vals, dest, keep, ns))
    cnt = jnp.sum(keep.astype(jnp.int32), axis=-1)            # [BB, npb]

    # 3. chain compaction: surviving nodes shift into the lowest slots
    nonempty = cnt > 0
    slot_dest = jnp.cumsum(nonempty.astype(jnp.int32), axis=-1) - 1
    slot_lane = jax.lax.broadcasted_iota(jnp.int32, (bb, npb, npb), 2)
    oh = (slot_dest[:, :, None] == slot_lane) & nonempty[:, :, None]
    # move whole rows: [BB, src npb, dst npb] x [BB, src npb, ns]
    moved_k = jnp.sum(jnp.where(oh[..., None], new_keys[:, :, None, :], 0), axis=1)
    moved_v = jnp.sum(jnp.where(oh[..., None], new_vals[:, :, None, :], 0), axis=1)
    row_filled = jnp.any(oh, axis=1)                          # [BB, npb]
    okeys = jnp.where(row_filled[..., None], moved_k, _EMPTY)
    ovals = jnp.where(row_filled[..., None], moved_v, 0)

    # 4. metadata
    ocnt = jnp.sum((okeys != _EMPTY).astype(jnp.int32), axis=-1)
    last = jnp.maximum(ocnt - 1, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (bb, npb, ns), 2)
    omax = jnp.sum(jnp.where(lane == last[..., None], okeys, 0), axis=-1)
    omax = jnp.where(ocnt > 0, omax, _EMPTY)

    okeys_ref[...] = okeys
    ovals_ref[...] = ovals
    ocnt_ref[...] = ocnt
    omax_ref[...] = omax
    onn_ref[...] = jnp.sum((ocnt > 0).astype(jnp.int32), axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def flix_delete_pallas(
    state: FliXState,
    sorted_del_keys: jax.Array,
    *,
    block_b: int = DEFAULT_BLOCK_B,
    interpret: bool = False,
):
    """TL-Bulk deletion via the Pallas kernel. Returns the new FliXState."""
    from repro.core.query import point_query

    nb, npb, ns = state.num_buckets, state.nodes_per_bucket, state.node_size
    cap = state.bucket_capacity
    dk = sorted_del_keys.astype(KEY_DTYPE)
    # pre-filter to PRESENT keys so every bucket's sublist fits its capacity
    # tile (a bucket can't hold more than `cap` live keys, but a raw batch
    # may aim arbitrarily many absent keys at one bucket's range).
    present = point_query(state, dk) != -1
    dk = jnp.sort(jnp.where(present, dk, EMPTY))
    starts, ends = bucket_slices(state, dk)
    del_tile, _, _ = gather_sublists(dk, starts, ends, cap)   # [nb, cap]

    nb_p = pl.cdiv(nb, block_b) * block_b
    keys = state.keys
    vals = state.vals
    if nb_p != nb:
        pad = nb_p - nb
        keys = jnp.pad(keys, ((0, pad), (0, 0), (0, 0)), constant_values=EMPTY)
        vals = jnp.pad(vals, ((0, pad), (0, 0), (0, 0)))
        del_tile = jnp.pad(del_tile, ((0, pad), (0, 0)), constant_values=EMPTY)

    grid = (nb_p // block_b,)
    bmap3 = pl.BlockSpec((block_b, npb, ns), lambda i: (i, 0, 0))
    bmap2 = pl.BlockSpec((block_b, npb), lambda i: (i, 0))

    okeys, ovals, ocnt, omax, onn = pl.pallas_call(
        functools.partial(_delete_kernel, npb=npb, ns=ns),
        grid=grid,
        in_specs=[
            bmap3,
            bmap3,
            pl.BlockSpec((block_b, cap), lambda i: (i, 0)),
        ],
        out_specs=[
            bmap3,
            bmap3,
            bmap2,
            bmap2,
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb_p, npb, ns), jnp.int32),
            jax.ShapeDtypeStruct((nb_p, npb, ns), jnp.int32),
            jax.ShapeDtypeStruct((nb_p, npb), jnp.int32),
            jax.ShapeDtypeStruct((nb_p, npb), jnp.int32),
            jax.ShapeDtypeStruct((nb_p, 1), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
    )(keys, vals, del_tile)

    return FliXState(
        keys=okeys[:nb],
        vals=ovals[:nb],
        node_count=ocnt[:nb],
        node_max=omax[:nb],
        num_nodes=onn[:nb, 0],
        mkba=state.mkba,
        needs_restructure=state.needs_restructure,
    )
