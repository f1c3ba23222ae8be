"""Bytes a batch needs, counted from the batch and the pre-batch fences.

The count says what any implementation of the store's batch semantics has
to move, never what one kernel's grid happens to move, so the same work
reads the same count whatever executes it:

  * the stripe of every bucket that holds an op of the batch, read once —
    a bucket's keys and values (``npb * ns`` each), its per-node counts and
    maxima (``npb`` each), its node count and its fence;
  * that stripe written back once more for every bucket that holds an
    INSERT or a DELETE;
  * the op arrays (tag, key, val) read, and the result arrays the batch
    returns (value, succ_key, range_start, range_count per op, and the
    dense ``max_results`` RANGE keys and values) written.

An op belongs to the bucket its key routes to under the pre-batch fences
(bucket ``b`` holds keys in ``(mkba[b-1], mkba[b]]``); a RANGE ``[lo, hi)``
to every bucket from ``lo``'s to ``hi - 1``'s.  A successor whose bucket
holds no larger key reads further buckets; they are not counted, so the
count is a floor and a share of the roofline built on it cannot pass 100%.
"""

from __future__ import annotations

import numpy as np

OP_INSERT, OP_DELETE, OP_RANGE, OP_NOP = 0, 1, 5, 4
WORD = 4  # every plane of the state and of a batch is int32


def stripe_bytes(nodes_per_bucket: int, node_size: int) -> int:
    """One bucket's share of the state: keys, vals, node_count, node_max,
    num_nodes and its fence."""
    return WORD * (2 * nodes_per_bucket * node_size + 2 * nodes_per_bucket + 2)


def batch_bytes(mkba, nodes_per_bucket: int, node_size: int, tag, key, val, *,
                max_results: int) -> int:
    """HBM bytes the batch needs (see the module docstring)."""
    mkba = np.asarray(mkba)
    tag, key, val = (np.asarray(a, np.int64) for a in (tag, key, val))
    live = tag != OP_NOP
    tag, key, val = tag[live], key[live], val[live]
    n_ops = int(tag.size)
    top = mkba.size - 1
    first = np.minimum(np.searchsorted(mkba, key, side="left"), top)
    is_r = tag == OP_RANGE
    last = first.copy()
    hi = np.maximum(val[is_r] - 1, key[is_r])
    last[is_r] = np.minimum(np.searchsorted(mkba, hi, side="left"), top)
    touched = np.zeros(mkba.size, bool)
    touched[first[~is_r]] = True
    for a, b in zip(first[is_r], last[is_r]):
        touched[a : b + 1] = True
    updated = np.zeros(mkba.size, bool)
    updated[first[(tag == OP_INSERT) | (tag == OP_DELETE)]] = True
    stripe = stripe_bytes(nodes_per_bucket, node_size)
    ops_io = WORD * (3 + 4) * n_ops + WORD * 2 * max_results
    return int(stripe * (touched.sum() + updated.sum()) + ops_io)
