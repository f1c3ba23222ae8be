"""How unevenly a batch's updates fall on the shards: the mean, over the
batches the window ran, of the largest shard's update count over the mean
shard's (``stats["shard_updates"]``, the updates each shard owns, counted
by the sharded executor).  1 is an even spread; a batch waits for its
busiest shard.  A program without the counter reads nothing."""

import numpy as np


def read(run):
    skews = []
    for b in run.batches:
        counts = b.get("shard_updates")
        if counts is not None and np.mean(counts) > 0:
            skews.append(float(np.max(counts) / np.mean(counts)))
    return float(np.mean(skews)) if skews else None
