"""Device milliseconds per batch of the sharded executor's program
(``jit_flix_shard_replicated``, or ``_a2a``, on the ``XLA Modules`` line):
its runs' summed durations on the chip where they sum largest, over the
batches the window ran (``shardtrace.largest_chip_ns``)."""

from chipbench import shardtrace


def read(run):
    if not run.batches:
        return None
    ns = shardtrace.largest_chip_ns(run, "modules", shardtrace.PROGRAM)
    return ns / 1e6 / len(run.batches) if ns else None
