"""Device milliseconds of the fused Pallas kernel (``kernels/flix_apply``)
per batch, in the sharded cell: the summed durations of the kernel's
events on the ``XLA Ops`` line of the chip where they sum largest, over
the batches the window ran.  Each shard runs the kernel over its own
slice of the table."""

from chipbench import shardtrace


def read(run):
    if not run.batches:
        return None
    ns = shardtrace.largest_chip_ns(run, "ops", shardtrace.KERNEL)
    return ns / 1e6 / len(run.batches) if ns else None
