"""Device milliseconds per batch of the sharded executor's combine
(``core/distributed``, scope ``flix.shard.combine``): the ``pmin``, the one
contribution ``psum`` and the result assembly, with the wait of each
collective for the slowest shard.  The outermost ops that carry the scope
in the compiled program (``progtrace``), on the chip where they sum
largest, over the batches the window ran.  A program without the scope
reads nothing."""

from chipbench import shardtrace

SCOPE = "flix.shard.combine"


def read(run):
    if not run.batches:
        return None
    ns = shardtrace.largest_chip_scope_ns(run, SCOPE)
    return ns / 1e6 / len(run.batches) if ns else None
