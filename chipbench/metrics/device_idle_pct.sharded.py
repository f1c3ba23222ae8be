"""Share of the traced window in which no operation ran on a chip, in %,
in the sharded cell: ``100 * (1 - busy / window)``, busy being the union of
each chip's ``XLA Ops`` events' intervals, averaged over the chips
(``devtrace.idle_pct``)."""

from chipbench import devtrace


def read(run):
    return None if run.trace is None else devtrace.idle_pct(run.trace)
