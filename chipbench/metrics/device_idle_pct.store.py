"""Share of the traced window in which no operation ran on the device,
in %, in the fused-kernel cell: ``100 * (1 - busy / window)``, busy being the union of the
``XLA Ops`` events' intervals (``devtrace.idle_pct``)."""

from chipbench import devtrace


def read(run):
    return None if run.trace is None else devtrace.idle_pct(run.trace)
