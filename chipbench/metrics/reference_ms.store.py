"""Device milliseconds of the jnp reference executor
(``core/ops._apply_ops_reference``) per batch of the window: the summed
durations of its program's events on the first device's ``XLA Modules``
line, over the batches the window ran."""

from chipbench import devtrace

PROGRAM = r"_apply_ops_reference"


def read(run):
    if run.trace is None or not run.trace.devices or not run.batches:
        return None
    lo, hi = run.trace.window
    ns = devtrace.matching_ns(run.trace.modules[run.trace.devices[0]], PROGRAM, lo, hi)
    return ns / 1e6 / len(run.batches) if ns else None
