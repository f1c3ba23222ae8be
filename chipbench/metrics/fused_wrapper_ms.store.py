"""Device milliseconds per batch that the fused executor spends outside
its kernel: the durations of its program's events (``jit_flix_apply_pallas``
on the first device's ``XLA Modules`` line) less those of the kernel's
events on the ``XLA Ops`` line, over the batches the window ran.  This is
the wrapper's routing, tile building and whole-table passes
(``kernels/flix_apply._fused_apply``)."""

from chipbench import devtrace

PROGRAM = r"^jit_flix_apply_pallas"
KERNEL = r"^%flix_apply_pallas[.0-9]* = .*tpu_custom_call"


def read(run):
    if run.trace is None or not run.trace.devices or not run.batches:
        return None
    lo, hi = run.trace.window
    dev = run.trace.devices[0]
    program = devtrace.matching_ns(run.trace.modules[dev], PROGRAM, lo, hi)
    kernel = devtrace.matching_ns(run.trace.ops[dev], KERNEL, lo, hi)
    if not program or not kernel:
        return None
    return (program - kernel) / 1e6 / len(run.batches)
