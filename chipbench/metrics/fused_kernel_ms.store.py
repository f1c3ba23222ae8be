"""Device milliseconds of the fused Pallas kernel (``kernels/flix_apply``)
per batch of the window: the summed durations of the kernel's events on the
first device's ``XLA Ops`` line, over the batches the window ran."""

from chipbench import devtrace

KERNEL = r"^%flix_apply_pallas[.0-9]* = .*tpu_custom_call"  # as the trace names it


def read(run):
    if run.trace is None or not run.trace.devices or not run.batches:
        return None
    lo, hi = run.trace.window
    ns = devtrace.matching_ns(run.trace.ops[run.trace.devices[0]], KERNEL, lo, hi)
    return ns / 1e6 / len(run.batches) if ns else None
