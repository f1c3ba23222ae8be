"""The fused kernel's share of its HBM roofline, in %: the bytes the
window's batches need (``roofline.batch_bytes``: counted from each batch and
its pre-batch fences, never from the kernel's grid) over the chip's HBM
bandwidth (``peaks.json``), over the kernel's device time in the trace.
The store does integer compares and moves no floating-point work, so the
roofline is bandwidth's alone."""

from chipbench import devtrace, roofline

KERNEL = r"^%flix_apply_pallas[.0-9]* = .*tpu_custom_call"


def read(run):
    if run.trace is None or not run.trace.devices or not run.batches or not run.peaks:
        return None
    lo, hi = run.trace.window
    ns = devtrace.matching_ns(run.trace.ops[run.trace.devices[0]], KERNEL, lo, hi)
    if not ns:
        return None
    need = sum(
        roofline.batch_bytes(b["mkba"], *run.geometry, b["tag"], b["key"], b["val"],
                             max_results=run.max_results)
        for b in run.batches
    )
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / (ns / 1e9)
