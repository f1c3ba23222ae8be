"""The fused kernel's share of the HBM roofline of the cell's chips, in %:
the bytes the window's batches need (``roofline.batch_bytes`` of each
batch on its global pre-batch fences, the count the store cell uses) over
the chips' summed HBM bandwidth (``peaks.json``), over the kernel's device
time on the chip where it sums largest.  The store does integer compares
and moves no floating-point work, so the roofline is bandwidth's alone."""

from chipbench import roofline, shardtrace


def read(run):
    if not run.batches or not run.peaks:
        return None
    ns = shardtrace.largest_chip_ns(run, "ops", shardtrace.KERNEL)
    if not ns:
        return None
    need = sum(
        roofline.batch_bytes(b["mkba"], *run.geometry, b["tag"], b["key"], b["val"],
                             max_results=run.max_results)
        for b in run.batches
    )
    bandwidth = run.cell.chips * run.peaks["hbm_bytes_per_s"]
    return 100.0 * need / bandwidth / (ns / 1e9)
