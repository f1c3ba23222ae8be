"""Device milliseconds per batch of the fused wrapper's successor fence
rows (``kernels/flix_apply._fused_apply``, scope ``flix.fused.fence_rows``):
the delete-membership search over every state slot, the surviving minimum
and the suffix scan.  The outermost ops of the executor's runs on the first
device whose instruction carries that scope in the compiled program
(``progtrace``), over the batches the window ran.  A program without the
scope reads nothing."""

from chipbench import progtrace

SCOPE = "flix.fused.fence_rows"


def read(run):
    if run.trace is None or not run.trace.devices or not run.batches:
        return None
    scopes = progtrace.op_scopes(progtrace.executor_text(run.cell, "fused"))
    if SCOPE not in scopes.values():
        return None
    lo, hi = run.trace.window
    program = {progtrace.PROGRAMS["fused"]: scopes}
    labels = progtrace.label_ops(run.trace, run.trace.devices[0], program)
    ns = progtrace.scope_ns(labels, lo, hi).get(SCOPE, 0)
    return ns / 1e6 / len(run.batches) if ns else None
