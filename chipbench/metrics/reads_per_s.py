"""Reads completed per second over the whole window (host clock): every
op of every read-only batch the window ran, over the window's length,
which ends when the batch in flight at ``--seconds`` completes."""


def read(run):
    return run.ops / run.window_s if run.ops and run.window_s > 0 else None
