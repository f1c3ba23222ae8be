"""HBM the run needs at its peak, in GiB, on the fullest chip: the
runtime's ``peak_bytes_in_use`` after the window (the table, the batch,
the executor's arguments and outputs) plus the temporaries of the window's
executor as its compiler sizes them (``memory_analysis().temp_size_in_bytes``),
which the runtime's count leaves out.  Keys per chip is the index's
capacity, and those temporaries are what caps it."""


def read(run):
    if run.peak_bytes is None or run.temp_bytes is None:
        return None
    return (run.peak_bytes + run.temp_bytes) / 2**30
