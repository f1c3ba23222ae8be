"""Set-up seconds on the host clock: process start to the window's start
(imports, finding the chip, making the data, loading or compiling every
program the window runs)."""


def read(run):
    return run.setup_s
