"""Set-up: from process start to the opening of the window (loading,
weights from the seed, compression, tuning, compiling, warm-up and the
traffic's ramp)."""


def read(run):
    return run.setup_s
