"""Set-up: process start to the start of the measured window (loading,
weights, compilation or cache loads, warm-up)."""


def read(run):
    return run["setup_s"]
