"""Set-up: process start to the start of the window (weights,
calibration, warm-up), host clock."""


def read(run):
    return run.setup["setup_s"]
