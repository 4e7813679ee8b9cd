"""Wall time of the calibration process, spawned to its exit."""


def read(run):
    return run.setup.get("calibrate_s")
