"""Time to warm the cell's bucket programs before the window (compile or
load from the persistent cache, and one run of each)."""


def read(run):
    return run.setup.get("warmup_s")
