"""Share of the window, in percent, in which no operation ran on the device, from the
profiler trace."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace["idle_share"]
