"""Share of the window, in percent, in which the device was idle while
the engine was inside a ``serve.*`` span other than ``serve.sleep``: idle
time the host caused, from the profiler trace (``trace_scopes.py``)."""
import trace_scopes


def read(run):
    trace_scopes.augment(run)
    trace = run.trace or {}
    if "host_idle_s" not in trace or not trace["host_window_s"]:
        return None
    return 100.0 * trace["host_idle_s"] / trace["host_window_s"]
