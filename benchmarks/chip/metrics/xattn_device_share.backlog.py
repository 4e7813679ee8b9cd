"""Device self time under the model's ``xattn`` scope (cross-attention to
the text memory) over the device's busy time in the profiler trace, in
percent (``trace_scopes.py``)."""
import trace_scopes


def read(run):
    return trace_scopes.share(run, "xattn")
