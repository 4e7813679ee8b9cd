"""95th percentile of admission lag (arrival to the first engine tick at
or after it), from the engine's ``ServerMetrics``."""


def read(run):
    return (run.report.get("admit_lag_s") or {}).get("p95")
