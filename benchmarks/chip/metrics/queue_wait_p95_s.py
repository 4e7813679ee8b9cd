"""95th percentile of queue wait (arrival to batch launch), from the
engine's ``ServerMetrics``."""


def read(run):
    return (run.report.get("queue_wait_s") or {}).get("p95")
