"""Median service time (batch launch to latent ready), from the engine's
``ServerMetrics``."""


def read(run):
    return (run.report.get("service_s") or {}).get("p50")
