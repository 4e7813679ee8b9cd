"""Median request latency: scheduled arrival to latent ready, over every
request due in the window (host clock)."""
import numpy as np


def read(run):
    lat = [r["finished"] - r["arrival"] for r in run.requests
           if r["finished"] is not None]
    return float(np.percentile(lat, 50)) if lat else None
