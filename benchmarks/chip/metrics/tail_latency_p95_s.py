"""95th percentile of request latency, scheduled arrival to latent ready,
over every request due in the window (host clock).  Per layer, not end to
end: below the knee a few requests that land behind a started batch set
it, and sub-millisecond timing decides which."""
import numpy as np


def read(run):
    lat = [r["finished"] - r["arrival"] for r in run.requests
           if r["finished"] is not None]
    return float(np.percentile(lat, 95)) if lat else None
