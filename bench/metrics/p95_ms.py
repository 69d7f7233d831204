"""95th percentile latency, submit to answer, over every answered request
of the window (not a statistic of rounds or chunks)."""

import numpy as np


def read(run):
    lat = [r["answer"] - r["submit"] for r in run["window"]["requests"]
           if r["answer"] is not None]
    return float(np.percentile(lat, 95) * 1e3) if lat else None
