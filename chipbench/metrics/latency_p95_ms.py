"""95th percentile latency, submit to output ready, over every request
sent in the window."""
import numpy as np


def read(run):
    lat = run.window.latencies_s
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
