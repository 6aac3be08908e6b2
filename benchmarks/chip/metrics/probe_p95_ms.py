"""95th percentile of the latency of every call of the window, in
milliseconds (linear interpolation between order statistics).  Host
clock."""
import numpy as np


def read(run):
    if run.kind != "probe":
        return None
    lat = [(c["t1"] - c["t0"]) * 1e3 for c in run.calls]
    return float(np.percentile(lat, 95))
