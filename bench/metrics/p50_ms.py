"""``p50_ms``: median latency of every request answered in the window, from
when it was due (host clock, edge side)."""
import numpy as np


def read(rec):
    lat = [r.done - r.due for r in rec["finished"]]
    return float(np.percentile(lat, 50)) * 1e3 if lat else None
