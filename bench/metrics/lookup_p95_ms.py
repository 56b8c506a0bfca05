"""``lookup_p95_ms``: 95th percentile of every window batch's time from
submission to both answers (host clock), by linear interpolation."""
import numpy as np


def read(run):
    if not run.latencies_s:
        return None
    return float(np.percentile(run.latencies_s, 95)) * 1e3
