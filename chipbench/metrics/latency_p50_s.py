"""The 50th percentile of completion latency over every request due in
the window, timed from its due time; a request that failed or never
completed counts as infinitely late."""

import numpy as np


def read(run):
    lat = run.get("latencies")
    if not lat:
        return None
    return float(np.percentile(np.asarray(lat), 50, method="higher"))
