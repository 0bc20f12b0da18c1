"""95th percentile of the window's query latencies, due time to answer
(host clock), ms."""
import numpy as np


def read(run):
    if run.latency_ms is None or not len(run.latency_ms):
        return None
    return float(np.percentile(run.latency_ms, 95))
