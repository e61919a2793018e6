"""99th percentile latency of every request of the window, from its due
time, as the client saw it (a failed or unanswered request waited until
the window closed). Host stalls of 0.1 s and more set it, so it is read
here and not judged end to end."""
import numpy as np


def read(run: dict):
    w = run["window"]
    due, done = np.asarray(w.due), np.asarray(w.done)
    if not due.size:
        return None
    latency = np.where(np.isfinite(done), done, w.closed) - due
    return float(np.percentile(latency, 99) * 1e3)
