"""Mean time a request spent outside the index: from when it was sent to
when its caller resumed, less ``SearchResult.latency_s`` of its batch
(waiting, coalescing, scatter)."""
import numpy as np


def read(run: dict):
    w = run["window"]
    done, sent = np.asarray(w.done), np.asarray(w.sent)
    index = np.asarray(w.index_latency)
    ok = np.isfinite(done) & np.isfinite(index)
    if not ok.any():
        return None
    return float(np.mean(done[ok] - sent[ok] - index[ok]) * 1e3)
