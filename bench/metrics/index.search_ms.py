"""Mean ``SearchResult.latency_s`` per batch (device-synchronized wall
time of one ``index.search``). Every row of a batch carries the batch's
own latency, so distinct values are distinct batches."""
import numpy as np


def read(run: dict):
    lat = np.asarray(run["window"].index_latency)
    lat = np.unique(lat[np.isfinite(lat)])
    return float(lat.mean() * 1e3) if lat.size else None
