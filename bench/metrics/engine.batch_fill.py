"""Mean coalesced requests per ``index.search`` over the window, from the
engine's counters (``EngineMetrics``: requests / batches)."""


def read(run: dict):
    a, b = run["engine_before"], run["engine_after"]
    batches = b["batches"] - a["batches"]
    queued = ((b["requests"] - b["cached_requests"])
              - (a["requests"] - a["cached_requests"]))
    return queued / batches if batches else None
