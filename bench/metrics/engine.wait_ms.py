"""Mean time a queued request waited for its batch over the window: from
when the engine took it to when the search executor took its batch
(queueing and coalescing), from the engine's ``wait_s_total`` counter
(``EngineMetrics``). None where the engine keeps no such counter."""


def read(run: dict):
    a, b = run["engine_before"], run["engine_after"]
    if "wait_s_total" not in b:
        return None
    queued = ((b["requests"] - b["cached_requests"])
              - (a["requests"] - a["cached_requests"]))
    if not queued:
        return None
    return (b["wait_s_total"] - a["wait_s_total"]) / queued * 1e3
