"""``device.idle`` of the open-loop cells, where it bears on ``p50_ms``:
the share of the traced window in which no op ran on the device,
1 - busy / window, from ``bench/trace.py``'s reduction."""


def read(run: dict):
    t = run["trace"]
    if not t or not t["window_s"] or t["busy_s"] <= 0:
        return None
    return t["idle"]
