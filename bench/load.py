"""Traffic: one general generator driven by a mix's parameters.

A mix (``bench/traffic/<name>.json``) is data only. Its keys:

* ``loop``: ``"open"`` (requests due at a fixed ``rate_qps``, whatever
  the service does) or ``"closed"`` (``clients`` callers, each sending
  its next query when the last one returns);
* ``arrivals`` (open loop): ``"poisson"``; an optional ``profile`` of
  ``[seconds, multiplier]`` steps, repeated over the window, modulates
  the rate (``[[0.5, 2.0], [0.5, 0.0]]`` is on/off bursts at twice the
  rate, with the same mean);
* ``draw``: how queries are picked from the pool: ``"passes"`` (the
  whole pool, in an order of the seed's, then again in another, as
  ann-benchmarks sends every query of its test set), ``"uniform"``, or
  ``"zipf"`` with ``zipf_s`` (popularity rank r drawn with weight
  r ** -zipf_s; the seed decides which query holds which rank);
* ``k``.

Every seed gets the same work: an open loop's inter-arrival gaps are the
same fixed set of exponential quantiles, put in another order by the
seed, so each window holds the same number of requests over the same
span. The seed also picks the queries; with ``"passes"`` every window
asks the same queries as often, give or take one pass, in another
order.

The generator runs as coroutines on the engine's own event loop and
calls ``SearchEngine.asearch``, so a request costs the host no thread
hop. An open-loop request is timed from when it was due, not from when
it was sent; how late the generator ran is recorded apart.
"""
from __future__ import annotations

import asyncio
from dataclasses import dataclass, field

import numpy as np

#: how long past the window's close a request may take before it counts
#: as never answered
GRACE_S = 60.0


def _profile_time(profile, seconds: float):
    """(total rate-weighted time of the window, map from rate-weighted to
    real time) of a repeated ``[[seconds, multiplier], ...]`` profile."""
    if not profile:
        return seconds, lambda u: u
    steps = np.array(profile, np.float64)
    if steps.ndim != 2 or steps.shape[1] != 2 or np.any(steps < 0) \
            or steps[:, 0].sum() <= 0 or steps[:, 1].max() <= 0:
        raise ValueError(f"bad rate profile {profile!r}")
    # breakpoints of real time and of rate-weighted time over the window
    period = steps[:, 0].sum()
    reps = int(np.ceil(seconds / period))
    dur, mult = np.tile(steps[:, 0], reps), np.tile(steps[:, 1], reps)
    t = np.concatenate([[0.0], np.cumsum(dur)])
    u = np.concatenate([[0.0], np.cumsum(dur * mult)])
    u_end = float(np.interp(seconds, t, u))

    def to_real(x):
        # the last breakpoint at or below x starts the step x falls in
        # (a step at multiplier 0 spans no rate-weighted time)
        i = np.clip(np.searchsorted(u, x, side="right") - 1, 0, dur.size - 1)
        return t[i] + (x - u[i]) / np.where(mult[i] > 0, mult[i], 1.0)

    return u_end, to_real


def open_schedule(rate_qps: float, seconds: float, seed: int,
                  profile=None) -> np.ndarray:
    """Due times (s from the window's start) of an open loop: a fixed
    multiset of exponential gaps, one request per ``1 / rate_qps`` of
    rate-weighted time, shuffled by the seed and scaled so the last
    request is due inside the window."""
    span, to_real = _profile_time(profile, seconds)
    n = max(1, int(round(rate_qps * span)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate_qps
    gaps = np.random.default_rng(seed).permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return to_real(due * (span / gaps.sum()))


def query_draw(pool: int, n: int, seed: int, draw: str = "uniform",
               zipf_s: float = 1.0) -> np.ndarray:
    """Pool indices of the first ``n`` requests."""
    rng = np.random.default_rng([seed, 1])
    if draw == "passes":
        reps = -(-n // pool)
        return np.concatenate([rng.permutation(pool)
                               for _ in range(reps)])[:n]
    if draw == "uniform":
        return rng.integers(0, pool, n)
    if draw == "zipf":
        w = np.arange(1, pool + 1, dtype=np.float64) ** -float(zipf_s)
        rank = rng.choice(pool, n, p=w / w.sum())
        return rng.permutation(pool)[rank]
    raise ValueError(f"unknown query draw {draw!r}")


def mix_draw(traffic: dict, pool: int, n: int, seed: int) -> np.ndarray:
    """``query_draw`` as the mix sets it."""
    return query_draw(pool, n, seed, traffic.get("draw", "uniform"),
                      traffic.get("zipf_s", 1.0))


@dataclass
class Window:
    """Everything the window's requests produced, in request order."""

    start: float = 0.0            # loop clock at the window's start
    end: float = 0.0              # start + seconds
    closed: float = 0.0           # when the last request ended
    pool_idx: list = field(default_factory=list)
    due: list = field(default_factory=list)
    sent: list = field(default_factory=list)
    done: list = field(default_factory=list)   # nan: failed / unanswered
    ids: list = field(default_factory=list)
    scores: list = field(default_factory=list)
    index_latency: list = field(default_factory=list)
    stats: list = field(default_factory=list)
    errors: list = field(default_factory=list)

    def _new(self, i: int, due: float, sent: float) -> int:
        self.pool_idx.append(int(i))
        self.due.append(due)
        self.sent.append(sent)
        for name in ("done", "index_latency"):
            getattr(self, name).append(float("nan"))
        for name in ("ids", "scores", "stats"):
            getattr(self, name).append(None)
        return len(self.due) - 1

    def _finish(self, r: int, res, t: float) -> None:
        self.done[r] = t
        self.ids[r] = np.asarray(res.indices)[0]
        self.scores[r] = np.asarray(res.scores)[0]
        self.index_latency[r] = float(res.latency_s)
        self.stats[r] = res.stats


async def _one(engine, w: Window, r: int, q: np.ndarray, k: int) -> None:
    loop = asyncio.get_running_loop()
    try:
        res = await engine.asearch(q, k)
    except Exception as e:  # a failed request is counted, never raised
        w.errors.append(f"{type(e).__name__}: {e}")
        return
    w._finish(r, res, loop.time())


async def _gather(tasks, deadline: float, w: Window) -> None:
    loop = asyncio.get_running_loop()
    if tasks:
        _, late = await asyncio.wait(tasks,
                                     timeout=max(0.0, deadline - loop.time()))
        for t in late:
            t.cancel()
        if late:
            w.errors.append(f"{len(late)} requests unanswered "
                            f"{GRACE_S:.0f} s past the window")
            await asyncio.gather(*late, return_exceptions=True)
    w.closed = loop.time()


async def open_loop(engine, pool: np.ndarray, due: np.ndarray,
                    draw: np.ndarray, k: int, seconds: float,
                    lead_s: float = 0.05) -> Window:
    loop = asyncio.get_running_loop()
    w = Window()
    w.start = loop.time() + lead_s
    w.end = w.start + seconds
    tasks = []
    for t_off, i in zip(due, draw):
        t_due = w.start + t_off
        delay = t_due - loop.time()
        if delay > 0:
            await asyncio.sleep(delay)
        r = w._new(i, t_due, loop.time())
        tasks.append(loop.create_task(_one(engine, w, r, pool[i], k)))
    await _gather(tasks, w.end + GRACE_S, w)
    return w


async def closed_loop(engine, pool: np.ndarray, clients: int,
                      seconds: float, k: int, draw: np.ndarray,
                      lead_s: float = 0.05) -> Window:
    loop = asyncio.get_running_loop()
    w = Window()
    w.start = loop.time() + lead_s
    w.end = w.start + seconds
    draws = iter(draw)

    async def client():
        await asyncio.sleep(max(0.0, w.start - loop.time()))
        while loop.time() < w.end:
            i = next(draws)
            now = loop.time()
            r = w._new(i, now, now)
            await _one(engine, w, r, pool[i], k)

    tasks = [loop.create_task(client()) for _ in range(clients)]
    await _gather(tasks, w.end + GRACE_S, w)
    return w


def run_window(engine, traffic: dict, pool: np.ndarray, seconds: float,
               seed: int) -> Window:
    """Drive the mix for ``seconds`` on the engine's loop; block until
    every request has ended (or the grace period has passed)."""
    k = int(traffic["k"])
    if traffic["loop"] == "open":
        if traffic.get("arrivals", "poisson") != "poisson":
            raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
        due = open_schedule(float(traffic["rate_qps"]), seconds, seed,
                            traffic.get("profile"))
        coro = open_loop(engine, pool, due,
                         mix_draw(traffic, len(pool), len(due), seed), k,
                         seconds)
    elif traffic["loop"] == "closed":
        coro = closed_loop(engine, pool, int(traffic["clients"]), seconds,
                           k, mix_draw(traffic, len(pool), 1 << 22, seed))
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    fut = asyncio.run_coroutine_threadsafe(coro, engine.loop)
    return fut.result(timeout=seconds + GRACE_S + 30.0)


def warm_traffic(engine, traffic: dict, pool: np.ndarray, n: int,
                 seed: int) -> None:
    """A few real requests through the whole serving path before the
    window (set-up): ``n`` at once, so every scheduler path has run."""
    k = int(traffic["k"])

    async def burst():
        draw = mix_draw(traffic, len(pool), n, seed + 7)
        await asyncio.gather(*(engine.asearch(pool[i], k) for i in draw))

    asyncio.run_coroutine_threadsafe(burst(), engine.loop).result(
        timeout=600)
