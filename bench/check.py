"""The numbers that decide ``correct``, from the window's own answers.

Every answer of the window is compared with the exact top-k of
``bench/reference.py`` for recall; a sample drawn from the seed is
rescored in float64 on the host:

* ``score_err`` — widest gap, over the sample and its k ranks, between
  a returned score and minus the float64 squared distance of the row it
  names, over the query's exact k-th distance. It covers the rerank, and
  the engine's scatter: a row handed to the wrong caller scores against
  another query.
* ``rank_gap`` — widest amount by which the j-th returned row lies
  farther than the exact j-th row, over the exact k-th distance. An exact
  search reads rounding here; an approximate one reads its misses.
* ``recall_miss`` — 1 - recall@k over every answer.

A configuration lists under ``correct`` the numbers it compares and the
limit of each; a failed or unanswered request always fails the run.
"""
from __future__ import annotations

import numpy as np

from . import reference

SAMPLE = 1024
CHUNK = 32


def recall_per_request(ids: list, truth: np.ndarray, k: int) -> np.ndarray:
    """Share of the exact top-k each answer holds; 0 for a failed one."""
    out = np.zeros(len(ids))
    for r, got in enumerate(ids):
        if got is not None:
            out[r] = np.intersect1d(got[:k], truth[r][:k]).size / k
    return out


def numbers(w, pool: np.ndarray, truth: np.ndarray, corpus: np.ndarray,
            k: int, seed: int) -> dict[str, float]:
    """``truth`` holds the exact ids of each request's query, in request
    order."""
    rec = recall_per_request(w.ids, truth, k)
    answered = np.flatnonzero([x is not None for x in w.ids])
    rng = np.random.default_rng([seed, 2])
    pick = rng.choice(answered, min(SAMPLE, answered.size), replace=False)
    score_err = rank_gap = 0.0
    for lo in range(0, pick.size, CHUNK):
        rows = pick[lo:lo + CHUNK]
        q = pool[np.asarray(w.pool_idx)[rows]]
        got = np.stack([w.ids[r][:k] for r in rows])
        scores = np.stack([w.scores[r][:k] for r in rows]).astype(np.float64)
        d_got = reference.rescore(corpus, q, got)
        d_ref = reference.rescore(corpus, q, truth[rows][:, :k])
        scale = np.maximum(d_ref.max(axis=1, keepdims=True), 1e-30)
        score_err = max(score_err,
                        float(np.max(np.abs(-scores - d_got) / scale)))
        rank_gap = max(rank_gap, float(np.max(
            (np.sort(d_got, axis=1) - np.sort(d_ref, axis=1)) / scale)))
    return {"score_err": score_err, "rank_gap": rank_gap,
            "recall_miss": float(1.0 - rec.mean()) if rec.size else 1.0,
            "recall": float(rec.mean()) if rec.size else 0.0}


def verdict(found: dict[str, float], failed: int, limits: dict[str, float]
            ) -> tuple[bool, dict[str, dict[str, float]]]:
    """(correct, {name: {"value", "limit"}}) over the configuration's
    limits, with the failed-request count held to 0."""
    checks = {"failed": {"value": failed, "limit": 0}}
    for name, limit in limits.items():
        checks[name] = {"value": found[name], "limit": limit}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
