"""Mean stage-1 distance evaluations per query, from
``SearchResult.stats``: ``stage1_distance_evals`` under a reducer,
``distance_evals`` for a stage-1-only stack."""
import numpy as np


def read(run: dict):
    vals = [s.get("stage1_distance_evals", s.get("distance_evals"))
            for s in run["window"].stats if s is not None]
    vals = [v for v in vals if v is not None]
    return float(np.mean(vals)) if vals else None
