"""Shared pad-sentinel convention for every kernel triple.

Every fused scan/traversal kernel in this package pads — short candidate
lists, k > N, masked adjacency slots, pow2 row pads — and every pad slot
must look the same on the way out: score ``NEG_INF``, id ``PAD_ID``. The
serving cache compares results byte-for-byte across batch sizes and the
two-stage rerank pins pad slots by id, so two kernels disagreeing on the
sentinel (or one drifting to ``-inf`` vs ``-1e30``) is a correctness bug,
not a cosmetic one.

This module is the single definition site. Kernel modules import from
here; ``scripts/lint.py`` (the ``kernel-contract`` checker) rejects any
module under ``repro.kernels`` that re-defines ``NEG_INF`` or spells the
raw ``1e30`` literal.

``NEG_INF`` is a large finite negative instead of ``-inf`` because the
branchless top-k merges run max/argmax sweeps over candidate tiles on the
VPU: with ``-inf`` candidates, a padded tile produces ``inf - inf = nan``
in the ``2qv - v^2 - q^2`` distance form the kernels use, and bf16 inputs
overflow to ``-inf`` earlier than f32. A finite sentinel keeps every
lane's arithmetic defined while still losing every comparison against a
real score.
"""
from __future__ import annotations

#: Pad-slot score: loses every max/merge against any real similarity.
NEG_INF = -1e30

#: Pad-slot id (FAISS convention: index -1 = "no result in this slot").
PAD_ID = -1

#: Additive distance penalty for padded *rows* in positive-distance forms
#: (ops-layer row padding: a padded db/code row must never win the scan).
PAD_PENALTY = 1e30

#: Matmul precision of every distance and encode contraction. A TPU f32
#: dot at default precision rounds its operands to bf16 (one MXU pass),
#: and exact search must agree with an f32 brute force; the CPU computes
#: f32 either way.
EXACT = "highest"


def canonicalize_pads(vals, ids):
    """Pin every pad slot of a merged (vals, ids) pair to the canonical
    ``(NEG_INF, PAD_ID)`` sentinel, numpy or jax alike.

    Pad slots are identified by ``ids < 0`` — the one invariant every
    producer (beam merge, probe scan, k > N tail) already guarantees.
    Works on numpy and jax arrays (dispatches on the module of ``vals``);
    numpy inputs are canonicalized in place and returned.
    """
    import numpy as np

    if isinstance(vals, np.ndarray):
        vals[ids < 0] = NEG_INF
        return vals, ids
    import jax.numpy as jnp

    return jnp.where(ids < 0, NEG_INF, vals), ids
