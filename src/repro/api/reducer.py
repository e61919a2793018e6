"""``Reducer``: one fit/transform/save/load interface for every DR method.

The five baselines (``core.baselines``) and the paper's RAE
(``core.trainer`` + ``core.rae``) historically exposed incompatible APIs —
dataclass ``fit/transform`` vs a raw ``TrainResult``. Here they share one
protocol and one string registry, so callers (serving, benchmarks, the
index factory) never special-case the method.

Persistence layout (one directory per reducer)::

    <dir>/meta.json     # {"kind": ..., "state"/"config": json-able fields}
    <dir>/arrays.npz    # fitted numpy state (weights, train embeddings, ...)

``load_reducer(dir)`` dispatches on ``meta.json["kind"]``.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from typing import Any, Callable, Optional, Protocol, runtime_checkable

import numpy as np

from ..core import baselines

_META = "meta.json"
_ARRAYS = "arrays.npz"


@runtime_checkable
class Reducer(Protocol):
    """Dimensionality reduction map R^n -> R^m.

    A reducer whose fitted map runs on the device may also implement
    ``device_encoder() -> DevicePart`` (``search.twostage``): ``transform``
    as a pure device function of its fitted arrays, which lets
    ``TwoStageIndex`` run a whole search as one program."""

    kind: str
    out_dim: int

    @property
    def fitted(self) -> bool: ...

    def fit(self, train_x: np.ndarray) -> "Reducer": ...

    def transform(self, x: np.ndarray) -> np.ndarray: ...

    def save(self, directory: str) -> None: ...


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
_REDUCERS: dict[str, Callable[..., Reducer]] = {}


def register_reducer(name: str):
    """Class decorator: register under ``name`` (lowercase canonical)."""

    def deco(cls):
        _REDUCERS[name.lower()] = cls
        cls.kind = name.lower()
        return cls

    return deco


def get_reducer(name: str) -> Callable[..., Reducer]:
    try:
        return _REDUCERS[name.lower()]
    except KeyError:
        raise KeyError(
            f"unknown reducer {name!r}; known: {sorted(_REDUCERS)}") from None


def list_reducers() -> list[str]:
    return sorted(_REDUCERS)


def make_reducer(name: str, out_dim: int, **kw) -> Reducer:
    return get_reducer(name)(out_dim=out_dim, **kw)


def load_reducer(directory: str) -> Reducer:
    with open(os.path.join(directory, _META)) as f:
        meta = json.load(f)
    cls = get_reducer(meta["kind"])
    return cls._load(directory, meta)


def _save_meta(directory: str, meta: dict[str, Any]) -> None:
    os.makedirs(directory, exist_ok=True)
    with open(os.path.join(directory, _META), "w") as f:
        json.dump(meta, f, indent=1)


# ---------------------------------------------------------------------------
# Baseline adapters
# ---------------------------------------------------------------------------
class _BaselineReducer:
    """Adapter over a ``core.baselines`` dataclass. Fitted state lives in the
    wrapped dataclass; persistence splits its fields into json scalars and
    npz arrays generically, so every baseline round-trips with no per-class
    code."""

    _impl_cls: type

    def __init__(self, out_dim: int, **kw):
        self._impl = self._impl_cls(out_dim=out_dim, **kw)
        self._fitted = False

    @property
    def out_dim(self) -> int:
        return self._impl.out_dim

    @property
    def fitted(self) -> bool:
        return self._fitted

    def fit(self, train_x: np.ndarray):
        self._impl.fit(np.asarray(train_x, np.float32))
        self._fitted = True
        return self

    def transform(self, x: np.ndarray) -> np.ndarray:
        if not self._fitted:
            raise RuntimeError(f"{self.kind}: transform before fit")
        return np.asarray(self._impl.transform(np.asarray(x, np.float32)))

    def fingerprint(self) -> str:
        """Content hash of the fitted map — same role as
        ``VectorIndex.fingerprint``: ``TwoStageIndex`` folds it into the
        composite hash so swapping reducer weights changes the serving
        cache key. Hashes every field of the wrapped dataclass with the
        same scalar/array split ``save`` uses."""
        if not self._fitted:
            raise RuntimeError(f"{self.kind}: fingerprint before fit")
        h = hashlib.sha1(self.kind.encode())
        for f in dataclasses.fields(self._impl):
            v = getattr(self._impl, f.name)
            h.update(f.name.encode())
            if v is None or isinstance(v, (bool, int, float, str)):
                h.update(str(v).encode())
            else:
                a = np.asarray(v)
                h.update(f"{a.shape}:{a.dtype}".encode())
                h.update(a.tobytes())
        return h.hexdigest()[:16]

    def save(self, directory: str) -> None:
        scalars: dict[str, Any] = {}
        arrays: dict[str, np.ndarray] = {}
        for f in dataclasses.fields(self._impl):
            v = getattr(self._impl, f.name)
            if isinstance(v, np.ndarray):
                arrays[f.name] = v
            elif v is None or isinstance(v, (bool, int, float, str)):
                scalars[f.name] = v
            else:  # jax arrays etc.
                arrays[f.name] = np.asarray(v)
        _save_meta(directory, {"kind": self.kind, "state": scalars,
                               "fitted": self._fitted})
        np.savez(os.path.join(directory, _ARRAYS), **arrays)

    @classmethod
    def _load(cls, directory: str, meta: dict[str, Any]):
        self = cls.__new__(cls)
        state = dict(meta["state"])
        with np.load(os.path.join(directory, _ARRAYS)) as z:
            state.update({k: z[k] for k in z.files})
        self._impl = cls._impl_cls(**state)
        self._fitted = bool(meta.get("fitted", True))
        return self


@register_reducer("pca")
class PCAReducer(_BaselineReducer):
    _impl_cls = baselines.PCA


@register_reducer("rp")
class GaussianRPReducer(_BaselineReducer):
    _impl_cls = baselines.GaussianRP


@register_reducer("mds")
class MDSLinearReducer(_BaselineReducer):
    _impl_cls = baselines.MDSLinear


@register_reducer("isomap")
class IsomapReducer(_BaselineReducer):
    _impl_cls = baselines.Isomap


@register_reducer("umap")
class UMAPLiteReducer(_BaselineReducer):
    _impl_cls = baselines.UMAPLite


# ---------------------------------------------------------------------------
# RAE
# ---------------------------------------------------------------------------
@register_reducer("rae")
class RAEReducer:
    """The paper's RAE behind the same interface as the baselines.

    ``fit`` runs the full distributed trainer (mesh-aware batch sharding,
    optional fault-tolerant checkpointing via ``checkpoint_dir``);
    ``transform`` is the trained encoder f(x) = x W_e. ``in_dim`` is taken
    from the training data, so construction needs only ``out_dim`` — same
    ergonomics as PCA.
    """

    def __init__(self, out_dim: int, *, steps: int = 3000,
                 weight_decay: float = 1e-2, seed: int = 0,
                 batch_size: int = 128, lr_max: float = 1e-3,
                 lr_min: float = 1e-5, explicit_frobenius: bool = False,
                 mesh: Any = None, checkpoint_dir: Optional[str] = None,
                 log_every: int = 10 ** 9):
        self.out_dim = out_dim
        self.steps = steps
        self.weight_decay = weight_decay
        self.seed = seed
        self.batch_size = batch_size
        self.lr_max = lr_max
        self.lr_min = lr_min
        self.explicit_frobenius = explicit_frobenius
        self.mesh = mesh
        self.checkpoint_dir = checkpoint_dir
        self.log_every = log_every
        self.params_: Optional[dict] = None
        self.cfg_ = None
        self.history_: list[dict[str, float]] = []

    @property
    def fitted(self) -> bool:
        return self.params_ is not None

    def _make_cfg(self, in_dim: int):
        from ..configs import RAEConfig

        return RAEConfig(in_dim=in_dim, out_dim=self.out_dim,
                         steps=self.steps, weight_decay=self.weight_decay,
                         seed=self.seed, batch_size=self.batch_size,
                         lr_max=self.lr_max, lr_min=self.lr_min,
                         explicit_frobenius=self.explicit_frobenius)

    def fit(self, train_x: np.ndarray) -> "RAEReducer":
        from ..core import trainer

        train_x = np.asarray(train_x, np.float32)
        self.cfg_ = self._make_cfg(train_x.shape[1])
        ckpt = None
        if self.checkpoint_dir is not None:
            from ..distributed.checkpoint import CheckpointManager

            ckpt = CheckpointManager(self.checkpoint_dir)
        res = trainer.train(self.cfg_, train_x, mesh=self.mesh,
                            log_every=self.log_every,
                            checkpoint_manager=ckpt)
        if ckpt is not None:
            ckpt.wait()
        self.params_ = res.params
        self.history_ = res.history
        return self

    def transform(self, x: np.ndarray) -> np.ndarray:
        if self.params_ is None:
            raise RuntimeError("rae: transform before fit")
        import jax.numpy as jnp

        from ..core import rae

        return np.asarray(rae.rae_encode(self.params_,
                                         jnp.asarray(x, jnp.float32)))

    def device_encoder(self):
        """``transform`` as a device part: :func:`core.rae.encode` of the
        fitted params, at ``EXACT`` as ``transform`` runs it."""
        if self.params_ is None:
            raise RuntimeError("rae: transform before fit")
        from ..core import rae
        from ..search.twostage import DevicePart

        return DevicePart("rae_encode", rae.encode, self.params_)

    def fingerprint(self) -> str:
        """Content hash of the trained encoder (config + weights)."""
        if self.params_ is None:
            raise RuntimeError("rae: fingerprint before fit")
        h = hashlib.sha1(self.kind.encode())
        if self.cfg_ is not None:
            h.update(json.dumps(dataclasses.asdict(self.cfg_),
                                sort_keys=True).encode())
        for k in sorted(self.params_):
            a = np.asarray(self.params_[k])
            h.update(f"{k}:{a.shape}:{a.dtype}".encode())
            h.update(a.tobytes())
        return h.hexdigest()[:16]

    def save(self, directory: str) -> None:
        if self.params_ is None:
            raise RuntimeError("rae: save before fit")
        cfg = dataclasses.asdict(self.cfg_)
        _save_meta(directory, {"kind": self.kind, "config": cfg,
                               "history_tail": self.history_[-1:]})
        np.savez(os.path.join(directory, _ARRAYS),
                 **{k: np.asarray(v) for k, v in self.params_.items()})

    @classmethod
    def _load(cls, directory: str, meta: dict[str, Any]) -> "RAEReducer":
        import jax.numpy as jnp

        from ..configs import RAEConfig

        cfg = RAEConfig(**meta["config"])
        self = cls(out_dim=cfg.out_dim, steps=cfg.steps,
                   weight_decay=cfg.weight_decay, seed=cfg.seed,
                   batch_size=cfg.batch_size, lr_max=cfg.lr_max,
                   lr_min=cfg.lr_min,
                   explicit_frobenius=cfg.explicit_frobenius)
        self.cfg_ = cfg
        with np.load(os.path.join(directory, _ARRAYS)) as z:
            self.params_ = {k: jnp.asarray(z[k]) for k in z.files}
        self.history_ = list(meta.get("history_tail", []))
        return self
