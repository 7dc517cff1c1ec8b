"""Model registry: sklearn class name -> TPU kernel.

Replaces the reference's exec/eval-based dynamic import whitelist
(``aws-prod/worker/worker.py:36-57, 436-455`` — flagged in SURVEY.md as a
security hole) with an explicit registry. The target surface is the same 15
names: 5 classifiers, 5 regressors, 5 transformers.
"""

from __future__ import annotations

import threading
from typing import Dict, List

from .base import ModelKernel

_REGISTRY: Dict[str, ModelKernel] = {}


def register_kernel(kernel: ModelKernel) -> ModelKernel:
    _REGISTRY[kernel.name] = kernel
    return kernel


def get_kernel(model_type: str) -> ModelKernel:
    _ensure_populated()
    try:
        return _REGISTRY[model_type]
    except KeyError:
        raise ValueError(
            f"Unsupported model type {model_type!r}. Supported: {sorted(_REGISTRY)}"
        ) from None


def supported_models() -> List[str]:
    _ensure_populated()
    return sorted(_REGISTRY)


_populated = False
_populate_lock = threading.Lock()


def _ensure_populated() -> None:
    """Register every family once. Under a lock, the flag set last: a
    thread that finds it set finds every family (set before the optional
    imports, a process's concurrent first jobs could look a family up
    while another thread was still importing it)."""
    global _populated
    if _populated:
        return
    with _populate_lock:
        if _populated:
            return
        from .linear import LinearRegressionKernel, RidgeKernel
        from .logistic import LogisticRegressionKernel

        register_kernel(LogisticRegressionKernel())
        register_kernel(LinearRegressionKernel())
        register_kernel(RidgeKernel())
        # Remaining families land with their modules (see models/):
        for optional in ("knn", "svm", "trees", "mlp", "transforms", "naive_bayes"):
            try:
                __import__(f"{__package__}.{optional}")
            except ImportError:
                pass
        _populated = True
