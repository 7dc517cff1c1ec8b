"""Work of one search of the LogisticRegression family, from shapes alone."""

from __future__ import annotations

from typing import Any, Dict


def logreg_fit(train_rows: float, d: int, c: int, steps: int, n_trials: int) -> float:
    """Gradient steps of multinomial logistic regression: logits A@W and the
    Gram product A'@R, 2 FLOPs a multiply-add, over the training rows of
    every (trial, split)."""
    return steps * n_trials * 2 * (2.0 * train_rows * (d + 1) * c)


def logreg_step_bytes(n: int, d: int, c: int, lanes: int) -> float:
    """Least traffic of one step: the bf16 design matrix once (all lanes can
    share a pass) plus each lane's weights and previous weights read and
    written."""
    dp = d + 1
    return n * dp * 2.0 + lanes * dp * c * 4.0 * 4.0


def logreg_score(held_rows: float, d: int, c: int, n_trials: int) -> float:
    return n_trials * 2.0 * held_rows * (d + 1) * c


def search_work(cell: Dict[str, Any], flops) -> Dict[str, float]:
    ds, est = cell["config"]["dataset"], cell["config"]["estimator"]
    n, d, c = int(ds["n_samples"]), int(ds["n_features"]), int(ds["n_classes"])
    T, K = int(cell["traffic"]["n_iter"]), int(cell["traffic"]["cv"])
    train, held = flops.split_rows(n, K, float(cell["traffic"]["test_size"]))
    steps = int(est["params"]["max_iter"])
    fit = logreg_fit(train, d, c, steps, T)
    return {"fit_flops": fit, "score_flops": logreg_score(held, d, c, T), "kernel_flops": fit,
            "kernel_bytes": steps * logreg_step_bytes(n, d, c, T * (K + 1))}
